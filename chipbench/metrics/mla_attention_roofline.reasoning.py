"""Roofline share of latent attention in the serve step, in %: for the
macro-steps of the traced part of the window, the least time the chip
could take for the attention (per micro-step the larger of its FLOPs
over the bf16 peak and its bytes over the HBM bandwidth: Wq, Wkva, Wkvb
and Wo of every layer at their product width, each busy slot's live
latent rows, position + 1 of them at 576 x 4 B a layer, and the row it
writes; FLOPs of the projections and of the absorbed scores and values
over the live rows; ``mla_costs``), over the device time of the ops
the compiled serve step's HLO tags with the named scope ``mla``."""
import mla_costs
from readers import traced_steps


def _op(name: str) -> str:
    return name.lstrip("%_")


def read(w):
    steps = traced_steps(w)
    ops = {_op(n) for n in getattr(w, "mla_ops", ())}
    if not steps or not ops or w.trace_summary is None:
        return None
    dev = sum(t for n, t in w.trace_summary["ops"].items() if _op(n) in ops)
    if dev <= 0:
        return None
    least = sum(max(mla_costs.mla_step_flops(w.c, s["runs"])
                    / w.peak["bf16_flops_s"],
                    mla_costs.mla_step_bytes(w.c, s["ticks"], s["runs"])
                    / w.peak["hbm_bytes_s"]) for s in steps)
    return 100.0 * least / dev
