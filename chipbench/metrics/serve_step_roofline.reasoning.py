"""Roofline share of the fused serve step of a latent-attention MoE
model, in %: for the macro-steps of the traced part of the window, the
least time the chip could take (per micro-step the larger of the FLOPs
over the bf16 peak and the bytes over the HBM bandwidth; bytes: every
product weight once at the width its products read it, the held
experts counted whole, the busy slots' embedding rows and the norm
scales, each busy slot's live latent rows and the row it writes;
``mla_costs``), over the device time of the serve-step program.

The held experts count whole every micro-step, though about 4% of them
go unrouted in a micro-step at 32 busy slots (each held expert is chosen
by a token with odds 6/64): a program that skipped them could read
about 1.5% above its floor."""
import mla_costs
from readers import traced_steps


def read(w):
    steps = traced_steps(w)
    if not steps or w.trace_summary is None:
        return None
    from trace_reduce import module_seconds
    dev = module_seconds(w.trace_summary, "serve_chunk_step")
    tokens = sum(b - a for s in steps for a, b in s["runs"])
    if dev <= 0 or not tokens:
        return None
    held = w.held_assignments / max(sum(
        b - a for s in w.steps for a, b in s["runs"]), 1)
    least = sum(max(mla_costs.runs_flops(w.c, s["runs"], held)
                    / w.peak["bf16_flops_s"],
                    mla_costs.serve_step_bytes(w.c, s["ticks"], s["runs"])
                    / w.peak["hbm_bytes_s"]) for s in steps)
    return 100.0 * least / dev
