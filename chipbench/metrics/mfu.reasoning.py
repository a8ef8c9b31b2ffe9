"""Model FLOP/s utilization of a latent-attention MoE model served on
its chip share, in %: the forward operations required by every prompt
and generated token the window processed, each at its position
(absorbed attention over its live rows, attention and shared-expert
products, dense MLPs, routers, head), plus the held experts' work for
the assignments the program routed to them (its counter
``serve.held_assignments``), over the window and the chip's bf16 peak."""
import mla_costs
from readers import model_steps


def read(w):
    runs = [r for s in model_steps(w.steps) for r in s["runs"]]
    tokens = sum(b - a for a, b in runs)
    if not tokens:
        return None
    flops = mla_costs.runs_flops(w.c, runs, 0.0) \
        + w.held_assignments * mla_costs.expert_flops(w.c)
    return 100.0 * flops / w.seconds / (w.chips * w.peak["bf16_flops_s"])
