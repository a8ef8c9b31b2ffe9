"""Arithmetic the metric readers share: percentiles, the operations
and bytes of the macro-steps a window ran, and the traced steps."""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

import costs


def p95(values) -> Optional[float]:
    values = list(values)
    return float(np.percentile(values, 95)) if values else None


def model_steps(steps: List[Dict]) -> List[Dict]:
    """Macro-steps that ran the model (an idle verified tick does not)."""
    return [s for s in steps if s["model"]]


def step_flops(c: Dict, s: Dict) -> int:
    return costs.positions_flops(c, s["runs"])


def step_bytes(c: Dict, s: Dict) -> int:
    """HBM bytes the macro-step's micro-steps must move: the weights
    once per micro-step at the width the products read them, the busy
    slots' embedding rows, and each busy slot's cache rows."""
    return costs.serve_step_bytes(c, s["ticks"], s["runs"])


def traced_steps(w) -> List[Dict]:
    t = w.trace
    if t is None or not t.done:
        return []
    return model_steps(w.driver.steps[t.first_step:t.last_step])


def mfu_percent(w) -> Optional[float]:
    flops = sum(step_flops(w.c, s) for s in model_steps(w.steps))
    if not flops:
        return None
    return 100.0 * flops / w.seconds / (w.chips * w.peak["bf16_flops_s"])


def serve_step_roofline_percent(w) -> Optional[float]:
    """Least time the traced macro-steps could take on this chip (the
    larger of operations over peak and bytes over bandwidth, per
    step), over the device time of the serve-step program."""
    steps = traced_steps(w)
    if not steps or w.trace_summary is None:
        return None
    from trace_reduce import module_seconds
    dev = module_seconds(w.trace_summary, "serve_chunk_step")
    if dev <= 0:
        return None
    least = sum(max(step_flops(w.c, s) / w.peak["bf16_flops_s"],
                    step_bytes(w.c, s) / w.peak["hbm_bytes_s"])
                for s in steps)
    return 100.0 * least / dev


def device_idle_percent(w) -> Optional[float]:
    t = w.trace_summary
    if t is None or t["window_s"] <= 0 or t["chips"] == 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
