"""A tiny copy of a cell for CPU tests: the mixtral-8x7b block at two
layers and narrow widths, a few slots, short requests, a short window.
The limit on the mean logit gap is the smoke size's own: on the CPU
the program's products are exact float32, so any reading above
rounding noise is a departure.  A rounds cell is small already and runs
at its own sizes and limits, in a short window."""
import copy

import bench_paths  # noqa: F401  (first: the import path)
import run

SMOKE_SIZES = dict(num_layers=2, d_model=256, num_heads=4, num_kv_heads=2,
                   head_dim=64, d_ff=128, moe_d_ff=128, vocab_size=512,
                   num_experts=4, num_experts_per_tok=2)
SMOKE_LIMIT = 1e-4
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAK = {"bf16_flops_s": 1e12, "hbm_bytes_s": 1e11}


def bench():
    return run.load_json(run.ROOT / "BENCHMARK.json")


def cell(name):
    return {w["name"]: w for w in bench()["workloads"]}[name]


def config(name="mixtral-8x7b"):
    """A served model at the smoke sizes; a rounds configuration (small
    already) as it stands."""
    c = run.load_config(name)
    if c["system"] == "lm_serve":
        c.update(SMOKE_SIZES, serving={"slots": 3, "cache_len": 256,
                                       "prefill_chunk": 2})
    return c


def mix(name):
    m = copy.deepcopy(run.load_json(run.HERE / "traffic" / f"{name}.json"))
    m["trace_seconds"] = 0.5
    if "prompt_tokens" not in m:
        return m
    m["prompt_tokens"].update(min=4, max=24)
    if "median" in m["prompt_tokens"]:
        m["prompt_tokens"]["median"] = 8
    m["output_tokens"].update(min=3, max=12, median=6)
    if "arrivals" in m:
        m["arrivals"]["rate_per_s"] = 4.0
    if "backlog" in m:
        m["backlog"] = 3
    m["check"].update(sample_tokens=40, min_tokens=10,
                      mean_logit_gap=SMOKE_LIMIT)
    return m


def execute(cell_name, seconds=2.0, trace=False, c=None, m=None, **kw):
    w = cell(cell_name)
    return run.execute(bench(), w, 2**31 + 7, seconds, trace, CPU, PEAK,
                       c=c or config(w["config"]), mix=m or mix(w["traffic"]),
                       **kw)
