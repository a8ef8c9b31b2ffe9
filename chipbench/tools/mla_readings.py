#!/usr/bin/env python3
"""The readings a latent-attention cell's limit is set between, on the chip.

    python3 chipbench/tools/mla_readings.py --workload W --seeds 1,2,3 \
        [--seconds 51] [--controls bf16] [--trace 1]

Runs the cell's whole run (``systems/lm_serve_mla.run``: set-up, window,
sample, reference) once per seed, in one process, and reads over the
same sample the program's logit gaps and each control's (the reference
in that precision put in the program's place), with the run's
generated tokens per second, set-up time and peak memory.  With
``--trace 1`` each window is traced as ``run.py --trace 1`` traces it,
and the line adds the device seconds of the serve step and of its ops
under the named scope ``mla``, and their ratio.  One JSON line per
seed on stdout and in ``chiprun_out/mla_readings.jsonl``.  Needs a
TPU, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import clock  # noqa: E402
import run  # noqa: E402

OUT = HERE.parent / "chiprun_out"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--controls", default="bf16")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("mla_readings: no TPU", file=sys.stderr)
        return 1
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(run.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from systems import lm_serve_mla
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    c = run.load_config(cell["config"])
    mix = run.load_json(run.HERE / "traffic" / f"{cell['traffic']}.json")
    controls = tuple(k for k in args.controls.split(",") if k)
    OUT.mkdir(exist_ok=True)
    trace_dir = run.OUT / "trace" / "mla_readings"
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = clock.now()
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            trace_dir.mkdir(parents=True)
        w = lm_serve_mla.run(cell, c, mix, seed, args.seconds,
                             trace_dir=str(trace_dir) if args.trace else None,
                             controls=controls)
        traced = {}
        if args.trace:
            import trace_reduce
            s = trace_reduce.reduce(trace_reduce.load(
                trace_reduce.find_xplane(str(trace_dir))))
            ops = {n.lstrip("%_") for n in w.mla_ops}
            mla_s = sum(t for n, t in s["ops"].items()
                        if n.lstrip("%_") in ops)
            step_s = trace_reduce.module_seconds(s, "serve_chunk_step")
            traced = {"mla_s": mla_s, "serve_step_s": step_s,
                      "mla_share": mla_s / step_s if step_s else None,
                      "busy_s": s["busy_s"], "window_s": s["window_s"],
                      "device_ops": s["device_ops"]}
            shutil.rmtree(trace_dir, ignore_errors=True)
        line = json.dumps({
            "workload": args.workload, "seed": seed,
            "device": jax.devices()[0].device_kind,
            "gen_tokens_s": w.delivered / w.seconds, "setup_s": w.setup_s,
            "memory_peak_bytes": w.memory_peak_bytes,
            "held_assignments": w.held_assignments,
            "checks": w.checks, "info": w.info, **traced,
            "seconds": clock.now() - t0})
        print(line, flush=True)
        with open(OUT / "mla_readings.jsonl", "a") as f:
            f.write(line + "\n")
        del w
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
