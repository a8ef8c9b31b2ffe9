#!/usr/bin/env python3
"""Where a cell's device idle time goes, by the program's own spans, on
the chip.

    python3 chipbench/tools/attribute.py --workload W --seed N \
        --seconds S [--keep-trace]

Runs one traced window of the cell as ``run.py --trace 1`` does, but
with JAX's Python tracer off: it is on by default and records every
Python call in the window, which slows the host's share of each step
and so inflates the host spans and the device's idle time.  Then it
reads the trace twice: ``trace_reduce.reduce`` (the benchmark's
``breakdown``: gaps labelled by its own annotations) and
``span_gaps.attribute`` (gaps split at every annotation edge and booked
to the program's ``repro.*`` spans).  Prints one JSON line with both,
each program span's milliseconds per traced macro-step or round, and
the share of ``repro.step`` / ``repro.round`` that the spans nested in
them cover.  The line is also appended to
``chiprun_out/attribute.jsonl``; ``--keep-trace`` copies the
``.xplane.pb`` to ``chiprun_out/trace/<cell>/``.  Needs a TPU, as
``run.py`` does.
"""
from __future__ import annotations

import argparse
import functools
import importlib
import json
import os
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import run  # noqa: E402

OUT = HERE.parent / "chiprun_out"


def traced_units(w) -> tuple:
    """How many macro-steps that ran the model, or rounds, the trace
    holds, and what they are."""
    if hasattr(w, "driver"):
        from readers import traced_steps
        return len(traced_steps(w)), "macro_step"
    return w.trace.last_step - w.trace.first_step, "round"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--keep-trace", action="store_true")
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("attribute: no TPU", file=sys.stderr)
        return 1
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(run.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace = functools.partial(jax.profiler.start_trace,
                                                 profiler_options=options)
    import span_gaps
    import trace_reduce

    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    c = run.load_config(cell["config"])
    mix = run.load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    system = importlib.import_module(f"systems.{c['system']}")
    logdir = run.OUT / "trace" / "attribute"
    shutil.rmtree(logdir, ignore_errors=True)
    logdir.mkdir(parents=True)
    w = system.run(cell, c, mix, args.seed, args.seconds,
                   trace_dir=str(logdir))
    path = trace_reduce.find_xplane(str(logdir))
    official = trace_reduce.reduce(trace_reduce.load(path))
    got = span_gaps.attribute(span_gaps.load(path))
    n, unit = traced_units(w)
    line = {
        "workload": args.workload, "seed": args.seed,
        "seconds": args.seconds, "correct": all(
            chk["value"] is not None and chk["limit"] is not None and (
                chk["value"] <= chk["limit"] if chk["le"]
                else chk["value"] >= chk["limit"])
            for chk in w.checks.values()),
        "traced": n, "unit": unit,
        "breakdown_idle_gaps": official["idle_gaps"],
        "busy_s": official["busy_s"], "window_s": official["window_s"],
        **got,
        "ms_per_" + unit: {
            k: 1e3 * v[0] / n for k, v in got["spans"].items()} if n else {},
    }
    text = json.dumps(line)
    print(text, flush=True)
    OUT.mkdir(exist_ok=True)
    with open(OUT / "attribute.jsonl", "a") as f:
        f.write(text + "\n")
    if args.keep_trace:
        keep = OUT / "trace" / args.workload
        keep.mkdir(parents=True, exist_ok=True)
        shutil.copy(path, keep / Path(path).name)
    shutil.rmtree(logdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
