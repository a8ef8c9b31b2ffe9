#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Everything the run needs is found by name from ``BENCHMARK.json`` at
the root of the checkout:

- the cell (``workloads``) names its configuration and traffic mix;
- ``chipbench/configs/<config>.json`` holds the configuration's sizes,
  and its ``system`` names the module under ``chipbench/systems/`` that
  builds, drives and checks it (and, where it has ``resolve``, reads the
  file's published keys into the names the benchmark uses);
- ``chipbench/traffic/<traffic>.json`` holds the mix's parameters;
- each metric is read by ``chipbench/metrics/<metric>.py``, whose
  ``read(w)`` returns a number or ``None`` (nothing to read); a metric
  ``<base>.<cells>`` with no file of its own is read by
  ``chipbench/metrics/<base>.py``, shared by the cells that report it.

With ``--trace 0`` the result carries the cell's end-to-end metrics;
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
part of the window and from the program's spans and counters.

The run fails, and prints no result, when JAX finds no TPU or fewer
chips than the cell asks for, or when the program under test is not in
the checkout.  The last line of stdout is one JSON object; the numbers
compared to decide ``correct`` are the last lines on stderr and the
last key of that object.
"""
from __future__ import annotations

import clock  # noqa: F401  (first: set-up time counts from here)

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
OUT = ROOT / ".chipbench_out"


def fail(msg: str, code: int = 1) -> int:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    return code


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    """Configuration ``name`` as its system reads it."""
    c = load_json(HERE / "configs" / f"{name}.json")
    system = importlib.import_module(f"systems.{c['system']}")
    return system.resolve(c) if hasattr(system, "resolve") else c


def metric_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists():
        path = HERE / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench, cell: str, trace: bool):
    """The metric entries this cell reports in this kind of run."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads",
                             [cell] if m["moves"] in moved else [])]


def compile_counter():
    """Counts compiles and persistent-cache loads while ``on``."""
    import jax
    state = {"on": False, "n": 0}

    def on_duration(event, *_a, **_k):
        if state["on"] and event == "/jax/core/compile/backend_compile_duration":
            state["n"] += 1

    def on_event(event, **_k):
        if state["on"] and event == "/jax/compilation_cache/cache_hits":
            state["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return state


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        return fail(f"no workload {args.workload!r} in BENCHMARK.json", 2)
    cell = cells[args.workload]
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"the program under test (src/repro) is not in {ROOT}", 2)
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return fail(f"no TPU: JAX's first device is {devs[0].platform!r}")
    if len(devs) < cell["chips"]:
        return fail(f"{args.workload} needs {cell['chips']} chips, JAX "
                    f"reports {len(devs)}")
    from peaks import peaks
    peak = peaks(devs[0].device_kind)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    result = execute(bench, cell, args.seed, args.seconds, bool(args.trace),
                     device, peak)
    print(json.dumps(result), flush=True)
    return 0


def execute(bench, cell, seed: int, seconds: float, trace: bool,
            device: dict, peak: dict, c: dict = None, mix: dict = None):
    """Everything of a run after the look for the chip: set-up, window,
    readers, checks.  Prints the checks to stderr and returns the result
    object.  ``c`` and ``mix`` default to the cell's files."""
    c = c or load_config(cell["config"])
    mix = mix or load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    system = importlib.import_module(f"systems.{c['system']}")
    wanted = cell_metrics(bench, cell["name"], trace)
    readers = {m["name"]: metric_reader(m["name"]) for m in wanted
               if m["name"] != "setup_s"}

    trace_dir = None
    if trace:
        trace_dir = OUT / "trace" / cell["name"]
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    compiles = compile_counter()

    def on_window(opened: bool):
        compiles["on"] = opened

    w = system.run(cell, c, mix, seed, seconds,
                   trace_dir=str(trace_dir) if trace_dir else None,
                   on_window=on_window)
    w.c, w.mix, w.peak, w.chips = c, mix, peak, cell["chips"]
    w.trace_summary = None
    if trace_dir is not None:
        import trace_reduce
        w.trace_summary = trace_reduce.reduce(
            trace_reduce.load(trace_reduce.find_xplane(str(trace_dir))))
        shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = {}
    for m in wanted:
        v = w.setup_s if m["name"] == "setup_s" else readers[m["name"]](w)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = dict(device, memory_peak_bytes=w.memory_peak_bytes)
    result = {"correct": None, "attempted": w.attempted, "failed": w.failed,
              "metrics": metrics, "device": device}
    if w.trace_summary is not None:
        device["busy_s"] = w.trace_summary["busy_s"]
        device["window_s"] = w.trace_summary["window_s"]
        result["breakdown"] = {k: w.trace_summary[k]
                               for k in ("device_ops", "idle_gaps")}
    ok = True
    for chk in w.checks.values():
        v, lim = chk["value"], chk["limit"]
        ok &= v is not None and lim is not None and bool(
            v <= lim if chk["le"] else v >= lim)
    result["correct"] = ok
    print(f"chipbench: {cell['name']} seed {seed}: compiles or cache "
          f"loads inside the window: {compiles['n']}", file=sys.stderr)
    for name, v in getattr(w, "info", {}).items():
        print(f"info {name}: {v!r} (not compared)", file=sys.stderr)
    for name, chk in w.checks.items():
        op = "<=" if chk["le"] else ">="
        print(f"check {name}: {chk['value']!r} (must be {op} "
              f"{chk['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    result["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                        for k, v in w.checks.items()}
    return result

if __name__ == "__main__":
    sys.exit(main())
