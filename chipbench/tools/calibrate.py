#!/usr/bin/env python3
"""One-off measurements that set a cell's traffic and limits, on the chip.

    python3 chipbench/tools/calibrate.py sweep --workload W --seed N \
        --rates 2,4,6 --seconds 20
    python3 chipbench/tools/calibrate.py readings --workload W \
        --seeds 1,2,3 --seconds 15
    python3 chipbench/tools/calibrate.py trace --workload W --seed N \
        --seconds 10

``sweep`` offers the cell's open-loop mix at each rate in turn (one
engine, drained between rates) and prints, per rate, the queue at the
window's close, queue waits in each half of the window and requests
finished per second: the highest rate whose queue does not grow is the
cell's capacity.

``readings`` runs the cell's window once per seed and reads, over the
same sample of finished requests, the program's logit gaps and the
control's (the reference in bfloat16 in the program's place): the
lower and upper readings a limit is set between.

``rounds`` reads a training cell's numbers per seed: the program's
first three steps against the reference's, the control's (the
reference in bfloat16 in the program's place) and the half-batch
fault's (planted in the reference).

``trace`` runs one traced window and writes the names of the trace's
planes and lines, and a short slice of its events, to
``chiprun_out/trace_events.json``.

Results are JSON lines on stdout and in ``chiprun_out/``.  Needs a TPU,
as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import clock  # noqa: E402
import run  # noqa: E402

OUT = HERE.parent / "chiprun_out"


def emit(obj) -> None:
    line = json.dumps(obj)
    print(line, flush=True)
    OUT.mkdir(exist_ok=True)
    with open(OUT / "calibrate.jsonl", "a") as f:
        f.write(line + "\n")


def cell_files(name: str):
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}[name]
    c = run.load_config(cell["config"])
    mix = run.load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    return cell, c, mix


def sweep(args) -> None:
    from readers import p95
    from systems import lm_serve
    cell, c, mix = cell_files(args.workload)
    _, eng = lm_serve.setup(c, mix, args.seed)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        m = dict(mix, arrivals=dict(mix["arrivals"], rate_per_s=rate))
        w = lm_serve.drive(eng, c, m, args.seed + i, args.seconds)
        mid = w.t0 + args.seconds / 2
        waits = lambda rs: p95(1e3 * ((r["admit"] or w.t1) - r["due"])
                               for r in rs)
        done = [r for r in w.records if r["done"]]
        emit({"mode": "sweep", "workload": args.workload, "rate": rate,
              "offered": len(w.records), "finished": len(done),
              "finished_per_s": len(done) / w.seconds,
              "queued_at_close": eng.sched.depth(),
              "active_at_close": eng.sched.num_active,
              "wait_p95_ms_first_half": waits(
                  [r for r in w.records if r["due"] < mid]),
              "wait_p95_ms_second_half": waits(
                  [r for r in w.records if r["due"] >= mid]),
              "ttft_p95_ms": p95(1e3 * ((r["first"] or w.t1) - r["due"])
                                 for r in w.records),
              "ticks_per_s": sum(s["ticks"] for s in w.steps) / w.seconds})
        eng.run()                       # drain before the next rate


def readings(args) -> None:
    from lm_reference import Reference, readings as read_gaps
    from systems import lm_serve
    cell, c, mix = cell_files(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = clock.now()
        params, eng = lm_serve.setup(c, mix, seed)
        w = lm_serve.drive(eng, c, mix, seed, args.seconds)
        chosen = lm_serve.sample(eng, w, mix, seed)
        del eng
        w.driver.eng = None
        gc.collect()
        ref = Reference(c, params)
        g = read_gaps(ref, chosen, args.controls.split(","))
        emit({"mode": "readings", "workload": args.workload, "seed": seed,
              "requests": len(chosen), "readings": g,
              "seconds": clock.now() - t0})
        del ref, params
        gc.collect()


def rounds(args) -> None:
    """Training readings: per seed, the system's first three steps
    against the reference's, and in the program's place the control
    (the reference in bfloat16) and the half-batch fault planted in the
    reference."""
    import jax.numpy as jnp
    import rounds_reference as ref
    from systems import bmoe_rounds
    cell, c, mix = cell_files(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = clock.now()
        s, batches, record = bmoe_rounds.setup(c, mix, seed)
        del s
        gc.collect()
        want = ref.trajectory(c, record["p0"], batches,
                              precision=c["matmul_precision"])
        out = {"program": ref.numbers(c, record, want)}
        for name, kw in (("bf16", {"dtype": jnp.bfloat16}),
                         ("half_batch", {"rows": c["batch"] // 2}),
                         ("highest", {"precision": "highest"})):
            kw.setdefault("precision", c["matmul_precision"])
            out[name] = ref.numbers(c, ref.trajectory(
                c, record["p0"], batches, **kw), want)
        emit({"mode": "rounds", "workload": args.workload, "seed": seed,
              "same_init": record["same_init"], "losses": record["losses"],
              "readings": out, "seconds": clock.now() - t0})


def trace(args) -> None:
    import shutil
    import trace_reduce
    from jax.profiler import ProfileData
    from systems import lm_serve
    cell, c, mix = cell_files(args.workload)
    logdir = run.OUT / "trace" / "calibrate"
    shutil.rmtree(logdir, ignore_errors=True)
    logdir.mkdir(parents=True)
    w = lm_serve.run(cell, c, mix, args.seed, args.seconds,
                     trace_dir=str(logdir))
    path = trace_reduce.find_xplane(str(logdir))
    planes = []
    for plane in ProfileData.from_file(path).planes:
        planes.append({"plane": plane.name, "lines": [
            [line.name, sum(1 for _ in line.events)] for line in plane.lines]})
    events = trace_reduce.load(path)
    summary = trace_reduce.reduce(events)
    win = [e for e in events if e[2] == trace_reduce.WINDOW][0]
    a = win[3] + 0.5 * win[4]
    keep = [e for e in events if e[2] == trace_reduce.WINDOW
            or (a <= e[3] <= a + 0.3e9)]
    OUT.mkdir(exist_ok=True)
    with open(OUT / "trace_events.json", "w") as f:
        json.dump({"planes": planes, "events": keep,
                   "summary": summary}, f)
    emit({"mode": "trace", "workload": args.workload, "seed": args.seed,
          "xplane_bytes": os.path.getsize(path), "events": len(events),
          "kept": len(keep), "summary": summary,
          "traced_steps": [w.trace.first_step, w.trace.last_step],
          "checks": w.checks})
    shutil.rmtree(logdir, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("sweep", "readings", "rounds", "trace"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--controls", default="bf16")
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 1
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(run.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    {"sweep": sweep, "readings": readings, "rounds": rounds,
     "trace": trace}[args.mode](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
