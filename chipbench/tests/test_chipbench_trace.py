"""Trace reduction on small hand-made traces: busy union, loops that
hold other ops, clipping to the traced window, idle gaps labelled by
the benchmark's annotations; and the serve step's roofline read from
such a trace."""
from types import SimpleNamespace

import pytest

import bench_paths  # noqa: F401  (first: the import path)
import trace_reduce as tr

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, line, name, t, dur):
    return (plane, line, name, float(t), float(dur))


def small_trace():
    return [
        ev(HOST, "python3", "chipbench.traced", 1_000, 10_000),
        ev(HOST, "python3", "engine.step", 1_000, 6_500),
        ev(HOST, "python3", "submit", 7_500, 500),
        ev(HOST, "python3", "driver.wait", 8_000, 3_000),
        ev(DEV, "XLA Modules", "jit_serve_chunk_step(123)", 2_000, 4_000),
        ev(DEV, "XLA Ops", "%while.3 = (s32[]) while(...)", 2_000, 4_000),
        ev(DEV, "XLA Ops", "%fusion.1 = f32[8] fusion(...)", 2_000, 1_500),
        ev(DEV, "XLA Ops", "%copy.2 = f32[8] copy(...)", 3_500, 2_500),
        ev(DEV, "XLA Ops", "%fusion.1 = f32[8] fusion(...)", 8_500, 1_000),
        # outside the window: never counted
        ev(DEV, "XLA Ops", "%fusion.9 = f32[8] fusion(...)", 20_000, 500),
    ]


def test_busy_modules_ops_and_gaps():
    s = tr.reduce(small_trace())
    assert s["window_s"] == pytest.approx(10e-6)
    # busy: [2000, 6000] and [8500, 9500]
    assert s["busy_s"] == pytest.approx(5e-6)
    assert s["chips"] == 1
    assert tr.module_seconds(s, "serve_chunk_step") == pytest.approx(4e-6)
    ops = dict(s["device_ops"])
    assert ops == pytest.approx({"%fusion.1": 2.5e-6, "%copy.2": 2.5e-6})
    gaps = dict(s["idle_gaps"])
    # [1000, 2000] inside engine.step; [6000, 8500] has its middle
    # (7250) in engine.step; [9500, 11000] in driver.wait
    assert gaps == pytest.approx({"engine.step": 3.5e-6,
                                  "driver.wait": 1.5e-6})


def test_no_window_is_an_error():
    with pytest.raises(ValueError, match="chipbench.traced"):
        tr.reduce([e for e in small_trace() if e[2] != "chipbench.traced"])


def test_busy_is_averaged_over_chips():
    two = small_trace() + [
        ev("/device:TPU:1", "XLA Ops", "%fusion.1 = f32[8] fusion(...)",
           1_000, 10_000)]
    s = tr.reduce(two)
    assert s["chips"] == 2
    assert s["busy_s"] == pytest.approx((5e-6 + 10e-6) / 2)


def test_recorded_v5e_slice():
    """12 ms inside one serve macro-step as the chip's profiler wrote it:
    the device is busy throughout (the scan's loop op covers every
    micro-step), the loop itself is not an op of its own, and the KV
    cache update leads the ops."""
    import json
    from pathlib import Path
    data = json.loads((Path(__file__).parent / "data" /
                       "trace_v5e_slice.json").read_text())
    s = tr.reduce([tuple(e) for e in data["events"]])
    assert s["chips"] == 1
    assert s["window_s"] == pytest.approx(0.012)
    assert s["busy_s"] == pytest.approx(s["window_s"])
    assert tr.module_seconds(s, "serve_chunk_step") == pytest.approx(0.012)
    names = [n for n, _ in s["device_ops"]]
    assert names[0] == "%bitcast_dynamic-update-slice_fusion.3"
    assert not any(n.startswith(tr.CONTAINER_OPS) for n in names)
    assert s["idle_gaps"] == []


@pytest.mark.parametrize("share, reads", [(1.0, 100.0), (0.5, 200.0)])
def test_serve_step_roofline_against_its_floor(share, reads):
    """Two traced macro-steps of 16 micro-steps, 32 busy slots each:
    a serve-step program that takes exactly the least time the count
    allows reads 100%, one that takes half of it 200%."""
    import costs
    import readers
    import run
    from peaks import peaks
    c, peak = run.load_config("mixtral-8x7b"), peaks("TPU v5 lite")
    steps = [{"model": True, "ticks": 16, "runs": [(p, p + 16)] * 32}
             for p in (100, 1000)]
    floor_s = 0.0
    for s in steps:
        moved = costs.serve_step_bytes(c, s["ticks"], s["runs"])
        ops = costs.positions_flops(c, s["runs"])
        assert moved / peak["hbm_bytes_s"] > ops / peak["bf16_flops_s"]
        floor_s += moved / peak["hbm_bytes_s"]
    dev_ns = share * floor_s * 1e9
    events = [ev(HOST, "python3", "chipbench.traced", 0, 2 * dev_ns + 1e6),
              ev(DEV, "XLA Modules", "jit_serve_chunk_step(7)", 1e5,
                 dev_ns / 2),
              ev(DEV, "XLA Modules", "jit_serve_chunk_step(7)",
                 2e5 + dev_ns / 2, dev_ns / 2)]
    w = SimpleNamespace(
        c=c, peak=peak, trace_summary=tr.reduce(events),
        trace=SimpleNamespace(done=True, first_step=1, last_step=3),
        driver=SimpleNamespace(steps=[{"model": False}] + steps))
    assert readers.serve_step_roofline_percent(w) == pytest.approx(reads)
