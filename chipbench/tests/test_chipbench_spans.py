"""Idle gaps attributed to the program's spans (``span_gaps``): gaps
split at every annotation edge, the innermost annotation over each
piece, each span's time in the window, and the loader on a real
profiler capture of ``repro.obs`` spans."""
import random
import time

import pytest

import bench_paths  # noqa: F401  (first: the import path)
import span_gaps as sg
import trace_reduce as tr
from test_chipbench_trace import DEV, HOST, ev, small_trace


def test_gaps_split_at_every_annotation_edge():
    """The small trace's gap [6000, 8500] spans engine.step, submit and
    driver.wait: each piece goes to its own label, where the midpoint
    rule of ``trace_reduce`` books all of it to engine.step."""
    s = sg.attribute(small_trace())
    assert dict(s["idle_gaps"]) == pytest.approx({
        "engine.step": 2.5e-6, "submit": 0.5e-6, "driver.wait": 2.0e-6})
    assert s["idle_s"] == pytest.approx(
        s["window_s"] - tr.reduce(small_trace())["busy_s"])
    assert s["spans"] == {} and s["program_share"] == 0.0


def program_trace():
    """The small trace with the program's spans inside engine.step: a
    step that admits, launches and waits, then replays on the host
    while the device idles."""
    return small_trace() + [
        ev(HOST, "python3", "repro.step", 1_200, 6_200),
        ev(HOST, "python3", "repro.admit", 1_200, 600),
        ev(HOST, "python3", "repro.decode", 1_800, 4_300),
        ev(HOST, "python3", "repro.launch", 1_800, 300),
        ev(HOST, "python3", "repro.wait", 2_100, 4_000),
        ev(HOST, "python3", "repro.replay", 6_100, 1_200),
        ev(HOST, "python3", "repro.commit", 6_500, 200),
    ]


def test_gaps_go_to_the_innermost_program_span():
    s = sg.attribute(program_trace())
    gaps = dict(s["idle_gaps"])
    # [1000, 2000]: engine.step alone to 1200, then admit, then launch;
    # [6000, 8500]: wait to 6100, replay (less its commit), commit, the
    # step's own tail, engine.step after it, then submit, driver.wait
    assert gaps == pytest.approx({
        "engine.step": 0.2e-6 + 0.1e-6, "repro.admit": 0.6e-6,
        "repro.launch": 0.2e-6, "repro.wait": 0.1e-6,
        "repro.replay": 1.0e-6, "repro.commit": 0.2e-6,
        "repro.step": 0.1e-6, "submit": 0.5e-6, "driver.wait": 2.0e-6})
    assert sum(gaps.values()) == pytest.approx(s["idle_s"])
    assert s["program_share"] == pytest.approx(2.2 / 5.0)
    assert s["spans"]["repro.step"] == [pytest.approx(6.2e-6), 1]
    assert s["spans"]["repro.commit"] == [pytest.approx(0.2e-6), 1]
    # the step's children cover all of it but 100 ns; the decode's all
    assert s["covered"]["repro.step"] == pytest.approx(6.1 / 6.2)
    assert s["covered"]["repro.decode"] == pytest.approx(1.0)
    assert s["covered"]["repro.replay"] == pytest.approx(0.2 / 1.2)


def test_spans_are_clipped_to_the_window():
    s = sg.attribute(program_trace() + [
        ev(HOST, "python3", "repro.step", 10_500, 2_000),
        ev(HOST, "python3", "repro.step", 12_000, 500)])
    assert s["spans"]["repro.step"] == [pytest.approx(6.7e-6), 2]


def test_segments_match_the_shortest_cover():
    """The sweep labels every elementary segment as a scan of all
    annotations would, on random nested intervals over two threads."""
    rnd = random.Random(5)
    host = []
    for line in ("a", "b"):
        def nest(t0, t1, depth):
            t = t0
            while depth < 4 and t < t1 - 4:
                a = rnd.randint(t, t1 - 4)
                b = rnd.randint(a + 1, min(t1, a + 60))
                host.append((HOST, line, f"{line}{len(host)}", a, b - a))
                nest(a, b, depth + 1)
                t = b + rnd.randint(0, 5)
        nest(0, 1_000, 0)
    cuts, labels = sg.segments(host)
    for (a, b), got in zip(zip(cuts, cuts[1:]), labels):
        mid = (a + b) / 2
        cover = [e for e in host if e[3] <= mid <= e[3] + e[4]]
        shortest = min((e[4] for e in cover), default=None)
        if shortest is None:
            assert got == "other"
        else:
            assert got in {e[2] for e in cover if e[4] == shortest}


def test_recorded_v5e_slice_has_no_idle_and_no_program_spans():
    import json
    from pathlib import Path
    data = json.loads((Path(__file__).parent / "data" /
                       "trace_v5e_slice.json").read_text())
    events = [tuple(e) for e in data["events"]]
    s = sg.attribute(events)
    assert s["window_s"] == pytest.approx(tr.reduce(events)["window_s"])
    assert s["idle_s"] == pytest.approx(0.0, abs=1e-12)
    assert s["idle_gaps"] == [] and s["spans"] == {}


def test_load_reads_program_spans_from_a_capture(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation
    from repro.obs import Observability
    obs = Observability()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation(tr.WINDOW):
            with obs.span("step", metric="serve.tick_s"):
                with obs.span("replay"):
                    time.sleep(0.002)
                jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    events = sg.load(tr.find_xplane(str(tmp_path)))
    assert {e[2] for e in events} >= {tr.WINDOW, "repro.step",
                                      "repro.replay"}
    assert not any(e[0].startswith(DEV) for e in events)
    s = sg.attribute(events)
    assert s["spans"]["repro.step"][1] == 1
    assert s["spans"]["repro.replay"][0] >= 0.002
    assert 0.0 < s["covered"]["repro.step"] <= 1.0
    # trace_reduce's own loader keeps the benchmark's annotations only
    assert not any(e[2].startswith(sg.PREFIX)
                   for e in tr.load(tr.find_xplane(str(tmp_path))))
