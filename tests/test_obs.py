"""The repro.obs subsystem: span tracer, metrics registry, and the
regression pins tying the legacy reports to the one registry.

Timing inside these tests goes through metric-bearing spans (the
subsystem measures itself) — direct wall-clock call sites outside
``src/repro/obs/`` and ``benchmarks/common.py`` are CI-linted away.
"""
import contextlib
import glob
import json
import os
import time

import jax
import numpy as np
import pytest

from repro.core.attacks import AttackConfig
from repro.core.bmoe import BMoEConfig, BMoESystem
from repro.obs import (NOOP_SPAN, CounterGroup, MetricsRegistry,
                       Observability, Tracer)
from repro.trust.protocol import TrustConfig

# ------------------------------------------------------------- tracer


def test_nested_spans_child_within_parent():
    tr = Tracer(enabled=True)
    with tr.span("parent", round=1):
        with tr.span("child", expert=3):
            time.sleep(0.002)
        time.sleep(0.002)
    parent, child = {e["name"]: e for e in tr.events}["parent"], \
        {e["name"]: e for e in tr.events}["child"]
    assert child["parent_id"] == parent["span_id"]
    assert parent["parent_id"] == 0
    # the child's interval nests inside the parent's
    assert child["ts_s"] >= parent["ts_s"]
    assert child["ts_s"] + child["dur_s"] <= parent["ts_s"] + parent["dur_s"]
    assert child["dur_s"] <= parent["dur_s"]
    assert child["attrs"] == {"expert": 3}


def test_offpath_child_excluded_from_parent_metric():
    obs = Observability(enabled=True)
    with obs.span("consensus", metric="m.consensus_s") as p:
        time.sleep(0.002)
        with obs.span("audit-drain", metric="m.audit_s", off_path=True):
            time.sleep(0.005)
        time.sleep(0.002)
    audit = obs.metrics.value("m.audit_s")
    consensus = obs.metrics.value("m.consensus_s")
    assert audit >= 0.005
    assert p.off_child_s == pytest.approx(audit)
    # on-path metric + off-path child metric == parent wall
    assert consensus + audit == pytest.approx(p.dur_s)
    assert consensus < p.dur_s


def test_offpath_propagates_through_on_path_ancestors():
    obs = Observability(enabled=True)
    with obs.span("outer", metric="m.outer_s") as outer:
        with obs.span("mid"):                     # on-path, no metric
            with obs.span("leaf", off_path=True):
                time.sleep(0.004)
    assert outer.off_child_s >= 0.004
    assert obs.metrics.value("m.outer_s") == \
        pytest.approx(outer.dur_s - outer.off_child_s)


def test_chrome_trace_roundtrip(tmp_path):
    obs = Observability(enabled=True)
    with obs.span("round", metric="m.round_s", round=7, kind="train"):
        with obs.span("fetch", cid="abc123"):
            pass
    path = tmp_path / "trace.json"
    obs.trace.export_chrome(str(path))
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert len(events) == 2
    by_name = {e["name"]: e for e in events}
    for e in events:
        assert e["ph"] == "X" and e["cat"] == "repro"
        assert e["dur"] >= 0 and e["ts"] >= 0 and e["pid"] == 1
        assert e["tid"] == obs.trace.trace_id
    assert by_name["fetch"]["args"]["parent_id"] \
        == by_name["round"]["args"]["span_id"]
    assert by_name["fetch"]["args"]["cid"] == "abc123"
    assert by_name["round"]["args"]["metric"] == "m.round_s"
    assert by_name["round"]["args"]["round"] == 7
    # JSONL export round-trips the raw event log
    jl = tmp_path / "trace.jsonl"
    assert obs.trace.export_jsonl(str(jl)) == 2
    lines = [json.loads(ln) for ln in jl.read_text().splitlines()]
    assert lines == obs.trace.events


@contextlib.contextmanager
def _capture(logdir):
    """A JAX profiler capture into ``logdir``."""
    jax.profiler.start_trace(str(logdir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _program_events(logdir):
    """(name, start_ns, duration_ns) of every ``repro.*`` host event of
    the capture in ``logdir``."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(logdir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return [(e.name, e.start_ns, e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.")]


def test_noop_mode_zero_allocation_and_bounded(tmp_path):
    obs = Observability()                        # disabled
    assert not obs.enabled
    # no metric, not off-path -> the shared singleton: nothing allocated
    assert obs.span("anything", round=1) is NOOP_SPAN
    assert obs.span("x") is obs.span("y")
    assert obs.metrics.snapshot() == {}
    # a metric-bearing span still times itself even when disabled
    with obs.span("t", metric="m.t_s"):
        pass
    assert obs.metrics.value("m.t_s") > 0
    assert obs.trace.events == []                # ...but records nothing
    # overhead bound: 50k disabled spans, measured by the subsystem
    meter = Observability()
    with meter.span("bound", metric="m.bound_s"):
        for _ in range(50_000):
            with obs.span("hot", round=1):
                pass
    assert meter.metrics.value("m.bound_s") < 0.5   # <10us per no-op span
    # a profiler capture makes every span real, so that it reaches the
    # profiler's timeline; it still records nothing and books no metric
    with _capture(tmp_path):
        sp = obs.span("phase", round=1)
        assert sp is not NOOP_SPAN
        with sp:
            pass
    assert obs.span("phase") is NOOP_SPAN
    assert obs.trace.events == []
    assert set(obs.metrics.snapshot()) == {"m.t_s"}
    assert [e[0] for e in _program_events(tmp_path)] == ["repro.phase"]


# ------------------------------------------------------------- metrics


def test_histogram_percentiles_vs_numpy():
    rng = np.random.default_rng(0)
    xs = rng.uniform(0.0, 10.0, 5000)
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=np.linspace(0.0, 10.0, 2001))
    for x in xs:
        h.observe(float(x))
    snap = h.snapshot()
    for q, key in ((0.50, "p50"), (0.90, "p90"), (0.99, "p99")):
        assert abs(snap[key] - np.quantile(xs, q)) < 0.05, key
    assert snap["p50"] <= snap["p90"] <= snap["p99"]
    assert snap["count"] == len(xs)
    assert snap["sum"] == pytest.approx(xs.sum())
    assert snap["min"] == pytest.approx(xs.min())
    assert snap["max"] == pytest.approx(xs.max())


def test_histogram_constant_stream_is_exact():
    h = MetricsRegistry().histogram("c", buckets=(1.0, 2.0, 4.0))
    for _ in range(100):
        h.observe(3.0)
    s = h.snapshot()
    # percentiles clamp to the observed range: a constant stream is exact
    assert s["p50"] == s["p90"] == s["p99"] == 3.0


def test_counter_group_is_a_registry_view():
    reg = MetricsRegistry()
    stats = CounterGroup({"hits": 0, "misses": 0}, reg, "edge.cache")
    stats["hits"] += 3
    stats["misses"] += 1
    assert dict(stats) == {"hits": 3, "misses": 1}
    assert reg.value("edge.cache.hits") == 3
    assert isinstance(stats["hits"], int)        # int adds stay exact
    with pytest.raises(TypeError):
        del stats["hits"]
    # without a registry it degrades to a plain local dict
    local = CounterGroup({"n": 0})
    local["n"] += 2
    assert dict(local) == {"n": 2}


# -------------------------------------------------- system-level pins

R = 5


def _data(n=512, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(n, 784)).astype(np.float32),
            rng.integers(0, 10, n))


def _run(seed=0, obs=None, attack=None, rounds=R):
    atk = attack if attack is not None else AttackConfig(
        malicious_edges=(2,), attack_prob=1.0, noise_std=5.0)
    cfg = BMoEConfig(framework="optimistic", num_experts=4, num_edges=4,
                     top_k=2, pow_difficulty=1, seed=seed, attack=atk,
                     trust=TrustConfig(audit_rate=0.5, challenge_window=2,
                                       scheduling="pipelined"))
    s = BMoESystem(cfg, obs=obs)
    x, y = _data(seed=seed)
    rng = np.random.default_rng(seed)
    for _ in range(rounds):
        idx = rng.integers(0, len(x), 128)
        s.train_round(x[idx], y[idx])
    s.flush_trust()
    return s


@pytest.fixture(scope="module")
def traced_system():
    obs = Observability(enabled=True)
    return _run(obs=obs), obs


def test_audit_seconds_excluded_from_consensus(traced_system):
    """The satellite pin: pipelined audit drains are booked to
    ``audit_offpath_s`` and structurally subtracted from ``consensus_s``
    (nested off-path spans replaced the old manual subtraction)."""
    s, obs = traced_system
    ev = obs.trace.events
    cons_ids = {e["span_id"] for e in ev if e["name"] == "consensus"}
    drains = [e for e in ev if e["name"] == "audit-drain"]
    nested = [e for e in drains if e["parent_id"] in cons_ids]
    assert drains and nested                 # drains fired, some in-round
    cons_wall = sum(e["dur_s"] for e in ev if e["name"] == "consensus")
    expected = cons_wall - sum(e["dur_s"] for e in nested)
    assert obs.metrics.value("bmoe.consensus_s") \
        == pytest.approx(expected, rel=1e-6)
    assert obs.metrics.value("bmoe.audit_s") \
        == pytest.approx(sum(e["dur_s"] for e in drains), rel=1e-6)
    assert s._timers["audit"] == obs.metrics.value("bmoe.audit_s")


def test_latency_report_total_is_sum_of_components(traced_system):
    s, _ = traced_system
    lr = s.latency_report(1000, 1000, R)
    assert set(lr) == {"compute_s", "comm_s", "consensus_s", "chain_s",
                       "audit_offpath_s", "storage_s", "total_s"}
    assert lr["audit_offpath_s"] > 0
    assert lr["total_s"] == pytest.approx(
        lr["compute_s"] + lr["comm_s"] + lr["consensus_s"] + lr["chain_s"],
        rel=1e-9)                            # audit + storage excluded


def test_legacy_report_shapes_unchanged(traced_system):
    s, _ = traced_system
    assert set(s._timers) == {"compute", "consensus", "chain", "audit",
                              "audit_infer", "storage"}
    sr = s.storage_report()
    assert set(sr) == {"network", "store", "cache", "da", "wall_s"}
    assert set(sr["network"]) >= {"put_requests", "put_bytes",
                                  "get_requests", "get_bytes",
                                  "modeled_put_s", "modeled_get_s"}
    assert set(sr["cache"]) >= {"hits", "misses", "evictions"}
    rep = s.obs_report(1000, 1000, R)
    assert set(rep) == {"metrics", "timers", "storage", "verification",
                        "latency"}
    assert rep["storage"] == sr
    assert rep["latency"] == s.latency_report(1000, 1000, R)
    # the registry snapshot carries every layer's namespace
    names = set(rep["metrics"])
    for prefix in ("bmoe.", "storage.network.", "storage.store.",
                   "trust.train."):
        assert any(n.startswith(prefix) for n in names), prefix


def test_round_spans_cover_wall_and_blocks_link(traced_system):
    s, obs = traced_system
    ev = obs.trace.events
    rounds = [e for e in ev if e["name"] == "round"]
    assert len(rounds) == R
    for r in rounds:
        child = sum(e["dur_s"] for e in ev
                    if e["parent_id"] == r["span_id"])
        assert child >= 0.95 * r["dur_s"]
    # every mined block resolves to a live span in this trace
    ids = {e["span_id"] for e in ev}
    mined = [b for b in s.ledger.blocks if b.index > 0]
    assert mined
    for b in mined:
        assert b.payload["trace_id"] == obs.trace.trace_id
        assert b.payload["span_id"] in ids


def test_metrics_deterministic_and_blocks_unpolluted():
    """Two identical runs with tracing DISABLED: every non-wall-clock
    metric matches exactly (counters and bytes are simulation state, not
    timing) and ledger payloads carry no trace ids — block hashes are
    bit-identical to the pre-obs chain."""
    a, b = _run(seed=0), _run(seed=0)
    sa, sb = a.obs.metrics.snapshot(), b.obs.metrics.snapshot()
    assert set(sa) == set(sb)
    skipped = 0
    for name in sa:
        if name.endswith("_s"):              # wall-clock: machine noise
            skipped += 1
            continue
        assert sa[name] == sb[name], name
    assert skipped < len(sa)                 # the exact set is non-empty
    assert all("trace_id" not in blk.payload for blk in a.ledger.blocks)
    assert [blk.hash for blk in a.ledger.blocks] \
        == [blk.hash for blk in b.ledger.blocks]


def test_serving_engine_token_latency_report():
    """Per-tick spans, and one queue-wait and one time-to-first-token
    observation per request on the obs clock."""
    from repro.configs import get_config
    from repro.data.synthetic import serving_requests
    from repro.serve.engine import ServingEngine
    from repro.train.loop import init_model

    cfg = get_config("qwen2-moe-a2.7b", smoke=True)
    params = init_model(cfg, seed=0)
    obs = Observability(enabled=True)
    eng = ServingEngine(cfg, params, batch_slots=2, cache_len=32, obs=obs)
    reqs = list(serving_requests(cfg.vocab_size, 3, max_prompt=8,
                                 max_new=3, seed=0))
    eng.submit(reqs)
    done = eng.run(max_ticks=50)
    rep = eng.report()
    assert rep == eng.obs_report()
    emitted = int(obs.metrics.value("serve.tokens"))
    assert emitted >= sum(len(v) for v in done.values()) > 0
    assert "token_latency" not in rep and "sessions" not in rep
    # one observation per admitted and per first-token request (three
    # requests through two slots: the third waits for a free slot)
    meta = eng.request_meta
    assert all(meta[r["id"]]["first_token_tick"] >= 0 for r in reqs)
    assert rep["queue_wait"]["count"] == len(reqs)
    assert rep["ttft"]["count"] == len(reqs)
    for r in reqs:
        m = meta[r["id"]]
        assert m["submitted_s"] <= m["admitted_s"] <= m["first_token_s"]
    waits = sorted(meta[r["id"]]["admitted_s"] - meta[r["id"]]["submitted_s"]
                   for r in reqs)
    assert rep["queue_wait"]["max"] == pytest.approx(waits[-1])
    assert waits[-1] > waits[0]                  # the third one queued
    assert rep["ttft"]["sum"] == pytest.approx(sum(
        meta[r["id"]]["first_token_s"] - meta[r["id"]]["submitted_s"]
        for r in reqs))
    # a fused macro-step books to prefill_s while any prompt token is
    # in flight and to decode_s otherwise; short requests may generate
    # entirely inside prefill chunks, so assert over the pair
    assert rep["tick_s"] >= rep["prefill_s"] + rep["decode_s"] > 0
    assert rep["tick_s"] >= rep["replay_s"] > 0
    # one "step" span per fused macro-step (each covers C engine ticks;
    # the final drained step records a span too, before reporting no
    # work left)
    steps = [e for e in obs.trace.events if e["name"] == "step"]
    assert len(steps) >= eng.steps > 0
    assert eng.tick >= eng.steps


# ------------------------------------------------- profiler timeline

ROUND_SPANS = {"round-setup", "round", "fetch", "dispatch", "bookkeeping",
               "consensus", "commitment", "da", "schedule-audit",
               "audit-drain", "settle", "publish", "chain"}
STEP_SPANS = {"step", "admit", "reset-slots", "prepare", "prefill",
              "launch", "wait", "replay", "commit"}


def _verified_engine(obs=None):
    from repro.configs import get_config
    from repro.serve.engine import ServingEngine
    from repro.train.loop import init_model
    cfg = get_config("smollm-360m", smoke=True)
    params = init_model(cfg, seed=0)
    eng = ServingEngine(cfg, params, batch_slots=2, cache_len=32,
                        prefill_chunk=4, obs=obs,
                        trust=TrustConfig(audit_rate=1.0, num_verifiers=1,
                                          challenge_window=2))
    rng = np.random.default_rng(0)
    eng.submit([{"id": i, "prompt": rng.integers(0, cfg.vocab_size, 3),
                 "max_new_tokens": 4} for i in range(3)])
    return eng


@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    """Rounds and one engine macro-step under a profiler capture, with
    tracing off, beside the same rounds run without a capture."""
    logdir = tmp_path_factory.mktemp("capture")
    plain = _run(seed=0)
    eng = _verified_engine()
    with _capture(logdir):
        rounds = _run(seed=0)
        eng.step()
    return plain, rounds, _program_events(logdir)


def test_capture_puts_round_and_step_spans_on_the_profiler(captured):
    _, _, events = captured
    names = {n for n, _, _ in events}
    assert {"repro." + n for n in ROUND_SPANS | STEP_SPANS} <= names
    rounds = [e for e in events if e[0] == "repro.round"]
    assert len(rounds) == R
    # every phase of a round lies inside one of the rounds
    for name, t, dur in events:
        if name in ("repro.fetch", "repro.commitment", "repro.settle"):
            assert any(r[1] <= t and t + dur <= r[1] + r[2]
                       for r in rounds)


def test_capture_leaves_ledger_blocks_bit_identical(captured):
    plain, rounds, _ = captured
    assert all("trace_id" not in b.payload for b in rounds.ledger.blocks)
    assert [b.hash for b in rounds.ledger.blocks] \
        == [b.hash for b in plain.ledger.blocks]


def _on_path_sum(ev, name):
    """Seconds of the spans ``name``, less the off-path spans nested at
    any depth inside them."""
    ids = {e["span_id"] for e in ev if e["name"] == name}
    parent = {e["span_id"]: e["parent_id"] for e in ev}

    def inside(e):
        p = e["parent_id"]
        while p and p not in ids:
            p = parent.get(p, 0)
        return bool(p)
    return (sum(e["dur_s"] for e in ev if e["name"] == name)
            - sum(e["dur_s"] for e in ev if e["off_path"] and inside(e)))


def test_round_phase_metrics_unchanged_by_nested_spans(traced_system):
    """The spans nested in ``consensus`` and ``round`` feed their own
    metrics and leave every enclosing metric at its span's on-path
    wall time."""
    s, obs = traced_system
    ev = obs.trace.events
    m = obs.metrics.value
    assert m("bmoe.consensus_s") == pytest.approx(
        _on_path_sum(ev, "consensus"), rel=1e-9)
    assert m("bmoe.storage_s") == pytest.approx(
        sum(e["dur_s"] for e in ev if e["name"] in ("fetch", "publish")),
        rel=1e-9)
    for span, metric in (("commitment", "bmoe.commitment_s"),
                         ("da", "bmoe.da_s"), ("court", "bmoe.court_s"),
                         ("rollback-replay", "bmoe.replay_s")):
        assert m(metric) == pytest.approx(
            sum(e["dur_s"] for e in ev if e["name"] == span), rel=1e-9)
    assert m("bmoe.replay_s") > 0 and m("bmoe.court_s") > 0
    assert m("bmoe.commitment_s") + m("bmoe.da_s") < m("bmoe.consensus_s")
    # the round's direct children cover it, and so do consensus's
    for parent in ("round", "consensus"):
        for p in (e for e in ev if e["name"] == parent):
            child = sum(e["dur_s"] for e in ev
                        if e["parent_id"] == p["span_id"])
            assert child >= 0.9 * p["dur_s"], parent


def test_step_phase_metrics_unchanged_by_nested_spans():
    obs = Observability(enabled=True)
    eng = _verified_engine(obs)
    eng.run(max_ticks=80)
    ev = obs.trace.events
    m = obs.metrics.value
    assert {e["name"] for e in ev} >= STEP_SPANS | {"audit-drain"}
    for span, metric in (("prefill", "serve.prefill_s"),
                         ("decode", "serve.decode_s"),
                         ("commit", "serve.commit_s")):
        assert m(metric) == pytest.approx(
            sum(e["dur_s"] for e in ev if e["name"] == span), rel=1e-9)
    assert m("serve.tick_s") == pytest.approx(_on_path_sum(ev, "step"),
                                              rel=1e-9)
    # the audit drain, off the path, nests in the replay, whose metric
    # leaves it out just as the step's does
    replay_ids = {e["span_id"] for e in ev if e["name"] == "replay"}
    assert any(e["parent_id"] in replay_ids for e in ev
               if e["name"] == "audit-drain")
    assert m("serve.replay_s") == pytest.approx(_on_path_sum(ev, "replay"),
                                                rel=1e-9)
    for name in ("prefill", "decode"):
        for p in (e for e in ev if e["name"] == name):
            kids = {e["name"] for e in ev if e["parent_id"] == p["span_id"]}
            assert kids == {"launch", "wait"}
