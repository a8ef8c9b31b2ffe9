"""B-MoE training rounds (paper §V) driven through
``repro.core.bmoe.BMoESystem.train_round``.

Set-up builds one system from the configuration and the seed, checks
that its first state is the benchmark's own ``rounds_reference.initial``
weights, and drives it through its first three rounds on batches that
all differ: the steps the reference follows.  It then keeps going until
the malicious executor's first round has been convicted, rolled back
and replayed, so that the court's vote and the replay are compiled
before the window.  The window runs rounds back to back on the same
object.  After it, ``flush_trust`` settles every open round, and the
verdicts are checked: only the malicious executor's rounds are rolled
back, nobody else is slashed, and every round has settled.
"""
from __future__ import annotations

import sys
from types import SimpleNamespace
from typing import Dict

import numpy as np

import clock
import rounds_reference as ref
from traffic import image_batches

TIMERS = ("bmoe.compute_s", "bmoe.consensus_s", "bmoe.audit_s",
          "bmoe.storage_s", "bmoe.chain_s", "bmoe.round_s")


def _annotate(name: str):
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


def system_config(c: Dict, mix: Dict, seed: int):
    from repro.core.attacks import AttackConfig
    from repro.core.bmoe import BMoEConfig
    from repro.core.reputation import ReputationConfig
    from repro.trust.protocol import TrustConfig
    atk = mix["attack"]
    return BMoEConfig(
        framework=c["framework"], dispatch=c["dispatch"],
        num_experts=c["num_experts"], num_edges=c["num_edges"],
        top_k=c["top_k"], expert_kind=c["expert_kind"], in_dim=c["in_dim"],
        num_classes=c["num_classes"], lr=c["lr"],
        capacity_factor=c["capacity_factor"],
        pow_difficulty=c["pow_difficulty"], seed=seed,
        mesh=c.get("mesh", "off"), mesh_shards=c.get("mesh_shards"),
        attack=AttackConfig(malicious_edges=tuple(atk["malicious_edges"]),
                            attack_prob=atk["attack_prob"],
                            noise_std=atk["noise_std"]),
        reputation=ReputationConfig(**c["reputation"]),
        trust=TrustConfig(seed=seed, **c["trust"]))


def _params(s):
    import jax
    return jax.tree_util.tree_map(np.asarray, (s.gate, s.experts))


def _watch_audit_shapes(s):
    """Record the (shape, dtype) of every audit recompute's output while
    set-up runs; returns the record and a function that stops it."""
    seen = set()
    call = s._batched_recompute_call

    def watched(*a):
        out = call(*a)
        seen.add((tuple(out.shape), str(out.dtype)))
        return out
    s._batched_recompute_call = watched

    def stop():
        s._batched_recompute_call = call
    return seen, stop


def _warm_audit_slices(seen) -> int:
    """The audit drain hands back ``out[:n]`` of its (bucket, rows,
    classes) output, one eager slice program per ``n``; a bucket of
    ``b`` holds ``b/2 < n <= b``.  Compile every such slice now, so
    that none compiles inside the window."""
    import jax.numpy as jnp
    count = 0
    for shape, dtype in sorted(seen):
        z = jnp.zeros(shape, dtype)
        for n in range(shape[0] // 2 + 1, shape[0] + 1):
            z[:n].block_until_ready()
            count += 1
    return count


def setup(c: Dict, mix: Dict, seed: int):
    """The system after its first rounds, the batches, and what the
    reference compares: (system, batches, program record)."""
    import jax
    from repro.core.bmoe import BMoESystem
    seed = seed % (2**31)
    marks = [("start", clock.since_start())]
    batches = image_batches(mix["data"], mix["batches"], c["batch"], seed)
    s = BMoESystem(system_config(c, mix, seed))
    audit_shapes, unwatch = _watch_audit_shapes(s)
    p0 = _params(s)
    want = jax.tree_util.tree_map(np.asarray, ref.initial(c, seed))
    same_init = all(np.array_equal(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(p0), jax.tree_util.tree_leaves(want)))
    marks.append(("system", clock.since_start()))
    record = {"p0": want, "losses": [], "same_init": same_init}
    bad = set(mix["attack"]["malicious_edges"])
    for r in range(3):
        x, y = batches[r]
        record["losses"].append(float(s.train_round(x, y)["loss"]))
        if r == 0:
            record["p1"] = _params(s)
    record["p3"] = _params(s)
    marks.append(("three steps", clock.since_start()))
    # keep going until a malicious round has been rolled back, so the
    # court and the replay have run once
    r = 3
    while not any(st.phase.value == "rolled_back"
                  for st in s.protocol.rounds.values()):
        if r >= 3 + 3 * c["num_edges"]:
            raise RuntimeError(f"no conviction of edges {sorted(bad)} in "
                               f"{r} rounds of set-up")
        s.train_round(*batches[r % len(batches)])
        r += 1
    marks.append((f"{r} rounds", clock.since_start()))
    unwatch()
    n = _warm_audit_slices(audit_shapes)
    marks.append((f"{n} audit slices", clock.since_start()))
    print("chipbench: set-up " + ", ".join(
        f"{name} at {t:.2f} s" for name, t in marks), file=sys.stderr)
    return s, batches, record


def drive(s, batches, seconds: float, trace=None) -> SimpleNamespace:
    """Rounds back to back for ``seconds``; the window's record."""
    m = s.obs.metrics
    before = {k: float(m.value(k)) for k in TIMERS}
    first_round = s.round
    steps = []
    start = clock.now()
    end = start + seconds
    while True:
        now = clock.now()
        if trace is not None:
            trace.poll(now - start, len(steps))
        if now >= end:
            break
        t0 = clock.now()
        with _annotate("train_round"):
            s.train_round(*batches[s.round % len(batches)])
        steps.append({"t0": t0, "t1": clock.now()})
    if trace is not None:
        trace.close(len(steps))
    t1 = clock.now()
    return SimpleNamespace(
        t0=start, t1=t1, seconds=t1 - start, steps=steps,
        rounds=s.round - first_round, first_round=first_round,
        counters={k: float(m.value(k)) - before[k] for k in TIMERS})


def verdicts(s, mix: Dict) -> Dict[str, Dict]:
    """After ``flush_trust``: convictions of the malicious executor only,
    every one of its rounds rolled back, every round settled."""
    bad = set(mix["attack"]["malicious_edges"])
    rounds = s.protocol.rounds.values()
    phase = lambda st: st.phase.value
    rolled = [st for st in rounds if phase(st) == "rolled_back"]
    return {
        "convictions": {"value": len(rolled), "limit": 1, "le": False},
        "wrong_convictions": {"value": sum(st.executor not in bad
                                           for st in rolled),
                              "limit": 0, "le": True},
        "missed_attacks": {"value": sum(st.executor in bad
                                        and phase(st) != "rolled_back"
                                        for st in rounds),
                           "limit": 0, "le": True},
        "others_slashed": {"value": sum(ev.edge not in bad
                                        for ev in s.protocol.stakes.events),
                           "limit": 0, "le": True},
        "unsettled": {"value": sum(phase(st) not in (
            "finalized", "rolled_back", "invalidated") for st in rounds),
            "limit": 0, "le": True},
    }


def compared(c: Dict, batches, record, mix: Dict) -> Dict[str, Dict]:
    """The training numbers against the reference's first three steps."""
    got = ref.numbers(c, record, ref.trajectory(
        c, record["p0"], batches, precision=c["matmul_precision"]))
    limits = mix["check"]
    out = {k: {"value": v, "limit": limits[k], "le": True}
           for k, v in got.items()}
    out["init_differs"] = {"value": int(not record["same_init"]),
                           "limit": 0, "le": True}
    return out


def run(cell: Dict, c: Dict, mix: Dict, seed: int, seconds: float,
        trace_dir: str = None, on_window=None) -> SimpleNamespace:
    import jax
    from serve_driver import TraceWindow
    s, batches, record = setup(c, mix, seed)
    trace = (TraceWindow(trace_dir, mix.get("trace_from", 0.4) * seconds,
                         mix.get("trace_seconds", 4.0))
             if trace_dir else None)
    setup_s = clock.since_start()
    if on_window is not None:
        on_window(True)
    win = drive(s, batches, seconds, trace)
    if on_window is not None:
        on_window(False)
    win.setup_s, win.trace, win.batch = setup_s, trace, c["batch"]
    win.memory_peak_bytes = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.devices()[:cell["chips"]])
    with _annotate("flush_trust"):
        s.flush_trust()
    checks = verdicts(s, mix)
    win.attempted = win.rounds
    win.failed = checks["wrong_convictions"]["value"]
    del s
    win.checks = {**compared(c, batches, record, mix), **checks}
    win.info = {}
    return win
