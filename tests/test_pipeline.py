"""Pipelined optimistic rounds: chained rollback, scheduling/backend
determinism, the batch-inference pipeline, and serving-tick revocation.

The acceptance pin lives here: a fraud proof confirmed for round r AFTER
rounds r+1..r+k committed on the optimistic state rolls back the full
chain — state restored to the pre-r snapshot (bit-identical to a clean
twin after honest re-execution), the ledger records the rollback, and
exactly one slash is booked for round r.
"""
import numpy as np
import pytest

from repro.core.attacks import AttackConfig
from repro.core.bmoe import BMoEConfig, BMoESystem
from repro.core.ledger import digest_tree
from repro.core.reputation import ReputationConfig
from repro.data.synthetic import FMNIST, make_image_dataset
from repro.trust.protocol import RoundPhase, TrustConfig


@pytest.fixture(scope="module")
def data():
    xtr, ytr, xte, yte = make_image_dataset(FMNIST, n_train=1500, n_test=300,
                                            seed=0)
    return xtr.reshape(len(xtr), -1), ytr, xte.reshape(len(xte), -1), yte


def _system(attack, trust, seed=0):
    cfg = BMoEConfig(framework="optimistic", attack=attack, pow_difficulty=2,
                     reputation=ReputationConfig(init=0.5, gain=0.01,
                                                 slash=0.4,
                                                 exclusion_threshold=0.2),
                     trust=trust, seed=seed)
    return BMoESystem(cfg)


# ------------------------------------------------- chained rollback pin
def test_fraud_after_descendants_rolls_back_whole_chain(data):
    """Acceptance pin.  window=3 and a malicious edge 2: round 2's fraud
    is only drained at round 3 (round 0's deadline), AFTER round 3 has
    committed on the poisoned state.  The conviction must roll back the
    whole chain {2, 3}: snapshot restored + honest re-execution
    (bit-identical to a clean twin), rollback block in the ledger,
    exactly one slash — for round 2."""
    xtr, ytr, _, _ = data
    atk = AttackConfig(malicious_edges=(2,), attack_prob=1.0, noise_std=5.0)
    trust = TrustConfig(audit_rate=1.0, num_verifiers=1, challenge_window=3)
    s = _system(atk, trust)
    clean = _system(AttackConfig(), trust)
    rng = np.random.default_rng(0)
    digests, backlogs = [], []
    for idx in [rng.integers(0, len(xtr), 64) for _ in range(4)]:
        s.train_round(xtr[idx], ytr[idx])
        clean.train_round(xtr[idx], ytr[idx])
        digests.append((digest_tree(s.experts), digest_tree(clean.experts)))
        backlogs.append(s.protocol.audit_backlog())
    # rounds 0, 1: honest executors — trajectories identical
    assert digests[0][0] == digests[0][1] and digests[1][0] == digests[1][1]
    # round 2: the poisoned update went live (optimistic accept, audit
    # still queued — verification is off the critical path) and the
    # backlog only drained at round 3, in one burst
    assert digests[2][0] != digests[2][1]
    assert backlogs == [[0], [0, 1], [0, 1, 2], []]
    # round 3's drain convicted round 2 after descendant 3 had committed
    assert s.protocol.rounds[2].phase is RoundPhase.ROLLED_BACK
    assert s.protocol.rounds[3].phase is RoundPhase.INVALIDATED
    assert [(r.round_id, r.invalidated) for r in s.protocol.rollbacks] == \
        [(2, [3])]
    # exactly one slash, booked for round 2's executor
    assert [(ev.round_id, ev.edge) for ev in s.protocol.stakes.events] == \
        [(2, 2)]
    assert s.reputation.excluded[2]
    # the ledger records the rollback (and stays verifiable)
    blocks = s.ledger.rollbacks()
    assert len(blocks) == 1
    assert blocks[0].payload["rollback_of"] == 2
    assert blocks[0].payload["chain"] == [2, 3]
    assert blocks[0].payload["slashed"] == [2]
    assert s.ledger.verify_chain()
    # chain re-executed honestly from the pre-round-2 snapshot:
    # bit-identical to the clean twin
    assert digests[3][0] == digests[3][1]
    assert digest_tree(s.gate) == digest_tree(clean.gate)


def test_pipelined_rounds_commit_past_unaudited_ancestors(data):
    """The point of the pipeline: rounds r+1..r+w commit while round r's
    audit is still queued; backlogs drain in bursts; every round still
    reaches a terminal phase on flush."""
    xtr, ytr, _, _ = data
    s = _system(AttackConfig(),
                TrustConfig(audit_rate=0.3, challenge_window=4))
    rng = np.random.default_rng(0)
    backlog_sizes = []
    for idx in [rng.integers(0, len(xtr), 64) for _ in range(9)]:
        s.train_round(xtr[idx], ytr[idx])
        backlog_sizes.append(len(s.protocol.audit_backlog()))
    # the backlog grows between drains instead of emptying every round
    assert max(backlog_sizes) >= 4
    # drains are bursts: far fewer than one per round
    assert 1 <= s.protocol.stats["audit_drains"] <= 3
    s.flush_trust()
    assert s.protocol.pending() == [] and not s._round_ctx
    assert s.protocol.stats["finalized"] == 9


# ------------------------------------------------------- determinism
def _run(trust, atk, xtr, ytr, rounds=8, batch=64):
    s = _system(atk, trust)
    rng = np.random.default_rng(0)
    for idx in [rng.integers(0, len(xtr), batch) for _ in range(rounds)]:
        s.train_round(xtr[idx], ytr[idx])
    s.flush_trust()
    return s


def test_backend_determinism_batched_vs_eager(data):
    """Same TrustConfig.seed => identical audit plans (sampled leaves,
    lazy coins) and identical fraud verdicts under
    audit_backend="batched" vs "eager" — and an identical post-rollback
    model state."""
    xtr, ytr, _, _ = data
    atk = AttackConfig(malicious_edges=(7, 8, 9), attack_prob=1.0,
                       noise_std=5.0)
    a = _run(TrustConfig(audit_rate=0.3, challenge_window=2,
                         audit_backend="batched"), atk, xtr, ytr)
    b = _run(TrustConfig(audit_rate=0.3, challenge_window=2,
                         audit_backend="eager"), atk, xtr, ytr)
    assert set(a.protocol.rounds) == set(b.protocol.rounds)
    for rid in a.protocol.rounds:
        ra, rb = a.protocol.rounds[rid], b.protocol.rounds[rid]
        assert [(r.verifier, r.sampled_leaves, r.lazy)
                for r in ra.reports] == \
               [(r.verifier, r.sampled_leaves, r.lazy) for r in rb.reports]
        assert [(p.leaf_index, p.expert) for p in ra.proofs] == \
               [(p.leaf_index, p.expert) for p in rb.proofs]
        assert ra.phase is rb.phase
    assert [(ev.round_id, ev.edge, ev.amount)
            for ev in a.protocol.stakes.events] == \
           [(ev.round_id, ev.edge, ev.amount)
            for ev in b.protocol.stakes.events]
    for k in ("committed", "finalized", "rolled_back", "invalidated",
              "fraud_proofs"):
        assert a.protocol.stats[k] == b.protocol.stats[k], k
    assert digest_tree(a.experts) == digest_tree(b.experts)


def test_scheduling_determinism_pipelined_vs_synchronous(data):
    """Same seed => identical audit lotteries (keyed by round id, not by
    drain time) and identical fraud verdicts under pipelined vs
    synchronous scheduling; after settlement the model states agree
    bit-for-bit (the chained replay reproduces the synchronous
    trajectory)."""
    xtr, ytr, _, _ = data
    # a single fraud opportunity: executor rotation diverges between the
    # schedules only after a conviction shifts the eligible set, so keep
    # one malicious edge that both schedules see exactly once
    atk = AttackConfig(malicious_edges=(3,), attack_prob=1.0, noise_std=5.0)
    p = _run(TrustConfig(audit_rate=0.5, challenge_window=2,
                         scheduling="pipelined"), atk, xtr, ytr, rounds=6)
    q = _run(TrustConfig(audit_rate=0.5, challenge_window=2,
                         scheduling="synchronous"), atk, xtr, ytr, rounds=6)
    for rid in range(6):
        assert [(r.verifier, r.sampled_leaves)
                for r in p.protocol.rounds[rid].reports] == \
               [(r.verifier, r.sampled_leaves)
                for r in q.protocol.rounds[rid].reports]
    for s_ in (p, q):
        assert [(ev.round_id, ev.edge)
                for ev in s_.protocol.stakes.events] == [(3, 3)]
        assert s_.protocol.rounds[3].phase is RoundPhase.ROLLED_BACK
    # the pipelined run invalidated round 3's descendants; the
    # synchronous one settled round 3 before round 4 existed
    assert p.protocol.stats["invalidated"] > 0
    assert q.protocol.stats["invalidated"] == 0
    assert digest_tree(p.experts) == digest_tree(q.experts)
    assert digest_tree(p.gate) == digest_tree(q.gate)


# ------------------------------------------------- inference pipeline
def test_optimistic_infer_commits_audits_and_slashes(data):
    """Batch inference runs the same commit-challenge-audit pipeline on
    its own round clock: a cheating executor is convicted and slashed
    (shared stake book — it leaves the training rotation too), while
    independent clean batches still finalize (inference rounds do not
    chain: weights are frozen)."""
    xtr, ytr, xte, _ = data
    atk = AttackConfig(malicious_edges=(0,), attack_prob=1.0, noise_std=5.0)
    s = _system(atk, TrustConfig(audit_rate=1.0, num_verifiers=1,
                                 challenge_window=2))
    x = xte[:64]
    bad_logits, _, _ = s.infer(x, attack=atk)       # executor 0 cheats
    good_logits, _, _ = s.infer(x, attack=AttackConfig())
    # the optimistic view returned round 0's corrupted aggregate
    assert not np.allclose(bad_logits, good_logits)
    assert [e["event"] for e in s.infer_log[:2]] == ["commit", "commit"]
    assert s.pending_inference() == [0, 1]
    out = s.flush_trust()
    # round 0 convicted: revoked + slashed; round 1 clean: finalized
    assert s._infer_protocol.rounds[0].phase is RoundPhase.ROLLED_BACK
    assert s._infer_protocol.rounds[1].phase is RoundPhase.FINALIZED
    assert out["infer_finalized"] == [1]
    assert s.pending_inference() == []
    assert [(ev.round_id, ev.edge) for ev in s.protocol.stakes.events] == \
        [(0, 0)]
    assert any(e["event"] == "revoke" and e["round"] == 0
               for e in s.infer_log)
    rb = s.ledger.rollbacks()
    assert len(rb) == 1 and rb[0].payload["domain"] == "infer"
    # the shared stake book bars the convicted executor from BOTH
    # rotations from now on
    assert s.reputation.excluded[0]
    assert s._infer_protocol.pick_executor(2) != 0
    assert s.protocol.pick_executor(0) != 0


# --------------------------------------------------- serving pipeline
def _tiny_engine(**kw):
    from repro.configs import get_config
    from repro.serve.engine import ServingEngine
    from repro.train.loop import init_model
    cfg = get_config("smollm-360m", smoke=True)
    params = init_model(cfg, seed=0)
    return ServingEngine(cfg, params, batch_slots=2, cache_len=64, **kw)


def test_serving_dependent_revocation(data):
    """A revoked session revokes its co-batched (tick-overlapping)
    in-window neighbours — the serving analogue of the training chain
    rollback — while non-overlapping batches finalize untouched.

    The second pair is submitted only once the first is done (its
    windows still open), so the pairs share no tick.  Continuous
    admission into a running batch overlaps them instead (and chains
    the revocation further); that is covered in tests/test_serving.py."""
    from repro.data.synthetic import serving_requests
    trust = TrustConfig(audit_rate=1.0, num_verifiers=1,
                        challenge_window=60)
    eng = _tiny_engine(trust=trust)
    reqs = list(serving_requests(eng.cfg.vocab_size, 4, max_prompt=6,
                                 max_new=4, seed=3))
    for n in (2, 4):
        eng.submit(reqs[n - 2:n])
        while len(eng._done) < n and eng.step():
            pass
    assert eng.completed == {}                   # all windows still open
    pair1 = [reqs[0]["id"], reqs[1]["id"]]
    pair2 = [reqs[2]["id"], reqs[3]["id"]]
    rec = eng.records[pair1[0]]
    rec.tokens = [t ^ 1 for t in rec.tokens]     # executor alters stream
    rep = eng.audit_session(pair1[0])
    assert rep["revoked"]
    assert eng.records[pair1[1]].revoked         # same batch ticks: voided
    assert not eng.records[pair2[0]].revoked     # later batch: untouched
    assert not eng.records[pair2[1]].revoked
    assert any(e["event"] == "revoke_dependent"
               and e["cause"] == pair1[0] for e in eng.session_log)
    done = eng.run()
    assert set(done) == set(pair2)


def test_serving_finality_waits_for_overlapping_streams():
    """Serving-side sequential finality: a short stream whose window
    expires while a co-batched longer stream is still generating (or is
    sealed but unchecked) must not finalize until that neighbour is
    audited — if the neighbour was tampered, both are revoked."""
    from repro.data.synthetic import serving_requests
    trust = TrustConfig(audit_rate=1.0, num_verifiers=1, challenge_window=6)
    eng = _tiny_engine(trust=trust)
    short, long_ = list(serving_requests(eng.cfg.vocab_size, 2,
                                         max_prompt=6, max_new=3, seed=5))
    short["max_new_tokens"] = 1
    long_["max_new_tokens"] = 24                 # outlives short's window
    eng.submit([short, long_])
    eng.step()                                   # fills slots + records
    while len(eng.records[long_["id"]].tokens) < 4 and eng.step():
        pass
    # short finished and its window expired, but its co-batched
    # neighbour is still streaming: deferred, not finalized
    assert short["id"] in eng._done
    assert short["id"] not in eng.completed
    rec = eng.records[long_["id"]]
    rec.tokens[:2] = [t ^ 1 for t in rec.tokens[:2]]   # tamper mid-stream
    done = eng.run()
    # at seal, the deferred neighbour forces long_'s audit: the fraud is
    # confirmed and voids BOTH streams — short never finalizes on top of
    # a corrupted co-batched stream
    assert done == {}
    assert eng.records[long_["id"]].revoked
    assert eng.records[short["id"]].revoked


def test_serving_auto_audit_blocks_tampered_finalization():
    """Audits drain off the critical path at the window deadline: a
    stream tampered inside its window never finalizes, with no manual
    audit call."""
    from repro.data.synthetic import serving_requests
    trust = TrustConfig(audit_rate=1.0, num_verifiers=1,
                        challenge_window=30)
    eng = _tiny_engine(trust=trust)
    reqs = list(serving_requests(eng.cfg.vocab_size, 2, max_prompt=6,
                                 max_new=4, seed=4))
    eng.submit(reqs)
    while len(eng._done) < 2 and eng.step():
        pass
    rid = reqs[0]["id"]
    eng.records[rid].tokens = [t ^ 1 for t in eng.records[rid].tokens]
    done = eng.run()                             # deadline audit catches it
    assert eng.records[rid].revoked and rid not in done
    # co-batched neighbour revoked with it (shared decode ticks)
    assert reqs[1]["id"] not in done
    assert any(e["event"] == "revoke" and e["request"] == rid
               for e in eng.session_log)
