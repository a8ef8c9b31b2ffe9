"""Continuous-batching scheduler + engine edge cases, the engine's
streams against greedy decoding by the full forward pass, the serve
step's output contract, and the trust-verdict contract.

Most model-level tests run on the smallest dense config (smollm-360m
smoke) — scheduling is architecture-agnostic; the reference test also
serves a GQA model with QKV bias and a MoE model with shared experts.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as tfm
from repro.serve.scheduler import SlotScheduler, SlotState
from repro.trust.commitments import MerkleTree
from repro.trust.protocol import TrustConfig
from repro.trust.session import commit_tick, verify_session_inclusion


def _req(rid, plen, new, vocab=64, seed=None):
    rng = np.random.default_rng(rid if seed is None else seed)
    return {"id": rid,
            "prompt": rng.integers(0, vocab, size=plen).astype(np.int32),
            "max_new_tokens": new}


def _model(arch="smollm-360m", **overrides):
    from repro.train.loop import init_model
    cfg = dataclasses.replace(get_config(arch, smoke=True), **overrides)
    return cfg, init_model(cfg, seed=0)


def _engine(batch_slots=2, cache_len=64, **kw):
    from repro.serve.engine import ServingEngine
    cfg, params = _model()
    return ServingEngine(cfg, params, batch_slots=batch_slots,
                         cache_len=cache_len, **kw)


def _greedy_reference(cfg, params, prompt, n):
    """The plain reference: ``n`` tokens of greedy decoding by the full
    forward pass — each the argmax at the last position of
    ``forward_train`` over the prompt and the tokens so far.  MoE layers
    get a capacity that every assignment fits (E / k): a one-token
    decode step never drops one, and a full pass at the configured
    factor could."""
    if cfg.num_experts:
        cfg = dataclasses.replace(
            cfg, capacity_factor=cfg.num_experts / cfg.num_experts_per_tok)
    toks = [int(t) for t in prompt]
    for _ in range(n):
        logits, _ = tfm.forward_train(params, jnp.asarray([toks], jnp.int32),
                                      cfg, remat=False, q_chunk=8,
                                      kv_chunk=8)
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


# ----------------------------------------------------------- scheduler
def test_scheduler_admission_under_full_batch():
    """A full batch admits nothing; eviction frees exactly one slot and
    the head of the queue takes it (FIFO) on the next admit."""
    sched = SlotScheduler(2)
    sched.submit([_req(0, 4, 2), _req(1, 4, 2), _req(2, 4, 2),
                  _req(3, 4, 2)], tick=0)
    assert len(sched.admit(0)) == 2              # slots filled, 2 queued
    assert sched.depth() == 2
    assert sched.admit(1) == []                  # full batch: no admission
    assert sched.release(0, tick=5) == 0
    admitted = sched.admit(6)
    assert [(i, s.request_id) for i, s in admitted] == [(0, 2)]
    assert sched.meta[2]["admitted_tick"] == 6
    assert sched.meta[0]["finished_tick"] == 5
    assert sched.depth() == 1
    assert sched.occupancy() == 1.0


def test_scheduler_prefill_lengths_caps():
    """Chunk consumption is capped by chunk size, remaining prompt, and
    cache headroom — and is 0 for decoding/idle slots."""
    sched = SlotScheduler(3)
    sched.slots[0] = SlotState(request_id=0, pos=0,
                               prompt=np.zeros(20, np.int32), cursor=0,
                               to_generate=1)
    sched.slots[1] = SlotState(request_id=1, pos=6,
                               prompt=np.zeros(8, np.int32), cursor=6,
                               to_generate=1)
    # slot 2 decoding: prompt fully consumed
    sched.slots[2] = SlotState(request_id=2, pos=4,
                               prompt=np.zeros(4, np.int32), cursor=4,
                               to_generate=3)
    n = sched.prefill_lengths(chunk=16, cache_len=10)
    assert n.tolist() == [9, 2, 0]     # headroom 9; remaining prompt 2; 0


# ------------------------------------------------------- tick commitments
def test_commit_tick_inclusion_roundtrip():
    """One append per tick; every session's leaf proves membership in
    the tick root, and a rewritten leaf fails its inclusion proof."""
    leaves = [MerkleTree([f"x{i}"]).root for i in range(3)]
    tc, refs = commit_tick(7, list(zip([10, 11, 12], leaves)))
    assert tc.num_leaves == 3 and tc.request_ids == (10, 11, 12)
    for rid, leaf in zip([10, 11, 12], leaves):
        assert refs[rid].verify(leaf)
        assert refs[rid].root == tc.root and refs[rid].tick == 7
    assert not refs[10].verify(leaves[1])
    # session-side check: index 1 rewritten post-hoc
    tampered = [leaves[0], leaves[2], leaves[2]]
    assert verify_session_inclusion(
        tampered, [refs[10], refs[11], refs[12]], [0, 1, 2]) == [1]


def test_commit_tick_rejects_bad_entries():
    with pytest.raises(ValueError):
        commit_tick(0, [])
    with pytest.raises(ValueError):
        commit_tick(0, [(1, "a"), (1, "b")])    # one token per stream/tick


# ----------------------------------------------------------- the engine
def test_engine_warmup_compiles_every_bucket_without_state_change():
    """``warmup()`` visits every pow2 width bucket up to prefill_chunk
    and leaves generation unchanged — a warmed engine produces the same
    stream as a cold one."""
    cold = _engine(prefill_chunk=8)
    warm = _engine(prefill_chunk=8)
    assert warm.warmup() == 4          # C in {1, 2, 4, 8}
    assert warm.tick == 0 and warm.steps == 0
    reqs = [_req(0, 11, 4), _req(1, 3, 4)]
    assert warm.run() == {} and (warm.submit(reqs) or warm.run()) \
        == (cold.submit(reqs) or cold.run())


def test_engine_zero_max_new_tokens():
    """A zero-token request still runs prefill, finishes with an empty
    output, and (verified) still seals a one-leaf commitment that
    finalizes through the normal window."""
    trust = TrustConfig(audit_rate=1.0, num_verifiers=1, challenge_window=2)
    eng = _engine(trust=trust)
    eng.submit([_req(0, 6, 0), _req(1, 6, 3)])
    done = eng.run()
    assert done[0] == [] and len(done[1]) == 3
    rec = eng.records[0]
    assert rec.finalized and len(rec.leaves) == 1   # boundary token sealed
    assert any(e["event"] == "commit" and e["request"] == 0
               for e in eng.session_log)


def test_engine_eviction_of_revoked_session_mid_window():
    """Revoking a session mid-challenge-window: the request never
    reaches ``completed``, its window entry dies, and its former slot is
    reused by later requests."""
    trust = TrustConfig(audit_rate=1.0, num_verifiers=1,
                        challenge_window=40)
    eng = _engine(trust=trust)
    eng.submit([_req(0, 5, 2), _req(1, 5, 2)])
    while 0 not in eng._done and eng.step():
        pass
    assert len(eng._window) >= 1                 # window still open
    eng.records[0].tokens = [t ^ 1 for t in eng.records[0].tokens]
    rep = eng.audit_session(0)                   # mid-window audit
    assert rep["revoked"] and len(eng._window) <= 1
    eng.submit([_req(2, 5, 2)])                  # reuses the freed slot
    done = eng.run()
    assert 0 not in done and 2 in done
    assert eng.records[0].revoked and not eng.records[0].finalized


def test_engine_no_queue_starvation_under_long_prompts():
    """Chunked prefill + continuous admission: a request queued behind a
    long-prompt request gets its first token before the long request
    finishes, every stream is the plain reference's, and the long
    prompt costs ~len/chunk prefill dispatches instead of len decode
    ticks."""
    reqs = [_req(0, 48, 2), _req(1, 4, 2), _req(2, 4, 2), _req(3, 4, 2)]
    eng = _engine(prefill_chunk=16)
    eng.submit([dict(r, prompt=r["prompt"].copy()) for r in reqs])
    done = eng.run()
    assert set(done) == {0, 1, 2, 3}
    for r in reqs:
        assert done[r["id"]] == _greedy_reference(
            eng.cfg, eng.params, r["prompt"], r["max_new_tokens"]), r["id"]
    # request 2 waited only for request 1's slot, not for the long
    # prompt's whole batch to drain
    meta = eng.request_meta
    assert meta[2]["first_token_tick"] < meta[0]["finished_tick"]
    # the long prompt costs ceil(48/16)=3 fused dispatches for its
    # prefill instead of 48 single-token calls: total compiled-call
    # count stays far below the tick count
    assert eng.steps < eng.tick


@pytest.mark.parametrize("arch,overrides", [
    ("smollm-360m", {}),                          # dense
    ("qwen2.5-3b", {}),                           # GQA with QKV bias
    ("qwen2-moe-a2.7b", {"padded_num_experts": 0}),   # MoE, shared experts
])
def test_engine_streams_equal_greedy_reference(arch, overrides):
    """Staggered, co-batched requests — more requests than slots,
    prompts longer than ``prefill_chunk`` mixed with short ones, some
    submitted while others are mid-stream — each get exactly the tokens
    of greedy decoding by the full forward pass."""
    from repro.serve.engine import ServingEngine
    cfg, params = _model(arch, **overrides)
    eng = ServingEngine(cfg, params, batch_slots=2, cache_len=64,
                        prefill_chunk=4)
    reqs = [_req(0, 11, 4), _req(1, 3, 5), _req(2, 9, 3), _req(3, 2, 4),
            _req(4, 6, 5)]
    eng.submit(reqs[:3])
    for _ in range(3):
        eng.step()
    eng.submit(reqs[3:])
    done = eng.run()
    assert set(done) == {r["id"] for r in reqs}
    for r in reqs:
        assert done[r["id"]] == _greedy_reference(
            cfg, params, r["prompt"], r["max_new_tokens"]), r["id"]


@pytest.mark.parametrize("share", [False, True])
def test_serve_step_output_contract(share):
    """``make_serve_chunk_step`` returns ``(tokens (C, B), caches)``, and
    with an expert share ``(tokens, caches, held)``: the arity callers
    that wrap the step rely on."""
    from repro.models.builder import materialize
    from repro.train.step import make_serve_chunk_step
    cfg, params = _model("qwen2-moe-a2.7b", padded_num_experts=0,
                         held_experts=(0, 2) if share else ())
    B, C = 2, 4
    caches = materialize(tfm.cache_decl(cfg, B, 16), jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((B, C), jnp.int32),
             "start": jnp.zeros(B, jnp.int32),
             "pos": jnp.zeros(B, jnp.int32),
             "lengths": jnp.full(B, C, jnp.int32),
             "adv": jnp.full(B, C, jnp.int32)}
    out = jax.jit(make_serve_chunk_step(cfg))(params, caches, batch)
    assert len(out) == (3 if share else 2)
    assert out[0].shape == (C, B)
    assert jax.tree_util.tree_structure(out[1]) \
        == jax.tree_util.tree_structure(caches)
    if share:
        # assignments routed to held experts, over layers and micro-steps
        moe_layers = sum(s.mlp == "moe" for s in cfg.all_layers)
        held = int(out[2])
        assert out[2].shape == () and \
            0 < held <= C * B * cfg.num_experts_per_tok * moe_layers


def test_engine_batched_tick_commitments():
    """ONE Merkle append per batch tick (not per stream), leaves in slot
    order, and per-session inclusion refs verifying against the tick
    roots."""
    trust = TrustConfig(audit_rate=1.0, num_verifiers=1, challenge_window=2)
    eng = _engine(trust=trust)
    eng.submit([_req(0, 5, 3), _req(1, 5, 3)])
    done = eng.run()
    assert set(done) == {0, 1}
    emitting_ticks = {t for rec in eng.records.values() for t in rec.ticks}
    assert len(eng.tick_commitments) == len(emitting_ticks)
    by_tick = {tc.tick: tc for tc in eng.tick_commitments}
    for rid, rec in eng.records.items():
        assert len(rec.refs) == len(rec.leaves)
        for leaf, ref in zip(rec.leaves, rec.refs):
            assert ref.verify(leaf)
            assert by_tick[ref.tick].root == ref.root
            assert rid in by_tick[ref.tick].request_ids
    # a tick both streams emitted in carries both, slot order
    both = [tc for tc in eng.tick_commitments if tc.num_leaves == 2]
    assert both and both[0].request_ids == (0, 1)
    rep = eng.obs_report()
    assert rep["commit_appends"] == len(eng.tick_commitments)
    assert rep["commit_leaves"] == sum(tc.num_leaves
                                       for tc in eng.tick_commitments)


def test_engine_audit_catches_tick_inclusion_break():
    """A session whose leaf list is consistently rewritten (leaves AND
    per-session root recomputed) is still caught by the batch tick
    trees: the committed tick roots can't be rewritten retroactively."""
    trust = TrustConfig(audit_rate=1.0, num_verifiers=1,
                        challenge_window=50)
    eng = _engine(trust=trust)
    eng.submit([_req(0, 5, 4)])
    while 0 not in eng._done and eng.step():
        pass
    rec = eng.records[0]
    # consistent rewrite: alter records, re-derive leaves, re-seal
    rec.tokens = [t ^ 1 for t in rec.tokens]
    from repro.serve.engine import _tick_leaf
    rec.leaves = [_tick_leaf(0, t, tok)
                  for t, tok in zip(rec.ticks, rec.tokens)]
    rec.seal()
    rep = eng.audit_session(0)
    assert rep["revoked"]                        # inclusion proofs fail


def test_fixed_vs_continuous_trust_verdict_equivalence():
    """The trust contract of continuous batching on a seeded request
    trace: on the honest trace every session's audit is clean and every
    session finalizes; with request 2's stream altered, request 2 is
    revoked, and every session that shares no tick with request 2 keeps
    its honest verdict."""
    from repro.data.synthetic import serving_requests

    def run(tamper_rid=None):
        # window wide enough that no session finalizes before the whole
        # trace is served — the tamper must land in-window
        trust = TrustConfig(audit_rate=1.0, num_verifiers=1,
                            challenge_window=120)
        eng = _engine(trust=trust)
        eng.submit(list(serving_requests(eng.cfg.vocab_size, 5,
                                         max_prompt=10, max_new=5,
                                         seed=11)))
        while eng._done.keys() != {0, 1, 2, 3, 4} and eng.step():
            pass
        if tamper_rid is not None:
            rec = eng.records[tamper_rid]
            rec.tokens = [t ^ 1 for t in rec.tokens]
        done = eng.run()
        verdicts = {rid: ("revoked" if eng.records[rid].revoked
                          else "finalized" if rid in done else "open")
                    for rid in eng.records}
        return eng, verdicts

    honest, v = run()
    assert v == {rid: "finalized" for rid in range(5)}
    assert all(rec.audited for rec in honest.records.values())
    assert all(not a["mismatches"] and not a["revoked"]
               for a in honest.audit_all())
    tampered, v2 = run(tamper_rid=2)
    assert v2[2] == "revoked"
    rec2 = tampered.records[2]
    apart = [rid for rid, rec in tampered.records.items()
             if rid != 2 and not tampered._overlaps(rec, rec2)]
    assert apart and all(v2[rid] == v[rid] for rid in apart)


# ------------------------------------------------------------ KV paging
def _shared_prefix_reqs(shared_len=40, tail_len=6, new=4, vocab=64):
    """Two requests sharing a ``shared_len``-token system prompt with
    distinct tails — the cross-session prefix-reuse workload."""
    rng = np.random.default_rng(7)
    shared = rng.integers(0, vocab, shared_len).astype(np.int32)
    reqs = []
    for rid in range(2):
        tail = rng.integers(0, vocab, tail_len).astype(np.int32)
        reqs.append({"id": rid, "prompt": np.concatenate([shared, tail]),
                     "max_new_tokens": new})
    return reqs


def test_kv_paging_warm_prefix_reuse_bit_identical():
    """Two sessions sharing a system prompt, served one at a time: the
    second admission restores the sealed shared blocks instead of
    recomputing their prefill (warm hits, restored tokens, strictly
    earlier first token), while the token streams stay bit-identical to
    the paging-off oracle."""
    from repro.serve.engine import KVStorageConfig
    reqs = _shared_prefix_reqs()             # 40 shared + 6 tail, 4 new

    base = _engine(batch_slots=1)
    base.submit([dict(r, prompt=r["prompt"].copy()) for r in reqs])
    done_base = base.run()

    eng = _engine(batch_slots=1, kv_storage=KVStorageConfig(block_tokens=8))
    eng.submit([dict(r, prompt=r["prompt"].copy()) for r in reqs])
    done = eng.run()
    assert done == done_base
    rep = eng.obs_report()["kv"]
    # session 0 seals blocks 0..4 of the shared prefix (restorable
    # blocks end strictly inside the 46-token prompt: (46-1)//8 = 5);
    # session 1 restores all 5 — zero prefill recompute for 40 tokens
    assert rep["warm_hits"] == 5
    assert rep["restored_tokens"] == 40
    assert rep["sealed_blocks"] > 0 and rep["sealed_bytes"] > 0
    # the restored prefill shows up as a strictly shorter admission-to-
    # first-token distance than the cold session's
    meta = eng.request_meta
    ttft = {rid: meta[rid]["first_token_tick"] - meta[rid]["admitted_tick"]
            for rid in (0, 1)}
    assert ttft[1] < ttft[0]


def test_kv_paging_concurrent_identical_prompts_dedup_in_store():
    """Two slots running the SAME prompt concurrently seal the same
    prefix CIDs — the second seal of each block is an ExpertStore-level
    no-op (cross-session dedup), and streams still match the oracle."""
    from repro.serve.engine import KVStorageConfig
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, 64, 30).astype(np.int32)
    reqs = [{"id": rid, "prompt": prompt.copy(), "max_new_tokens": 3}
            for rid in range(2)]

    base = _engine()
    base.submit([dict(r, prompt=r["prompt"].copy()) for r in reqs])
    done_base = base.run()
    eng = _engine(kv_storage=KVStorageConfig(block_tokens=8))
    eng.submit([dict(r, prompt=r["prompt"].copy()) for r in reqs])
    assert eng.run() == done_base
    rep = eng.obs_report()["kv"]
    assert rep["dedup_blocks"] > 0
    # dedup'd blocks were never re-uploaded: one store version per
    # UNIQUE block, regardless of how many sessions sealed it
    assert rep["store"]["versions"] == rep["sealed_blocks"]


def test_kv_page_out_then_readmit_resumes_bit_identically():
    """A mid-decode slot paged out to the chunked store (full blocks +
    partial tail) resumes after readmission with the exact same stream
    as the never-paged oracle."""
    from repro.serve.engine import KVStorageConfig
    rng = np.random.default_rng(1)
    reqs = [{"id": 0, "prompt": rng.integers(0, 64, 20).astype(np.int32),
             "max_new_tokens": 12}]

    base = _engine(prefill_chunk=4)
    base.submit([dict(r, prompt=r["prompt"].copy()) for r in reqs])
    done_base = base.run()

    eng = _engine(prefill_chunk=4,
                  kv_storage=KVStorageConfig(block_tokens=8))
    eng.submit([dict(r, prompt=r["prompt"].copy()) for r in reqs])
    steps = 0
    while (not eng.sched.slots[0].decoding
           or len(eng.sched.slots[0].generated) < 4):
        assert eng.step() and steps < 100
        steps += 1
    rid = eng.page_out(0)                    # mid-decode: tail block too
    assert rid == 0 and not eng.sched.slots[0].active
    assert eng.sched.depth() == 1            # requeued at the front
    assert eng.run() == done_base
    rep = eng.obs_report()["kv"]
    assert rep["pageouts"] == 1 and rep["resumes"] == 1
    assert rep["restored_tokens"] > 0
    assert eng.request_meta[0]["preemptions"] == 1


def test_kv_sealing_keeps_tick_commitments_bit_identical():
    """With DISJOINT prompts (nothing to restore), sealing is pure
    side-band: every tick commitment's (tick, root, request_ids) equals
    the paging-off oracle's, kv_root carries the sealed manifests, and
    the verdict maps match — honest sessions finalize in both."""
    from repro.serve.engine import KVStorageConfig
    trust = TrustConfig(audit_rate=1.0, num_verifiers=1, challenge_window=4)
    reqs = [_req(0, 20, 3), _req(1, 17, 3)]

    def run(kv):
        eng = _engine(trust=trust,
                      kv_storage=KVStorageConfig(block_tokens=8)
                      if kv else None)
        eng.submit([dict(r, prompt=r["prompt"].copy()) for r in reqs])
        done = eng.run()
        verdicts = {rid: ("revoked" if eng.records[rid].revoked
                          else "finalized" if rid in done else "open")
                    for rid in eng.records}
        return eng, done, verdicts

    base, done_b, v_b = run(kv=False)
    kv, done_k, v_k = run(kv=True)
    assert done_k == done_b and v_k == v_b
    assert all(v == "finalized" for v in v_k.values())
    assert [(tc.tick, tc.root, tc.request_ids)
            for tc in kv.tick_commitments] == \
        [(tc.tick, tc.root, tc.request_ids) for tc in base.tick_commitments]
    assert all(tc.kv_root == "" for tc in base.tick_commitments)
    assert any(tc.kv_root != "" for tc in kv.tick_commitments)
    # every sealed block's manifest is reachable for DA challenges
    kvbs = kv.kvrt.kv
    assert len(kvbs.manifests(kvbs.sealed_cids())) \
        == kv.obs_report()["kv"]["sealed_blocks"]


def test_kv_paging_verified_warm_reuse_keeps_verdicts():
    """Warm-prefix reuse under trust: restored prefill changes WHEN
    tokens land (earlier), never WHAT is committed — both sessions
    finalize and post-hoc tampering is still caught."""
    from repro.serve.engine import KVStorageConfig
    trust = TrustConfig(audit_rate=1.0, num_verifiers=1, challenge_window=4)
    reqs = _shared_prefix_reqs()
    eng = _engine(batch_slots=1, trust=trust,
                  kv_storage=KVStorageConfig(block_tokens=8))
    eng.submit([dict(r, prompt=r["prompt"].copy()) for r in reqs])
    done = eng.run()
    assert set(done) == {0, 1}
    assert eng.obs_report()["kv"]["warm_hits"] > 0
    assert all(rec.finalized and not rec.revoked
               for rec in eng.records.values())
    eng.records[1].tokens = [t ^ 1 for t in eng.records[1].tokens]
    assert eng.audit_session(1)["revoked"]


def test_kv_storage_validation():
    from repro.serve.engine import KVStorageConfig
    with pytest.raises(ValueError, match="block_tokens"):
        _engine(cache_len=8, kv_storage=KVStorageConfig(block_tokens=8))
    with pytest.raises(ValueError, match="block_tokens"):
        _engine(kv_storage=KVStorageConfig(block_tokens=0))
    eng = _engine()
    with pytest.raises(ValueError, match="kv_storage"):
        eng.page_out(0)                      # paging not configured
    kv_eng = _engine(kv_storage=KVStorageConfig(block_tokens=8))
    with pytest.raises(ValueError, match="not active"):
        kv_eng.page_out(0)                   # no running request


def test_engine_continuous_dependent_revocation_chains_through_admission():
    """Continuous admission deliberately widens the dependent-revocation
    blast radius: a request admitted into a freed slot shares decode
    ticks with the still-running stream, so fraud on the long stream
    voids it too (pairs that share no tick are covered in
    tests/test_pipeline.py)."""
    trust = TrustConfig(audit_rate=1.0, num_verifiers=1,
                        challenge_window=80)
    eng = _engine(trust=trust)
    eng.submit([_req(0, 4, 20), _req(1, 4, 2), _req(2, 4, 2)])
    while eng._done.keys() != {0, 1, 2} and eng.step():
        pass
    # request 2 was admitted into request 1's freed slot while 0 ran
    assert eng.records[2].ticks[0] <= eng.records[0].ticks[-1]
    eng.records[0].tokens = [t ^ 1 for t in eng.records[0].tokens]
    rep = eng.audit_session(0)
    assert rep["revoked"]
    assert eng.records[1].revoked and eng.records[2].revoked
    assert eng.run() == {}
