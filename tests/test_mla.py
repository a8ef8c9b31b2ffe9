"""Latent attention, sigmoid routing with a selection bias, the expert
share and leading dense layers (the DeepSeek-V3 block of
``moonlight-16b-a3b``), against the plain reference of the chip
benchmark (``chipbench/mla_reference.py``, written from the layer
equations) at small sizes on the CPU."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "chipbench"))

import mla_reference  # noqa: E402
import mla_weights  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import moe as moe_lib  # noqa: E402
from repro.models import transformer as tfm  # noqa: E402
from repro.models.builder import materialize  # noqa: E402
from repro.models.layers import mla_decode, mla_train  # noqa: E402
from repro.serve.engine import ServingEngine  # noqa: E402
from repro.trust.protocol import TrustConfig  # noqa: E402

# the benchmark's configuration keys at a small size: one leading dense
# layer, two MoE layers of 8 experts (top 3) of which 4 are held
SMALL = dict(num_layers=3, leading_dense=1, d_model=64, num_heads=4,
             num_kv_heads=4, d_ff=96, moe_d_ff=32, num_experts=8,
             held_experts=4, num_experts_per_tok=3, num_shared_experts=2,
             kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, router_scoring="sigmoid",
             routed_scaling_factor=2.446, vocab_size=256, rope_theta=50000.0,
             norm_eps=1e-5, tie_embeddings=False)


def small_cfg(c=SMALL):
    base = get_config("moonlight-16b-a3b")
    keys = {k: v for k, v in c.items()
            if k not in ("leading_dense", "held_experts")}
    return dataclasses.replace(
        base, **keys, held_experts=tuple(range(c["held_experts"])),
        head_dim=c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
        num_blocks=c["num_layers"] - c["leading_dense"]).validate()


@pytest.fixture(scope="module")
def model():
    return small_cfg(), mla_weights.make(SMALL, 3)


def ref_logits(params, tokens, c=SMALL):
    S = len(tokens)
    pad = np.zeros(mla_reference.bucket(S), np.int32)
    pad[:S] = tokens
    return np.asarray(mla_reference.forward(
        params, jnp.asarray(pad), c=c, dtype=jnp.float32))[:S]


def test_weights_have_the_programs_layout(model):
    cfg, params = model
    want = jax.tree_util.tree_map(lambda a: a.shape,
                                  materialize(tfm.model_decl(cfg),
                                              jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_map(lambda a: a.shape, params) == want


def test_absorbed_decode_equals_expanded_position_by_position(model):
    """One layer: the expanded form over a whole prefix and the absorbed
    form, one token at a time through the latent cache, agree at every
    position; the cache holds the normed latent and the rotated key."""
    cfg, params = model
    p = params["leading"][0]["mla"]
    S, B = 12, 2
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
    want = mla_train(p, x, cfg, q_chunk=4, kv_chunk=4)
    cache = {"ckv": jnp.zeros((B, 16, cfg.kv_lora_rank)),
             "kpe": jnp.zeros((B, 16, cfg.qk_rope_head_dim))}
    for t in range(S):
        y, cache = mla_decode(p, x[:, t:t + 1], cache,
                              jnp.full((B,), t, jnp.int32), cfg)
        np.testing.assert_allclose(y[:, 0], want[:, t], rtol=2e-4, atol=2e-5)
    assert not bool(jnp.any(cache["ckv"][:, S:]))


def test_decode_logits_match_the_reference_over_a_prefix(model):
    """Whole model (leading dense layer, scanned MoE layers with the
    share): token-by-token decode through the latent cache gives the
    reference's full-forward logits at every position."""
    cfg, params = model
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (10,), 0,
                                         cfg.vocab_size))
    want = ref_logits(params, toks)
    caches = materialize(tfm.cache_decl(cfg, 1, 16), jax.random.PRNGKey(0))
    for t, tok in enumerate(toks):
        logits, caches, held = tfm.forward_decode(
            params, caches, jnp.asarray([[tok]]), jnp.asarray([t]), cfg)
        np.testing.assert_allclose(logits[0, 0], want[t], rtol=1e-4,
                                   atol=1e-4)
        assert 0 <= int(held) <= 2 * cfg.num_experts_per_tok


def _route_ref(logits, bias, k, scale):
    s = 1 / (1 + np.exp(-logits))
    idx = np.argsort(-(s + bias), axis=-1, kind="stable")[..., :k]
    w = np.take_along_axis(s, idx, -1)
    return w / w.sum(-1, keepdims=True) * scale, idx


def test_sigmoid_routing_with_bias_matches_the_reference():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(2, 64, 16)).astype(np.float32)
    bias = (rng.normal(size=16) * 0.3).astype(np.float32)
    k, scale = 4, 2.446
    w, idx, _, keep, _ = moe_lib.route(jnp.asarray(logits), k, 64,
                                       scoring="sigmoid",
                                       bias=jnp.asarray(bias), scale=scale)
    want_w, want_idx = _route_ref(logits, bias, k, scale)
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(want_idx, -1))
    order = lambda i, v: np.take_along_axis(v, np.argsort(i, -1), -1)
    np.testing.assert_allclose(order(np.asarray(idx), np.asarray(w)),
                               order(want_idx, want_w), rtol=1e-5)
    assert bool(keep.all())
    # the bias changes selections, and weighs nothing: weights are scores
    _, idx0 = _route_ref(logits, 0 * bias, k, scale)
    assert (np.sort(idx0, -1) != np.sort(want_idx, -1)).any(axis=-1).mean() > 0.2
    np.testing.assert_allclose(np.asarray(w).sum(-1), scale, rtol=1e-5)


def test_default_router_options_reproduce_softmax_route():
    """The new options at their defaults are today's softmax top-k,
    renormalized: the same numbers as the formula, bit for bit the same
    as an explicit call with the defaults."""
    logits = jax.random.normal(jax.random.PRNGKey(4), (2, 8, 6))
    got = moe_lib.route(logits, 2, 4)
    same = moe_lib.route(logits, 2, 4, scoring="softmax", bias=None,
                         scale=1.0)
    for a, b in zip(got, same):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    p = jax.nn.softmax(logits, -1)
    top, idx = jax.lax.top_k(p, 2)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(idx))
    np.testing.assert_allclose(np.asarray(got[0]),
                               np.asarray(top / top.sum(-1, keepdims=True)),
                               rtol=1e-6)


def _moe_layer(cfg, params, x, held):
    """One MoE layer of the program holding experts ``held``."""
    c = dataclasses.replace(cfg, held_experts=held)
    p = dict(params, **{k: params[k][np.asarray(held)]
                        for k in ("w_gate", "w_up", "w_down")})
    return moe_lib.moe_mlp(p, x, c, return_held=True)


def test_expert_shares_add_up_to_the_uncut_layer():
    """The 8 chips' shares of one MoE layer (8 experts each of 64, top 6,
    two shared experts), with the shared experts counted once, add up
    to the layer that holds every expert; every assignment lands on
    exactly one share."""
    cfg = small_cfg(dict(SMALL, num_experts=64, held_experts=64,
                         num_experts_per_tok=6))
    full = materialize(moe_lib.moe_decl(dataclasses.replace(
        cfg, held_experts=())), jax.random.PRNGKey(5))
    full["b_corr"] = 0.05 * jax.random.normal(jax.random.PRNGKey(6), (64,))
    x = jax.random.normal(jax.random.PRNGKey(7), (3, 1, cfg.d_model))
    whole, _ = moe_lib.moe_mlp(full, x, dataclasses.replace(
        cfg, held_experts=()))
    no_shared = dataclasses.replace(cfg, num_shared_experts=0)
    parts, assigned = 0.0, 0
    for chip in range(8):
        y, _, held = _moe_layer(no_shared, full, x,
                                tuple(range(8 * chip, 8 * chip + 8)))
        parts, assigned = parts + y, assigned + held
    sp = full["shared"]
    shared = (jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_up"])) @ sp["w_down"]
    np.testing.assert_allclose(parts + shared, whole, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(assigned), 6)


def test_no_held_assignment_is_dropped_at_one_token_per_slot():
    """At the cell's shapes (one token per slot and micro-step, top 6 of
    64, capacity 6) every assignment to a held expert is computed."""
    cfg = dataclasses.replace(get_config("moonlight-16b-a3b"),
                              d_model=64, moe_d_ff=16, num_shared_experts=0,
                              held_experts=tuple(range(8)))
    assert moe_lib.capacity_for(cfg, 1) == 6
    params = materialize(moe_lib.moe_decl(cfg), jax.random.PRNGKey(8))
    params["b_corr"] = 0.05 * jax.random.normal(jax.random.PRNGKey(9), (64,))
    x = jax.random.normal(jax.random.PRNGKey(10), (32, 1, 64))
    logits = x @ params["router"]
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + params["b_corr"], 6)
    routed_here = int((idx < 8).sum())
    _, _, held = moe_lib.moe_mlp(params, x, cfg, return_held=True)
    assert routed_here > 0 and int(held.sum()) == routed_here


def _requests(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return [{"id": i, "prompt": rng.integers(0, cfg.vocab_size,
                                             int(rng.integers(3, 9))
                                             ).astype(np.int32),
             "max_new_tokens": int(rng.integers(2, 7))} for i in range(n)]


def _served_gaps(params, reqs, done):
    """Per request, the gap between the reference's best logit and the
    served token's, at every generated position."""
    out = []
    for r in reqs:
        served = done[r["id"]]
        seq = np.concatenate([r["prompt"], served[:-1]]).astype(np.int32)
        logits = ref_logits(params, seq)[len(r["prompt"]) - 1:]
        out.append(logits.max(-1) - logits[np.arange(len(served)), served])
    return np.concatenate(out)


@pytest.mark.parametrize("verified", [False, True])
def test_engine_prefill_then_decode_matches_the_reference(model, verified):
    """Chunked prefill and decode through ``ServingEngine`` (the fused
    step, the latent cache written in place, the cache donated, the
    expert share counted) serve the reference's greedy tokens; verified,
    every session commits, passes its audits and finalizes."""
    cfg, params = model
    trust = (TrustConfig(audit_rate=1.0, num_verifiers=1,
                         challenge_window=2) if verified else None)
    eng = ServingEngine(cfg, params, batch_slots=3, cache_len=32,
                        prefill_chunk=4, trust=trust, donate_cache=True)
    reqs = _requests(cfg, 5, 11)
    eng.submit(reqs)
    done = eng.run()
    assert sorted(done) == [r["id"] for r in reqs]
    np.testing.assert_allclose(_served_gaps(params, reqs, done), 0.0,
                               atol=1e-4)
    held = eng.obs.metrics.value("serve.held_assignments")
    fed = sum(len(r["prompt"]) + r["max_new_tokens"] - 1 for r in reqs)
    assert 0 < held <= fed * (cfg.num_layers - 1) * cfg.num_experts_per_tok
    if verified:
        assert all(not a["revoked"] for a in eng.audit_all())
        assert all(eng.records[r["id"]].finalized for r in reqs)


@pytest.mark.parametrize("backend,blk", [("ref", 1024), ("interpret", 512)])
def test_latent_row_counters_equal_their_closed_form(model, monkeypatch,
                                                      backend, blk):
    """Served through the kernel (interpreted) or the reference, the
    tokens are the reference model's; ``serve.latent_rows_live`` sums,
    over micro-steps, slots and mla layers, the rows 0 .. min(pos,
    cap - 1) the attention core covers; ``serve.latent_rows_read`` the
    same in whole blocks: the kernel's 512 rows, or the whole cache for
    the reference, which reads every row."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", backend)
    cfg, params = model
    cap, slots = 1024, 3
    eng = ServingEngine(cfg, params, batch_slots=slots, cache_len=cap,
                        prefill_chunk=4)
    seen = []
    prepare = eng._prepare

    def recording():
        out = prepare()
        seen.append((out[0], out[3].copy()))     # C, positions
        return out

    eng._prepare = recording
    reqs = _requests(cfg, 4, 13)
    reqs[0]["prompt"] = np.arange(600, dtype=np.int32) % cfg.vocab_size
    eng.submit(reqs)
    done = eng.run()
    np.testing.assert_allclose(_served_gaps(params, reqs, done), 0.0,
                               atol=1e-4)
    live = read = 0
    for C, pos in seen:
        for t in range(C):
            rows = np.minimum(pos + t, cap - 1) + 1
            live += int(rows.sum())
            read += int((np.ceil(rows / blk) * blk).sum())
    metrics = eng.obs.metrics
    assert metrics.value("serve.latent_rows_live") == cfg.num_layers * live
    assert metrics.value("serve.latent_rows_read") == cfg.num_layers * read
    whole = cap * slots * sum(C for C, _ in seen)
    assert live < read <= whole
    assert (read == whole) == (blk == cap)


def test_donated_and_kept_caches_serve_the_same(model):
    cfg, params = model
    outs = []
    for donate in (False, True):
        eng = ServingEngine(cfg, params, batch_slots=2, cache_len=32,
                            prefill_chunk=4, donate_cache=donate)
        eng.warmup()
        eng.submit(_requests(cfg, 3, 12))
        outs.append(eng.run())
    assert outs[0] == outs[1]


def test_latent_cache_is_pageable_and_round_trips(model):
    cfg, _ = model
    tfm.check_kv_pageable(cfg)
    caches = materialize(tfm.cache_decl(cfg, 2, 16), jax.random.PRNGKey(0))
    caches = jax.tree_util.tree_map(
        lambda a: jax.random.normal(jax.random.PRNGKey(a.size), a.shape),
        caches)
    assert set(caches) == {"leading", "blocks"}
    assert set(caches["blocks"]["0"]) == {"ckv", "kpe"}
    block = tfm.slice_kv_block(caches, 1, 4, 9)
    assert block["leading"][0]["ckv"].shape == (5, cfg.kv_lora_rank)
    assert block["blocks"]["0"]["kpe"].shape == (
        cfg.num_layers - 1, 5, cfg.qk_rope_head_dim)
    zero = jax.tree_util.tree_map(jnp.zeros_like, caches)
    back = tfm.restore_kv_block(zero, 1, 4, block)
    again = tfm.slice_kv_block(back, 1, 4, 9)
    jax.tree_util.tree_map(np.testing.assert_array_equal, again, block)
    assert not bool(back["blocks"]["0"]["ckv"][:, 0].any())
