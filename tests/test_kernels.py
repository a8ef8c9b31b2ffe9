"""Per-kernel validation: Pallas (interpret=True on CPU) vs the pure-jnp
oracle in kernels/ref.py, swept over shapes and dtypes (hypothesis)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gemm import moe_gemm
from repro.kernels.redundancy_vote import pairwise_agreement
from repro.kernels.ssd_scan import ssd_scan

SETTINGS = dict(max_examples=12, deadline=None)


# ------------------------------------------------------------ moe_gemm
@settings(**SETTINGS)
@given(E=st.sampled_from([1, 3, 4]),
       C=st.sampled_from([8, 40, 128, 200]),
       d=st.sampled_from([32, 96, 128]),
       f=st.sampled_from([16, 128, 192]),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]))
def test_moe_gemm_matches_ref(E, C, d, f, dtype):
    key = jax.random.PRNGKey(E * 1000 + C)
    buf = jax.random.normal(key, (E, C, d), dtype)
    w = jax.random.normal(jax.random.fold_in(key, 1), (E, d, f), dtype)
    got = moe_gemm(buf, w, interpret=True)
    want = ref.moe_gemm_ref(buf, w)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 8)


def test_moe_gemm_nondivisible_blocks():
    buf = jax.random.normal(jax.random.PRNGKey(0), (2, 100, 50))
    w = jax.random.normal(jax.random.PRNGKey(1), (2, 50, 70))
    got = moe_gemm(buf, w, block_c=32, block_d=32, block_f=32,
                   interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.moe_gemm_ref(buf, w)),
                               rtol=1e-5, atol=1e-4)


# ----------------------------------------------------- redundancy_vote
@settings(**SETTINGS)
@given(E=st.sampled_from([1, 4, 10]),
       M=st.sampled_from([3, 5, 10]),
       T=st.sampled_from([7, 64, 1500]),
       n_bad=st.integers(0, 2))
def test_pairwise_agreement_matches_ref(E, M, T, n_bad):
    key = jax.random.PRNGKey(E + M + T)
    pub = jnp.broadcast_to(jax.random.normal(key, (E, 1, T)),
                           (E, M, T)).copy()
    if n_bad:
        noise = jax.random.normal(jax.random.fold_in(key, 2),
                                  (E, n_bad, T))
        pub = pub.at[:, :n_bad].add(noise)
    got = pairwise_agreement(pub, interpret=True, tile=64)
    want_unpadded = ref.pairwise_agreement_ref(pub)
    pad = (-T) % min(64, T)   # kernel clamps tile to T
    # padded zeros agree for every pair: constant offset
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(want_unpadded) + pad)


@pytest.mark.parametrize("M,n_bad", [(10, 3), (10, 4), (5, 2), (3, 1)])
def test_vote_rejects_minority(M, n_bad):
    """Colluding minority (paper §IV-B scenario 2, ratio < 50%) never
    flips the vote, in both ref and kernel backends."""
    key = jax.random.PRNGKey(0)
    honest = jax.random.normal(key, (4, 16, 8))
    pub = jnp.broadcast_to(honest[:, None], (4, M, 16, 8)).copy()
    delta = jax.random.normal(jax.random.fold_in(key, 1), (4, 1, 16, 8))
    pub = pub.at[:, :n_bad].add(jnp.broadcast_to(delta, (4, n_bad, 16, 8)))
    for backend in ("ref", "interpret"):
        from repro.kernels import ops
        trusted, support = ops.redundancy_vote(pub, backend=backend)
        np.testing.assert_allclose(np.asarray(trusted), np.asarray(honest),
                                   rtol=0, atol=0)
        assert int(support.min()) == M - n_bad


def test_vote_majority_collusion_wins():
    """> 50% colluding attackers mislead the chain (paper's threshold)."""
    key = jax.random.PRNGKey(0)
    honest = jax.random.normal(key, (2, 8, 4))
    pub = jnp.broadcast_to(honest[:, None], (2, 10, 8, 4)).copy()
    delta = jax.random.normal(jax.random.fold_in(key, 1), (2, 1, 8, 4))
    pub = pub.at[:, :6].add(jnp.broadcast_to(delta, (2, 6, 8, 4)))
    from repro.kernels import ops
    trusted, support = ops.redundancy_vote(pub)
    assert not np.allclose(np.asarray(trusted), np.asarray(honest))
    assert int(support.min()) == 6


# ------------------------------------------------------ flash attention
@settings(**SETTINGS)
@given(B=st.sampled_from([1, 2]),
       S=st.sampled_from([64, 128, 256]),
       H=st.sampled_from([2, 4]),
       KH=st.sampled_from([1, 2]),
       D=st.sampled_from([32, 64]),
       causal=st.booleans(),
       window=st.sampled_from([0, 32]))
def test_flash_attention_matches_ref(B, S, H, KH, D, causal, window):
    if H % KH:
        KH = 1
    key = jax.random.PRNGKey(S + H)
    q = jax.random.normal(key, (B, H, S, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, KH, S, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, KH, S, D))
    got = flash_attention(q, k, v, causal=causal, window=window, bq=64,
                          bk=64, interpret=True)
    want = ref.attention_ref(jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
                             jnp.moveaxis(v, 1, 2), causal=causal,
                             window=window)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.moveaxis(want, 2, 1)),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_softcap():
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(key, (1, 2, 128, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 128, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 128, 32))
    got = flash_attention(q, k, v, causal=True, softcap=20.0, bq=64, bk=64,
                          interpret=True)
    want = ref.attention_ref(jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
                             jnp.moveaxis(v, 1, 2), causal=True,
                             softcap=20.0)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.moveaxis(want, 2, 1)),
                               rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------ ssd scan
@settings(**SETTINGS)
@given(B=st.sampled_from([1, 2]),
       S=st.sampled_from([64, 256]),
       H=st.sampled_from([1, 3]),
       P=st.sampled_from([16, 32]),
       N=st.sampled_from([8, 16]),
       chunk=st.sampled_from([32, 64]))
def test_ssd_scan_matches_ref(B, S, H, P, N, chunk):
    key = jax.random.PRNGKey(S + P)
    x = jax.random.normal(key, (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1),
                                           (B, S, H))) * 0.1
    A = -jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (H,))) - 0.1
    Bm = jax.random.normal(jax.random.fold_in(key, 3), (B, S, N)) * 0.5
    Cm = jax.random.normal(jax.random.fold_in(key, 4), (B, S, N)) * 0.5
    got = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    state0 = jnp.zeros((B, H, P, N), jnp.float32)
    want, _ = ref.ssd_scan_ref(x, dt, A, Bm, Cm, state0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_ops_dispatch_backends():
    """ops.* must agree across ref and interpret backends."""
    from repro.kernels import ops
    key = jax.random.PRNGKey(0)
    buf = jax.random.normal(key, (2, 16, 32))
    w = jax.random.normal(jax.random.fold_in(key, 1), (2, 32, 24))
    np.testing.assert_allclose(
        np.asarray(ops.moe_gemm(buf, w, backend="ref")),
        np.asarray(ops.moe_gemm(buf, w, backend="interpret")),
        rtol=1e-5, atol=1e-5)


# ------------------------------------------------------ audit gather-MLP
def _mlp_bank(key, E, d, h, o):
    ks = jax.random.split(key, 4)
    return {"w1": jax.random.normal(ks[0], (E, d, h)) * 0.1,
            "b1": jax.random.normal(ks[1], (E, h)) * 0.1,
            "w2": jax.random.normal(ks[2], (E, h, o)) * 0.1,
            "b2": jax.random.normal(ks[3], (E, o)) * 0.1}


@settings(**SETTINGS)
@given(E=st.sampled_from([1, 3, 8]),
       S=st.sampled_from([1, 5, 16]),
       C=st.sampled_from([8, 33, 128]),
       d=st.sampled_from([64, 200, 784]))
def test_audit_mlp_matches_ref(E, S, C, d):
    """The fused grouped gather-MLP kernel vs the gathered-vmap oracle,
    with repeated group ids (duplicate sampled experts)."""
    from repro.kernels.audit_gemm import audit_mlp
    key = jax.random.PRNGKey(E * 100 + S + C + d)
    params = _mlp_bank(key, E, d, h=128, o=10)
    x = jax.random.normal(jax.random.fold_in(key, 9), (S, C, d))
    gid = jax.random.randint(jax.random.fold_in(key, 10), (S,), 0, E)
    got = audit_mlp(params, x, gid, interpret=True)
    want = ref.audit_mlp_ref(params, x, gid.astype(jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_audit_mlp_ref_bitwise_matches_per_chunk_apply():
    """The ref backend must be BIT-identical to the eager per-chunk
    expert apply — that is what makes batched leaf digests reproduce the
    executor's commitment exactly (hash equality, not allclose)."""
    from repro.core import experts as ex
    from repro.kernels import ops
    key = jax.random.PRNGKey(3)
    params, _ = ex.make_expert_bank("mlp", 4, key, in_dim=96, out=10)
    x = jax.random.normal(jax.random.fold_in(key, 1), (6, 17, 96))
    gid = jnp.asarray(np.array([0, 3, 3, 1, 2, 0], np.int32))
    got = np.asarray(jax.jit(ops.audit_mlp)(params, x, gid))
    for s in range(6):
        p = jax.tree_util.tree_map(lambda a: a[gid[s]], params)
        want = np.asarray(ex.mlp_expert_apply(p, x[s]))
        np.testing.assert_array_equal(got[s], want)


# ------------------------------------------------------------ rglru scan
@settings(**SETTINGS)
@given(B=st.sampled_from([1, 2]), S=st.sampled_from([64, 128, 256]),
       C=st.sampled_from([128, 256]),
       seq_block=st.sampled_from([32, 64]))
def test_rglru_scan_matches_ref(B, S, C, seq_block):
    from repro.kernels.rglru_scan import rglru_scan_pallas
    from repro.models.rglru import rglru_scan
    key = jax.random.PRNGKey(S + C)
    a = jax.nn.sigmoid(jax.random.normal(key, (B, S, C)))
    b = jax.random.normal(jax.random.fold_in(key, 1), (B, S, C))
    got = rglru_scan_pallas(a, b, seq_block=seq_block, chan_block=128,
                            interpret=True)
    want = rglru_scan(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ------------------------------------------------ absorbed MLA decode
def _mla_case(key, layers, B, cap, H=4, c=128, r=64):
    ks = jax.random.split(key, 5)
    lead = (layers,) if layers else ()
    return (jax.random.normal(ks[0], (B, H, c)) * 0.2,
            jax.random.normal(ks[1], (B, H, r)) * 0.2,
            jax.random.normal(ks[2], (B, r)),
            jax.random.normal(ks[3], lead + (B, cap, c)),
            jax.random.normal(ks[4], lead + (B, cap, r)))


MLA_CAP = 4 * 512                       # four of the kernel's blocks
MLA_CASES = {                           # positions; None: write dropped
    "pos-0": [0] * 3,
    "pos-blk-1": [511] * 3,
    "pos-blk": [512] * 3,
    "pos-mid": [1300] * 3,
    "pos-cap-1": [MLA_CAP - 1] * 3,
    "mixed-depths": [0, 511, 512, 1300, MLA_CAP - 1, MLA_CAP + 40],
    "masked-slots": [(5, None), 700, (1300, None), 129],
}


@pytest.mark.parametrize("stack", [0, 3], ids=["unstacked", "stacked"])
@pytest.mark.parametrize("case", sorted(MLA_CASES))
def test_mla_decode_matches_ref(case, stack):
    """The kernel writes each slot's rotary key at its row (none where
    the write is dropped) and reads rows 0 .. min(pos, cap - 1) in
    512-row blocks, a slot's last block masked past its position: it
    matches the whole-cache reference at block edges, at the cache's
    end, past it, with slots at different depths, with dropped writes,
    and at ``layer`` > 0 of a stacked cache."""
    from repro.kernels import ops
    spec = [p if isinstance(p, tuple) else (p, True)
            for p in MLA_CASES[case]]
    pos = jnp.asarray([p for p, _ in spec], jnp.int32)
    slot = jnp.asarray([min(p, MLA_CAP - 1) if keep else MLA_CAP
                        for p, keep in spec], jnp.int32)
    q_lat, q_pe, k_pe, ckv, kpe = _mla_case(jax.random.PRNGKey(len(case)),
                                            stack, len(pos), MLA_CAP)
    layer = stack - 1 if stack else None
    got, want = (ops.mla_decode(q_lat, q_pe, k_pe, ckv, kpe, pos, slot,
                                scale=0.3, layer=layer, backend=backend)
                 for backend in ("interpret", "ref"))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))

