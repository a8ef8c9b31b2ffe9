"""Expert-parallel edge cases (PR: mesh B-MoE rounds).

Pins the fixes that unblocked mesh execution of the B-MoE round loop:

- ragged token counts (``T_full % msize != 0``) pad the token axis and
  route pad rows to the sentinel expert, instead of the old fallback
  that dispatched every token from every model shard (msize-duplicate
  wire bytes and expert FLOPs);
- the router aux loss reduces the same psum'd global statistics whether
  or not the token axis is ragged (the old per-shard pmean disagreed
  between the msplit==1 and msplit>1 regimes);
- shared experts vote over the replica axis like routed buckets (they
  used to bypass ``_ep_vote`` entirely — a tampered shared expert was
  invisible to redundancy voting);
- ``launch.mesh`` factories derive widths from the live device count
  instead of hardcoding 16-device pods.

Host-side tests cover ``route_masked``; everything touching a mesh runs
in a forced-device subprocess (see conftest.run_with_devices).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import run_with_devices
from repro.models.moe import route, route_masked


# --------------------------------------------------------- route_masked
def test_route_masked_matches_route_when_unmasked():
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 8))
    w0, e0, p0, k0, _ = route(logits, 2, 4, 8)
    w1, e1, p1, k1, stats = route_masked(logits, 2, 4, 8)
    np.testing.assert_array_equal(np.asarray(w0), np.asarray(w1))
    np.testing.assert_array_equal(np.asarray(e0), np.asarray(e1))
    np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))
    np.testing.assert_array_equal(np.asarray(k0), np.asarray(k1))
    assert float(stats[2]) == 2 * 16                     # every token valid


def test_route_masked_pad_rows_are_inert():
    """Pad rows get the sentinel expert id (== num_experts), zero
    weight, no capacity slot, and are excluded from the routing stats —
    so they consume no capacity, no wire bytes, and no aux mass."""
    E, T, k = 4, 6, 2
    logits = jax.random.normal(jax.random.PRNGKey(1), (1, T, E))
    valid = jnp.asarray([[True, True, True, True, False, False]])
    w, eid, pos, keep, stats = route_masked(logits, k, 2, E, valid=valid)
    assert np.all(np.asarray(eid)[0, 4:] == E)           # sentinel id
    assert np.all(np.asarray(w)[0, 4:] == 0.0)
    assert not np.any(np.asarray(keep)[0, 4:])           # no bucket slot
    assert float(stats[2]) == 4.0                        # n_valid
    # stats must match routing only the valid prefix
    _, _, _, _, ref = route_masked(logits[:, :4], k, 2, E)
    np.testing.assert_allclose(np.asarray(stats[0]), np.asarray(ref[0]))
    np.testing.assert_allclose(np.asarray(stats[1]), np.asarray(ref[1]),
                               rtol=1e-6)


def test_route_masked_pad_rows_do_not_steal_capacity():
    """A pad row routed (pre-mask) to a popular expert must not occupy
    one of its capacity slots: real assignments keep their positions."""
    E, k = 2, 1
    logits = jnp.zeros((1, 4, E)).at[:, :, 0].set(5.0)   # all pick expert 0
    valid = jnp.asarray([[True, False, True, True]])
    _, eid, pos, keep, _ = route_masked(logits, k, 2, E, valid=valid)
    eid, pos, keep = (np.asarray(a)[0, :, 0] for a in (eid, pos, keep))
    assert eid[1] == E and not keep[1]
    # real rows 0, 2, 3 contend for 2 slots of expert 0: first two fit
    assert keep[0] and keep[2] and not keep[3]
    assert {pos[0], pos[2]} == {0, 1}


# ------------------------------------------------ ragged EP dispatch
def test_ep_ragged_tokens_match_oracle(repo_src):
    """T_full % msize != 0 (the seq length makes each data shard hold 60
    tokens on a 4-wide model axis): the padded token path must still
    match the single-device GSPMD oracle, aux included."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import get_config
        from repro.models import moe as moe_lib
        from repro.models.moe_ep import moe_mlp_ep
        from repro.models.builder import materialize
        from repro.sharding import logical_rules
        cfg = get_config("qwen2-moe-a2.7b", smoke=True)
        cfg = dataclasses.replace(cfg, capacity_factor=8.0,
                                  padded_num_experts=4, moe_impl="ep")
        key = jax.random.PRNGKey(0)
        params = materialize(moe_lib.moe_decl(cfg), key)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = logical_rules(mesh, cfg)
        for S in (31, 7):
            x = jax.random.normal(jax.random.fold_in(key, S),
                                  (4, S, cfg.d_model))
            assert (2 * S) % 4 != 0, S          # genuinely ragged per shard
            y_ref, aux_ref = moe_lib.moe_mlp(params, x, cfg)
            with mesh:
                y_ep, aux_ep = jax.jit(lambda p, x: moe_mlp_ep(
                    p, x, cfg, mesh, rules, fsdp=False))(params, x)
            np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                                       rtol=3e-3, atol=3e-3)
            assert abs(float(aux_ep) - float(aux_ref)) < 1e-3, S
            print("RAGGED OK", S, float(aux_ep))
    """, 8, repo_src)
    assert out.count("RAGGED OK") == 2


def test_ep_ragged_wire_bytes_parity(repo_src):
    """Regression for the old ragged fallback, which dispatched the FULL
    token set from every model shard (msize x wire bytes, msize x expert
    FLOPs).  The padded path's collective bytes for a ragged 31-token
    seq must stay within 1.25x of the even 32-token compile — not ~4x."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import get_config
        from repro.launch import hloanalysis
        from repro.models import moe as moe_lib
        from repro.models.moe_ep import moe_mlp_ep
        from repro.models.builder import materialize
        from repro.sharding import logical_rules
        cfg = get_config("qwen2-moe-a2.7b", smoke=True)
        cfg = dataclasses.replace(cfg, capacity_factor=8.0,
                                  padded_num_experts=4, moe_impl="ep")
        key = jax.random.PRNGKey(0)
        params = materialize(moe_lib.moe_decl(cfg), key)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = logical_rules(mesh, cfg)
        def bytes_for(S):
            x = jax.ShapeDtypeStruct((4, S, cfg.d_model), jnp.float32)
            with mesh:
                txt = jax.jit(lambda p, xx: moe_mlp_ep(
                    p, xx, cfg, mesh, rules, fsdp=False)
                ).lower(params, x).compile().as_text()
            return hloanalysis.analyze(txt)["total_collective_bytes"]
        ragged, even = bytes_for(31), bytes_for(32)
        assert even > 0
        assert ragged <= even * 1.25, (ragged, even)
        print("WIRE PARITY OK", ragged, even)
    """, 8, repo_src)
    assert "WIRE PARITY OK" in out


def test_ep_tiny_token_count(repo_src):
    """Decode-shaped inputs (fewer tokens than model shards): capacity
    still >= 1, pad rows stay inert, output matches the oracle."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import get_config
        from repro.models import moe as moe_lib
        from repro.models.moe_ep import moe_mlp_ep
        from repro.models.builder import materialize
        from repro.sharding import logical_rules
        cfg = get_config("qwen2-moe-a2.7b", smoke=True)
        cfg = dataclasses.replace(cfg, capacity_factor=8.0,
                                  padded_num_experts=4, moe_impl="ep")
        key = jax.random.PRNGKey(0)
        params = materialize(moe_lib.moe_decl(cfg), key)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1, 4), ("data", "model"))
        rules = logical_rules(mesh, cfg)
        for B, S in ((1, 1), (2, 1), (1, 3)):   # T_full < msize or ragged
            x = jax.random.normal(jax.random.fold_in(key, 10 * B + S),
                                  (B, S, cfg.d_model))
            y_ref, aux_ref = moe_lib.moe_mlp(params, x, cfg)
            with mesh:
                y_ep, aux_ep = jax.jit(lambda p, x: moe_mlp_ep(
                    p, x, cfg, mesh, rules, fsdp=False))(params, x)
            np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                                       rtol=3e-3, atol=3e-3)
            assert abs(float(aux_ep) - float(aux_ref)) < 1e-3, (B, S)
            print("TINY OK", B, S)
    """, 8, repo_src)
    assert out.count("TINY OK") == 3


# --------------------------------------------------- consensus modes
def test_ep_digest_vote_agrees_with_faithful_when_honest(repo_src):
    """With no attacker the cheap digest vote must select exactly the
    outputs the faithful full-tensor vote selects."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import get_config
        from repro.models import moe as moe_lib
        from repro.models.moe_ep import moe_mlp_ep
        from repro.models.builder import materialize
        from repro.models.config import RedundancyConfig
        from repro.sharding import logical_rules
        cfg = get_config("qwen2-moe-a2.7b", smoke=True)
        cfg = dataclasses.replace(cfg, capacity_factor=8.0,
                                  padded_num_experts=4, moe_impl="ep")
        key = jax.random.PRNGKey(0)
        params = materialize(moe_lib.moe_decl(cfg), key)
        x = jax.random.normal(jax.random.fold_in(key, 1),
                              (4, 16, cfg.d_model))
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1, 2, 4), ("data", "replica", "model"))
        rules = logical_rules(mesh, cfg)
        ys = {}
        for mode in ("faithful", "digest"):
            tcfg = dataclasses.replace(
                cfg, redundancy=RedundancyConfig(2, mode))
            with mesh:
                ys[mode], _ = jax.jit(lambda p, x: moe_mlp_ep(
                    p, x, tcfg, mesh, rules, fsdp=False))(params, x)
        np.testing.assert_allclose(np.asarray(ys["digest"]),
                                   np.asarray(ys["faithful"]),
                                   rtol=1e-5, atol=1e-6)
        print("HONEST AGREEMENT OK")
    """, 8, repo_src)
    assert "HONEST AGREEMENT OK" in out


def test_ep_shared_expert_tamper_covered_by_vote(repo_src):
    """Shared experts used to run outside the shard_map and skip
    ``_ep_vote`` — a tampered shared expert was invisible to redundancy
    voting.  Now (a) a minority attacker's tampering of the shared rows
    is repaired, and (b) a majority coalition corrupts the SHARED
    component too (isolated by differencing runs with and without the
    shared expert): the shared path demonstrably flows through the
    vote."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import get_config
        from repro.core.trusted_moe import LMAttack
        from repro.models import moe as moe_lib
        from repro.models.moe_ep import moe_mlp_ep
        from repro.models.builder import materialize
        from repro.models.config import RedundancyConfig
        from repro.sharding import logical_rules
        cfg = get_config("qwen2-moe-a2.7b", smoke=True)
        cfg = dataclasses.replace(cfg, capacity_factor=8.0,
                                  padded_num_experts=4, moe_impl="ep",
                                  redundancy=RedundancyConfig(2, "faithful"))
        assert cfg.num_shared_experts >= 1
        no_sh = dataclasses.replace(cfg, num_shared_experts=0)
        key = jax.random.PRNGKey(0)
        params = materialize(moe_lib.moe_decl(cfg), key)
        x = jax.random.normal(jax.random.fold_in(key, 1),
                              (4, 16, cfg.d_model))
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1, 2, 4), ("data", "replica", "model"))
        rules = logical_rules(mesh, cfg)
        def run(c, attack):
            with mesh:
                y, _ = jax.jit(lambda p, x: moe_mlp_ep(
                    p, x, c, mesh, rules, fsdp=False,
                    attack=attack))(params, x)
            return np.asarray(y)
        minority = LMAttack(malicious_replicas=(1,), noise_std=4.0)
        majority = LMAttack(malicious_replicas=(0, 1), noise_std=4.0)
        clean = run(cfg, None)
        np.testing.assert_allclose(run(cfg, minority), clean,
                                   rtol=1e-5, atol=1e-5)
        print("MINORITY REPAIRED")
        # shared contribution under majority collusion: y(with shared) -
        # y(routed only) must no longer equal the clean shared output
        sh_corrupt = run(cfg, majority) - run(no_sh, majority)
        sh_clean = clean - run(no_sh, None)
        assert not np.allclose(sh_corrupt, sh_clean, atol=1e-4)
        print("MAJORITY REACHES SHARED")
    """, 8, repo_src)
    assert "MINORITY REPAIRED" in out and "MAJORITY REACHES SHARED" in out


# --------------------------------------------------- mesh factories
def test_mesh_factories_derive_widths_from_device_count(repo_src):
    """launch.mesh used to assume 16x16 pods; the trusted/host/edge
    factories must now fold whatever jax.devices() reports."""
    out = run_with_devices("""
        import jax, pytest
        from repro.launch.mesh import (make_edge_mesh, make_host_mesh,
                                       make_trusted_mesh)
        def shape(m):
            return dict(zip(m.axis_names, m.devices.shape))
        m = make_trusted_mesh(2)
        assert shape(m) == {"data": 1, "replica": 2, "model": 4}, shape(m)
        m = make_trusted_mesh(4)
        assert shape(m) == {"data": 1, "replica": 4, "model": 2}, shape(m)
        with pytest.raises(ValueError):
            make_trusted_mesh(3)                 # 8 % 3 != 0
        m = make_host_mesh()
        assert shape(m) == {"data": 1, "model": 8}
        m = make_host_mesh(num_experts=6)        # widest divisor of both
        assert shape(m) == {"data": 4, "model": 2}, shape(m)
        m = make_edge_mesh(8)
        assert shape(m) == {"data": 1, "model": 8}
        m = make_edge_mesh(6)
        assert shape(m) == {"data": 4, "model": 2}, shape(m)
        m = make_edge_mesh(8, shards=4)
        assert shape(m) == {"data": 2, "model": 4}
        with pytest.raises(ValueError):
            make_edge_mesh(8, shards=3)          # 8 devices % 3 != 0
        with pytest.raises(ValueError):
            make_edge_mesh(6, shards=4)          # 6 experts % 4 != 0
        print("MESH FACTORIES OK")
    """, 8, repo_src)
    assert "MESH FACTORIES OK" in out
