"""Multi-device tests (subprocess with virtual CPU devices): sharding
rules, trusted-MoE consensus under attack, small-mesh lower/compile, and
the hloanalysis loop correction."""
from conftest import run_with_devices


def test_trusted_moe_vote_recovers_under_attack(repo_src):
    """r=4 replicas, 1 malicious: faithful AND digest modes reproduce the
    clean expert outputs bit-for-bit; 3 colluding replicas win instead."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.trusted_moe import make_trust, LMAttack
        from repro.models.config import RedundancyConfig
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1, 4, 2), ("data", "replica", "model"))
        y = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 4, 16))
        for mode in ("faithful", "digest"):
            clean = make_trust(mesh, RedundancyConfig(4, mode), True, None)
            atk = make_trust(mesh, RedundancyConfig(4, mode), True,
                             LMAttack(malicious_replicas=(1,), noise_std=3.0))
            maj = make_trust(mesh, RedundancyConfig(4, mode), True,
                             LMAttack(malicious_replicas=(0, 1, 2),
                                      noise_std=3.0))
            with mesh:
                got_clean = jax.jit(clean)(y)
                got_atk = jax.jit(atk)(y)
                got_maj = jax.jit(maj)(y)
            np.testing.assert_allclose(np.asarray(got_clean),
                                       np.asarray(y), rtol=0, atol=1e-6)
            np.testing.assert_allclose(np.asarray(got_atk),
                                       np.asarray(y), rtol=0, atol=1e-6)
            assert not np.allclose(np.asarray(got_maj), np.asarray(y)), mode
            print(mode, "OK")
    """, 8, repo_src)
    assert "faithful OK" in out and "digest OK" in out


def test_trusted_train_step_end_to_end(repo_src):
    """A trusted MoE train step on a (1, 2, 2) mesh runs under attack and
    produces finite loss equal to the attack-free loss (vote repairs)."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import get_config
        from repro.core.trusted_moe import LMAttack
        from repro.models.config import RedundancyConfig
        from repro.optim import adamw
        from repro.train.loop import init_model
        from repro.train.step import make_train_step
        cfg = get_config("bmoe-paper", smoke=True)
        cfg = dataclasses.replace(cfg,
            redundancy=RedundancyConfig(2, "faithful"), train_microbatches=1)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1, 2, 2), ("data", "replica", "model"))
        params = init_model(cfg, seed=0)
        opt = adamw.init(params)
        toks = jax.random.randint(jax.random.PRNGKey(0), (4, 32), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks, "labels": toks}
        losses = {}
        for name, atk in [("clean", None),
                          ("attacked", LMAttack(malicious_replicas=(1,),
                                                noise_std=5.0))]:
            step = make_train_step(cfg, adamw.AdamWConfig(total_steps=10),
                                   mesh, attack=atk, remat=False)
            with mesh:
                _, _, m = jax.jit(step)(params, opt, batch)
            losses[name] = float(m["loss"])
        assert np.isfinite(losses["clean"])
        assert abs(losses["clean"] - losses["attacked"]) < 1e-3, losses
        print("TRUSTED TRAIN OK", losses)
    """, 4, repo_src)
    assert "TRUSTED TRAIN OK" in out


def test_small_mesh_train_and_decode_compile(repo_src):
    """The production step functions lower+compile on a small (2, 4) mesh
    with real (materialized) params — an executable mini dry-run."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.launch import shapes as shp
        from repro.models.builder import materialize, partition_specs
        from repro.optim import adamw
        from repro.sharding import logical_rules
        from repro.train.loop import init_model
        from repro.train.step import make_step
        import dataclasses
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        for arch in ("qwen2-moe-a2.7b", "mamba2-2.7b", "gemma3-27b"):
            cfg = get_config(arch, smoke=True)
            cfg = dataclasses.replace(cfg, train_microbatches=1)
            params = init_model(cfg, seed=0)
            toks = jax.random.randint(jax.random.PRNGKey(0), (4, 64), 0,
                                      cfg.vocab_size)
            step = make_step(cfg, "train", mesh,
                             opt_cfg=adamw.AdamWConfig(total_steps=5),
                             remat=False)
            opt = adamw.init(params)
            with mesh:
                _, _, m = jax.jit(step)(params, opt,
                                        {"tokens": toks, "labels": toks})
            assert np.isfinite(float(m["loss"])), arch
            print(arch, "mesh-train OK", float(m["loss"]))
    """, 8, repo_src)
    assert out.count("mesh-train OK") == 3


def test_hloanalysis_loop_correction(repo_src):
    """Scan vs unrolled compile of the same model: loop-corrected
    collective bytes and dot flops from the scanned HLO must match the
    unrolled ground truth within 2%."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch import hloanalysis
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        W = jax.ShapeDtypeStruct((6, 128, 128), jnp.float32)
        X = jax.ShapeDtypeStruct((16, 128), jnp.float32)
        ws = NamedSharding(mesh, P(None, None, "model"))
        xs = NamedSharding(mesh, P("data", None))
        def scanned(x, w):
            def body(c, wi):
                y = c @ wi
                y = jax.lax.with_sharding_constraint(
                    y, NamedSharding(mesh, P("data", None)))
                return y, None
            y, _ = jax.lax.scan(body, x, w)
            return y
        def unrolled(x, w):
            for i in range(6):
                y = x @ w[i]
                x = jax.lax.with_sharding_constraint(
                    y, NamedSharding(mesh, P("data", None)))
            return x
        with mesh:
            t1 = jax.jit(scanned, in_shardings=(xs, ws)).lower(X, W).compile().as_text()
            t2 = jax.jit(unrolled, in_shardings=(xs, ws)).lower(X, W).compile().as_text()
        a1 = hloanalysis.analyze(t1)
        a2 = hloanalysis.analyze(t2)
        assert a2["dot_flops"] > 0
        rel = abs(a1["dot_flops"] - a2["dot_flops"]) / a2["dot_flops"]
        assert rel < 0.02, (a1["dot_flops"], a2["dot_flops"])
        c1, c2 = a1["total_collective_bytes"], a2["total_collective_bytes"]
        assert c2 > 0 and abs(c1 - c2) / c2 < 0.02, (c1, c2)
        print("HLO LOOP CORRECTION OK", a1["dot_flops"], c1)
    """, 8, repo_src)
    assert "HLO LOOP CORRECTION OK" in out


def test_fsdp_param_rules(repo_src):
    out = run_with_devices("""
        import jax
        from repro.configs import get_config
        from repro.sharding import logical_rules
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_config("qwen3-32b")
        act = logical_rules(mesh, cfg)
        par = logical_rules(mesh, cfg, params=True)
        assert act["embed"] is None
        assert par["embed"] == ("data",)
        assert par["vocab"] == "model"
        print("RULES OK")
    """, 8, repo_src)
    assert "RULES OK" in out


def test_moe_ep_matches_gspmd_path(repo_src):
    """shard_map expert-parallel MoE (all_to_all dispatch) must agree with
    the single-device GSPMD oracle when capacity is ample."""
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
        x = jax.random.normal(jax.random.fold_in(key, 1),
                              (4, 32, cfg.d_model))
        y_ref, aux_ref = moe_lib.moe_mlp(params, x, cfg)   # no mesh
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = logical_rules(mesh, cfg)
        with mesh:
            y_ep, aux_ep = jax.jit(lambda p, x: moe_mlp_ep(
                p, x, cfg, mesh, rules, fsdp=False))(params, x)
        np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                                   rtol=3e-3, atol=3e-3)
        assert abs(float(aux_ep) - float(aux_ref)) < 1e-3
        print("EP MATCHES GSPMD")
    """, 8, repo_src)
    assert "EP MATCHES GSPMD" in out


def test_moe_ep_trusted_vote(repo_src):
    """EP + B-MoE consensus: a malicious replica's manipulation of the
    expert outputs is repaired inside the EP shard_map."""
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
                                  padded_num_experts=4, moe_impl="ep")
        key = jax.random.PRNGKey(0)
        params = materialize(moe_lib.moe_decl(cfg), key)
        x = jax.random.normal(jax.random.fold_in(key, 1),
                              (4, 16, cfg.d_model))
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1, 2, 4), ("data", "replica", "model"))
        rules = logical_rules(mesh, cfg)
        for mode in ("faithful", "digest"):
            tcfg = dataclasses.replace(
                cfg, redundancy=RedundancyConfig(2, mode))
            with mesh:
                clean, _ = jax.jit(lambda p, x: moe_mlp_ep(
                    p, x, tcfg, mesh, rules, fsdp=False))(params, x)
                attacked, _ = jax.jit(lambda p, x: moe_mlp_ep(
                    p, x, tcfg, mesh, rules, fsdp=False,
                    attack=LMAttack(malicious_replicas=(1,),
                                    noise_std=4.0)))(params, x)
            np.testing.assert_allclose(np.asarray(attacked),
                                       np.asarray(clean), rtol=1e-5,
                                       atol=1e-5)
            print(mode, "EP VOTE OK")
    """, 8, repo_src)
    assert out.count("EP VOTE OK") == 2
