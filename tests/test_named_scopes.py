"""Named scopes in the compiled programs: the decode step's layer parts
and the grouped-GEMM kernels name their ops through ``jax.named_scope``,
which reaches each HLO op's ``op_name`` metadata, the name a profiler
trace carries for the op."""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops


def _scopes(compiled_text: str) -> set:
    """Every path component of every ``op_name`` in the HLO text."""
    return {part for name in re.findall(r'op_name="([^"]*)"', compiled_text)
            for part in name.split("/")}


@pytest.fixture(scope="module")
def decode_scopes():
    from repro.configs import get_config
    from repro.models import transformer as tfm
    from repro.models.builder import materialize
    from repro.train.loop import init_model
    cfg = get_config("qwen2-moe-a2.7b", smoke=True)
    params = init_model(cfg, seed=0)
    caches = materialize(tfm.cache_decl(cfg, 2, 16), jax.random.PRNGKey(0))
    step = jax.jit(lambda p, c, t, pos, m: tfm.forward_decode(
        p, c, t, pos, cfg, write_mask=m))
    text = step.lower(params, caches, jnp.zeros((2, 1), jnp.int32),
                      jnp.zeros(2, jnp.int32),
                      jnp.ones(2, bool)).compile().as_text()
    return _scopes(text)


@pytest.mark.parametrize("scope", ["attention", "kv_write", "moe", "head"])
def test_decode_step_names_its_scopes(decode_scopes, scope):
    assert scope in decode_scopes


def _moe_gemm():
    return jax.jit(lambda b, w: ops.moe_gemm(b, w, backend="ref")).lower(
        jnp.ones((2, 4, 8)), jnp.ones((2, 8, 3)))


def _audit_mlp():
    params = {"w1": jnp.ones((2, 8, 5)), "b1": jnp.ones((2, 5)),
              "w2": jnp.ones((2, 5, 3)), "b2": jnp.ones((2, 3))}
    return jax.jit(lambda p, x, g: ops.audit_mlp(p, x, g, backend="ref")
                   ).lower(params, jnp.ones((3, 4, 8)),
                           jnp.zeros(3, jnp.int32))


@pytest.mark.parametrize("scope,lowered", [("moe_gemm", _moe_gemm),
                                           ("audit_mlp", _audit_mlp)])
def test_kernel_wrappers_name_their_ops(scope, lowered):
    assert scope in _scopes(lowered().compile().as_text())
