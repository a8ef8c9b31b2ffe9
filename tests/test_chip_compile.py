"""The main-path Pallas kernels compiled for a described TPU v5e chip.

No chip is attached: ``jax.experimental.topologies`` describes a
``v5e:2x2`` host and the TPU compiler compiles for one of its chips.  That
rejects what interpret mode accepts — block shapes off the (8, 128)
tiling, more VMEM than a kernel may use — at no chip time.

Shapes are the paper's B-MoE round (§V): N=10 MLP experts 784->256->10,
top-k K=3, batch 256, sparse dispatch at ``capacity_factor=1.25`` (96
bucket slots per expert), 4 commitment chunks per expert.

Only one process at a time may load the TPU library, so the topology is
described inside a module-scoped fixture (never at import), and the
compiles happen in the test's own process.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import audit_gemm, moe_gemm, redundancy_vote

N, D_IN, HIDDEN, CLASSES = 10, 784, 256, 10       # experts, MLP widths
CAPACITY = 96                  # ceil(1.25 * 256 * 3 / 10) -> mult of 8
CHUNK = CAPACITY // 4          # rows per commitment leaf


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _hlo(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("d,f", [(D_IN, HIDDEN), (HIDDEN, CLASSES)],
                         ids=["layer1", "layer2"])
def test_moe_gemm_compiles_for_v5e(one_chip, d, f):
    buf = jax.ShapeDtypeStruct((N, CAPACITY, d), jnp.float32,
                               sharding=one_chip)
    w = jax.ShapeDtypeStruct((N, d, f), jnp.float32, sharding=one_chip)
    txt = _hlo(lambda b, w: moe_gemm.moe_gemm(b, w, interpret=False),
               buf, w)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("samples", [N * 4, 8],
                         ids=["commitment", "audit-bucket"])
def test_audit_mlp_compiles_for_v5e(one_chip, samples):
    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {"w1": spec((N, D_IN, HIDDEN)), "b1": spec((N, HIDDEN)),
              "w2": spec((N, HIDDEN, CLASSES)), "b2": spec((N, CLASSES))}
    txt = _hlo(lambda p, x, g: audit_gemm.audit_mlp(p, x, g,
                                                    interpret=False),
               params, spec((samples, CHUNK, D_IN)),
               spec((samples,), jnp.int32))
    assert "tpu_custom_call" in txt


def test_redundancy_vote_compiles_for_v5e(one_chip):
    # every edge's copy of every expert's bucketed output: (N, M, cap*C)
    pub = jax.ShapeDtypeStruct((N, 10, CAPACITY * CLASSES), jnp.float32,
                               sharding=one_chip)
    txt = _hlo(lambda p: redundancy_vote.pairwise_agreement(
        p, interpret=False), pub)
    assert "tpu_custom_call" in txt
