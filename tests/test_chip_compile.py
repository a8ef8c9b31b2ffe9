"""The main-path Pallas kernels compiled for a described TPU v5e chip.

No chip is attached: ``jax.experimental.topologies`` describes a
``v5e:2x2`` host and the TPU compiler compiles for one of its chips.  That
rejects what interpret mode accepts — block shapes off the (8, 128)
tiling, more VMEM than a kernel may use — at no chip time.

Shapes are the paper's B-MoE round (§V): N=10 MLP experts 784->256->10,
top-k K=3, batch 256, sparse dispatch at ``capacity_factor=1.25`` (96
bucket slots per expert), 4 commitment chunks per expert.

The latent-attention decode kernel and the serve step that calls it are
compiled at ``moonlight-16b-a3b``'s chip share: 32 slots, a cache of
8,192 rows, 16 heads, latent 512, rotary key 64, one leading and 8
stacked layers.

Mixtral 8x7B's serve step (one block: hidden 4096, 32 query and 8 KV
heads, 8 experts of 14,336, vocabulary 32,000; 32 slots, a cache of
2,048 rows, 16 micro-steps a call) is compiled with the weights the
serving engine holds on a TPU.

Only one process at a time may load the TPU library, so the topology is
described inside a module-scoped fixture (never at import), and the
compiles happen in the test's own process.
"""
import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import audit_gemm, mla_decode, moe_gemm, redundancy_vote

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


# ------------------------------------------------- latent-attention decode
SLOTS, CAP, HEADS, LATENT, ROPE, STACKED = 32, 8192, 16, 512, 64, 8
LAYER_CKV = f"f32[{SLOTS},{CAP},{LATENT}]"          # one layer's latents
STACK = (f"f32[{STACKED},{SLOTS},{CAP},{LATENT}]",
         f"f32[{STACKED},{SLOTS},{CAP},{ROPE}]")


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _whole_cache_moves(txt: str):
    """Instructions of the HLO's loop bodies (not inside fusions) that
    copy, slice or rebuild in a loop fusion a whole layer's latents or a
    whole stacked cache leaf."""
    bodies = set(re.findall(r"body=%?([\w.\-]+)", txt))
    found, comp = [], None
    for line in txt.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        ins = re.match(r"\s+(?:ROOT )?%(\S+) = (f32\[[\d,]+\])\S* "
                       r"([\w\-]+)\(", line)
        if comp not in bodies or not ins:
            continue
        name, shape, op = ins.groups()
        moves = (op in ("copy", "copy-start", "dynamic-slice", "slice",
                        "transpose")
                 or (op == "fusion" and "kind=kLoop" in line))
        if moves and (shape == LAYER_CKV or shape in STACK):
            found.append(f"{name} {shape} {op}")
    return found


def test_mla_decode_compiles_for_v5e(one_chip):
    """The kernel takes the stacked caches whole and writes the rotary
    keys in place (given their buffer): their view (L, B, r, cap) is the
    buffer as the TPU lays it out, not a copy."""
    S = lambda shape, dt=jnp.float32: _spec(shape, one_chip, dt)
    fn = lambda q, qp, kp, ckv, kpe, pos, slot, layer: mla_decode.mla_decode(
        q, qp, kp, ckv, kpe, pos, slot, layer, scale=192 ** -0.5,
        interpret=False)
    txt = jax.jit(fn, donate_argnums=(4,)).lower(
        S((SLOTS, HEADS, LATENT)), S((SLOTS, HEADS, ROPE)),
        S((SLOTS, ROPE)), S((STACKED, SLOTS, CAP, LATENT)),
        S((STACKED, SLOTS, CAP, ROPE)), S((SLOTS,), jnp.int32),
        S((SLOTS,), jnp.int32), S((), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in txt
    cache = rf"f32\[{STACKED},{SLOTS},({CAP},\d+|{ROPE},{CAP})\]"
    assert not re.search(cache + r"\S* (copy|transpose)\(", txt)


def test_serve_step_reads_the_latent_cache_in_place_for_v5e(one_chip,
                                                           monkeypatch):
    """Moonlight's fused serve step with the kernel: its custom call sits
    under the layer's named scope ``mla`` (which the benchmark's
    attention roofline times), no loop body copies or slices a layer's
    latent cache or a stacked cache leaf, and its temporaries are no
    larger than with the whole-cache XLA expression."""
    from repro.configs import get_config
    from repro.models import transformer as tfm
    from repro.models.builder import abstract
    from repro.train.step import make_serve_chunk_step
    cfg = dataclasses.replace(
        get_config("moonlight-16b-a3b"), num_layers=STACKED + 1,
        num_blocks=STACKED, held_experts=tuple(range(8)),
        vocab_size=20480, matmul_precision="high").validate()
    place = lambda tree: jax.tree_util.tree_map(
        lambda a: _spec(a.shape, one_chip, a.dtype), tree)
    params = place(abstract(tfm.model_decl(cfg), jnp.float32))
    caches = place(abstract(tfm.cache_decl(cfg, SLOTS, CAP), jnp.float32))
    batch = {"tokens": _spec((SLOTS, 16), one_chip, jnp.int32),
             **{k: _spec((SLOTS,), one_chip, jnp.int32)
                for k in ("start", "pos", "lengths", "adv")}}
    compiled = {}
    for backend in ("ref", "pallas"):
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", backend)
        compiled[backend] = jax.jit(make_serve_chunk_step(cfg),
                                    donate_argnums=(1,)).lower(
            params, caches, batch).compile()
    txt = compiled["pallas"].as_text()
    calls = [line for line in txt.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 2                  # the leading layer, the scan's
    for line in calls:
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        assert "mla" in op_name.split("/"), op_name
    assert _whole_cache_moves(txt) == []
    temp = {k: c.memory_analysis().temp_size_in_bytes
            for k, c in compiled.items()}
    assert temp["pallas"] <= temp["ref"], temp


# ------------------------------------------ product weights' held width
MIXTRAL_SLOTS, MIXTRAL_CAP, CHUNK_WIDTH = 32, 2048, 16
BIG = 1 << 20                  # elements: a weight, not a token's values
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\]"
                    r"(\{[^}]*\})? ([\w\-]+)\(([^)]*)\)")
_BITS = {"f32": 32, "bf16": 16, "f16": 16}


def _mixtral():
    from repro.configs import get_config
    return dataclasses.replace(
        get_config("bmoe-paper"), num_layers=1, num_blocks=1, d_model=4096,
        num_heads=32, num_kv_heads=8, head_dim=128, d_ff=14336,
        moe_d_ff=14336, num_experts=8, num_experts_per_tok=2,
        num_shared_experts=0, vocab_size=32000, rope_theta=1e6,
        norm_eps=1e-5, tie_embeddings=False).validate()


def _held(cfg, sharding):
    """The abstract weights (float32 as given) and what the serving
    engine's rule holds of them on a TPU."""
    from repro.models import transformer as tfm
    from repro.models.builder import abstract
    from repro.train.step import at_product_width, product_dtype
    given = abstract(tfm.model_decl(cfg), jnp.float32)
    held = jax.eval_shape(
        lambda p: at_product_width(p, product_dtype(cfg, "tpu"))[0], given)
    place = lambda tree: jax.tree_util.tree_map(
        lambda a: _spec(a.shape, sharding, a.dtype), tree)
    return place(given), place(held)


def _serve_step(cfg, params, sharding, slots, cap):
    from repro.models import transformer as tfm
    from repro.models.builder import abstract
    from repro.train.step import make_serve_chunk_step
    caches = jax.tree_util.tree_map(
        lambda a: _spec(a.shape, sharding, a.dtype),
        abstract(tfm.cache_decl(cfg, slots, cap), jnp.float32))
    batch = {"tokens": _spec((slots, CHUNK_WIDTH), sharding, jnp.int32),
             **{k: _spec((slots,), sharding, jnp.int32)
                for k in ("start", "pos", "lengths", "adv")}}
    return jax.jit(make_serve_chunk_step(cfg)).lower(params, caches, batch)


def _weight_moves(txt: str, shapes):
    """Instructions that convert a float array shaped like a weight (its
    dimensions of size one aside) of a million elements or more to a
    narrower type, or copy one into the device's HBM (an on-chip copy,
    layout ``S(1)``, is the loop's prefetch)."""
    types, found = {}, []
    for line in txt.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, dt, dims, layout, op, args = m.groups()
        types[name] = (dt, tuple(int(d) for d in dims.split(",") if d))
        if op not in ("convert", "copy") or not args.strip():
            continue
        src = types.get(args.split(",")[0].strip().lstrip("%"))
        if src is None or src[0] not in _BITS or dt not in _BITS:
            continue
        core = tuple(d for d in src[1] if d != 1)
        if core not in shapes or int(np.prod(core)) < BIG:
            continue
        narrower = _BITS[dt] < _BITS[src[0]]
        if narrower or (op == "copy" and "S(1)" not in (layout or "")):
            found.append(f"{name} {src} -> {dt}{layout or ''} {op}")
    return found


def test_serve_step_reads_held_weights_as_held_for_v5e(one_chip):
    """Mixtral's serve step given the weights the engine holds on a TPU
    (every product weight in bfloat16): no held weight is converted or
    copied to HBM per call, every expert and head product reads its
    bfloat16 weight, and the temporaries fall by about the bfloat16
    weights against float32 weights, which the step converts each call."""
    from repro.models import transformer as tfm
    cfg = _mixtral()
    given, held = _held(cfg, one_chip)
    flat = jax.tree_util.tree_flatten_with_path(held)[0]
    products = [a for path, a in flat if tfm.is_product_weight(path)]
    assert {a.dtype for a in products} == {jnp.dtype(jnp.bfloat16)}
    shapes = {tuple(d for d in a.shape if d != 1) for a in products}
    compiled = {k: _serve_step(cfg, p, one_chip, MIXTRAL_SLOTS,
                               MIXTRAL_CAP).compile()
                for k, p in (("given", given), ("held", held))}
    txt = compiled["held"].as_text()
    assert _weight_moves(txt, shapes) == []
    assert _weight_moves(compiled["given"].as_text(), shapes) != []
    for ffn in ("14336", "32000"):
        assert re.search(rf"bf16\[[\d,]*{ffn}[\d,]*\]\S* parameter\(", txt)
    temp = {k: c.memory_analysis().temp_size_in_bytes
            for k, c in compiled.items()}
    narrow = sum(a.size * 2 for a in products)
    assert temp["held"] <= temp["given"] - 0.9 * narrow, (temp, narrow)


def test_moonlight_serve_step_reads_float32_weights_for_v5e(one_chip):
    """At ``matmul_precision`` ``high`` the rule holds every weight as
    given: Moonlight's step takes float32 weights on a TPU."""
    from repro.configs import get_config
    cfg = dataclasses.replace(
        get_config("moonlight-16b-a3b"), num_layers=STACKED + 1,
        num_blocks=STACKED, held_experts=tuple(range(8)),
        vocab_size=20480, matmul_precision="high").validate()
    given, held = _held(cfg, one_chip)
    assert held == given
    txt = _serve_step(cfg, held, one_chip, SLOTS, CAP).as_text()
    main = next(line for line in txt.splitlines()
                if "func.func public @main" in line)
    assert "bf16" not in main and "xf32>" in main
