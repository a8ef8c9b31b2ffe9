"""The width at which the serving engine holds its matrix products'
weights: the rule (``train.step.product_dtype``), the held tree
(``train.step.at_product_width``), the product sites that read a
narrow weight (``models.layers.product``), and an engine on the narrow
path, all on the CPU.  On a TPU the rule holds bfloat16 at one
bfloat16 pass; here the narrow path is reached by asking the rule for
a TPU's width."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import layers
from repro.models import moe as moe_lib
from repro.models import transformer as tfm
from repro.models.builder import materialize
from repro.optim import adamw
from repro.serve.engine import ServingEngine
from repro.train.loop import init_model
from repro.train.step import (at_product_width, make_serve_chunk_step,
                              make_train_step, product_dtype)

# leaf names that are matrix products' weights wherever they sit
PRODUCTS = {"wq", "wk", "wv", "wo", "wkva", "wkvb", "router", "w_gate",
            "w_up", "w_down", "lm_head"}

ARCHS = {
    # GQA with projection biases, MoE with shared experts, untied head
    "qwen2-moe": ("qwen2-moe-a2.7b", {"padded_num_experts": 0}),
    # latent attention, a leading dense layer, sigmoid router with bias
    "moonlight": ("moonlight-16b-a3b", {}),
    # dense, tied embedding
    "smollm": ("smollm-360m", {}),
    # dense GQA with projection biases, untied head
    "qwen2.5": ("qwen2.5-3b", {}),
}


def _model(name):
    arch, overrides = ARCHS[name]
    cfg = dataclasses.replace(get_config(arch, smoke=True), **overrides)
    return cfg, init_model(cfg, seed=0)


def _name(path):
    return [k.key for k in path if isinstance(k, jax.tree_util.DictKey)][-1]


@pytest.mark.parametrize("precision,platform,want", [
    ("default", "tpu", jnp.bfloat16),
    ("bfloat16", "tpu", jnp.bfloat16),
    ("high", "tpu", None),
    ("highest", "tpu", None),
    ("float32", "tpu", None),
    ("default", "cpu", None),
    ("bfloat16", "cpu", None),
    ("default", "gpu", None),
])
def test_rule_maps_precision_and_platform_to_the_width(precision, platform,
                                                       want):
    cfg = dataclasses.replace(get_config("smollm-360m", smoke=True),
                              matmul_precision=precision)
    assert product_dtype(cfg, platform) == want


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_held_tree_narrows_exactly_the_product_leaves(name):
    """Every product weight is held in bfloat16; the embedding (a tied
    head's too), the norm scales, biases and ``b_corr`` are the
    caller's own arrays; the caller's tree is as it was."""
    cfg, params = _model(name)
    before = jax.tree_util.tree_map(np.asarray, params)
    held, narrowed = at_product_width(params, jnp.bfloat16)
    flat = jax.tree_util.tree_flatten_with_path(held)[0]
    given = jax.tree_util.tree_leaves(params)
    names = set()
    for (path, h), g in zip(flat, given):
        names.add(_name(path))
        if _name(path) in PRODUCTS:
            assert h.dtype == jnp.bfloat16, path
            np.testing.assert_array_equal(h, g.astype(jnp.bfloat16))
        else:
            assert h is g, path
    assert sorted(map(_name, narrowed)) == sorted(
        _name(p) for p, _ in flat if _name(p) in PRODUCTS)
    assert "embed" in names and "norm1" in names
    assert ("lm_head" in names) != cfg.tie_embeddings
    if name == "moonlight":
        assert {"b_corr", "kva_norm", "wkvb"} <= names
    jax.tree_util.tree_map(
        lambda a, b: (a.dtype == jnp.float32
                      and np.testing.assert_array_equal(a, b)),
        params, before)
    assert at_product_width(params, None) == (params, [])


@pytest.mark.parametrize("spec,xs,ws", [
    (None, (3, 1, 64), (64, 48)),
    ("bsd,de->bse", (2, 5, 64), (64, 8)),
    ("becd,edf->becf", (2, 4, 3, 64), (4, 64, 40)),
    ("bhn,chn->bhc", (3, 4, 16), (32, 4, 16)),
])
def test_product_site_reads_a_narrow_weight_as_one_bf16_pass(spec, xs, ws):
    """A bfloat16 weight: the activation rounded to bfloat16, the
    products exact and summed in float32, the result float32."""
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, xs, jnp.float32)
    w = jax.random.normal(kw, ws, jnp.float32).astype(jnp.bfloat16)
    got = layers.product(x, w, spec)
    up = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    spec = spec or "...d,df->...f"
    want = jnp.einsum(spec, up(x), up(w), precision="highest")
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def _requests(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return [{"id": i, "max_new_tokens": int(rng.integers(3, 7)),
             "prompt": rng.integers(0, cfg.vocab_size,
                                    int(rng.integers(2, 11))
                                    ).astype(np.int32)} for i in range(n)]


def _logits(cfg, params, seq):
    logits, _ = tfm.forward_train(params, jnp.asarray([seq], jnp.int32), cfg,
                                  remat=False, q_chunk=8, kv_chunk=8)
    return np.asarray(logits[0])


def _greedy(cfg, params, prompt, n, width=32):
    """Greedy tokens of the full forward pass over ``params``, with
    every MoE assignment inside capacity (as a decode step has it).
    Each pass reads ``width`` positions, the tokens so far and zeros
    after them, which the causal mask keeps from the last token's
    logits: one shape for every pass."""
    if cfg.num_experts:
        cfg = dataclasses.replace(
            cfg, capacity_factor=cfg.num_experts / cfg.num_experts_per_tok)
    toks = [int(t) for t in prompt]
    for _ in range(n):
        seq = toks + [0] * (width - len(toks))
        toks.append(int(_logits(cfg, params, seq)[len(toks) - 1].argmax()))
    return toks[len(prompt):]


@pytest.mark.parametrize("name", ["moonlight", "qwen2-moe", "smollm"])
def test_engine_on_the_narrow_path_serves_the_narrow_model(name):
    """An engine given the tree the rule holds on a TPU keeps it (the
    CPU narrows nothing more) and serves exactly the greedy tokens of
    the same bfloat16 weights; its gauges count the held product
    bytes, and on this platform none narrowed by the engine."""
    cfg, params = _model(name)
    held, narrowed = at_product_width(params, product_dtype(cfg, "tpu"))
    assert narrowed
    eng = ServingEngine(cfg, held, batch_slots=2, cache_len=32,
                        prefill_chunk=4)
    reqs = _requests(cfg, 4, 5)
    eng.submit(reqs)
    done = eng.run()
    for r in reqs:
        assert done[r["id"]] == _greedy(cfg, eng.params, r["prompt"],
                                        r["max_new_tokens"]), r["id"]
    products = sum(a.nbytes for p, a in
                   jax.tree_util.tree_flatten_with_path(held)[0]
                   if _name(p) in PRODUCTS)
    metrics = eng.obs.metrics
    assert metrics.value("serve.product_weight_bytes") == products
    assert metrics.value("serve.product_weight_narrowed_bytes") == 0


@pytest.mark.parametrize("name", ["smollm", "qwen2.5"])
def test_narrow_weights_keep_the_logits_within_bf16_tolerance(name):
    """The logits of the tree the narrow engine serves lie within
    bfloat16's rounding of the float32 path's.  Dense models: in a MoE
    model the rounding may turn a near tie of two experts the other
    way, as JAX's default precision on a TPU may, and the logits then
    differ by more than rounding (the expert products' arithmetic is
    the product-site test's)."""
    cfg, params = _model(name)
    held, _ = at_product_width(params, jnp.bfloat16)
    seq = np.random.default_rng(1).integers(0, cfg.vocab_size, 12)
    want, got = _logits(cfg, params, seq), _logits(cfg, held, seq)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 0.02 * scale
    assert np.abs(got - want).max() > 0          # the weights were rounded


def test_engine_on_the_cpu_holds_weights_as_given():
    cfg, params = _model("moonlight")
    eng = ServingEngine(cfg, params, batch_slots=2, cache_len=16)
    assert eng.params is params
    metrics = eng.obs.metrics
    assert metrics.value("serve.product_weight_bytes") == sum(
        a.nbytes for p, a in jax.tree_util.tree_flatten_with_path(params)[0]
        if _name(p) in PRODUCTS)
    assert metrics.value("serve.product_weight_narrowed_bytes") == 0


def _jaxpr(kind, cfg, params):
    if kind == "train":
        step = make_train_step(cfg, adamw.AdamWConfig())
        toks = jnp.zeros((2, 8), jnp.int32)
        return jax.make_jaxpr(step)(params, adamw.init(params),
                                    {"tokens": toks, "labels": toks})
    caches = materialize(tfm.cache_decl(cfg, 2, 16), jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((2, 4), jnp.int32),
             **{k: jnp.zeros(2, jnp.int32)
                for k in ("start", "pos", "lengths", "adv")}}
    return jax.make_jaxpr(make_serve_chunk_step(cfg))(params, caches, batch)


@pytest.mark.parametrize("kind", ["train", "serve"])
@pytest.mark.parametrize("name", ["qwen2-moe", "moonlight"])
def test_float32_weights_lower_to_the_plain_products(monkeypatch, kind,
                                                     name):
    """With float32 weights every product site is the plain ``x @ w``
    or einsum: the step's jaxpr is the one those give, with no
    conversion to a narrower type."""
    cfg, params = _model(name)
    got = str(_jaxpr(kind, cfg, params))

    def plain(x, w, spec=None):
        return x @ w if spec is None else jnp.einsum(spec, x, w)

    for mod in (layers, moe_lib, tfm):
        monkeypatch.setattr(mod, "product", plain)
    assert got == str(_jaxpr(kind, cfg, params))
    assert "new_dtype=bfloat16" not in got
