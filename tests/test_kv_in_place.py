"""The serve step writes each advancing slot's KV row in place.

``forward_serve_chunk`` drops the cache update of every slot that does
not advance at a micro-step (``mode="drop"`` on the row scatter) and
carries the stacked layer cache through the layer scan, so the compiled
step neither selects between an old and a new cache nor copies one: a
donated cache is updated where it lies.  The outputs the host reads are
those of a one-slot decode of the same tokens, and every row the chunk
does not advance keeps its bits."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as tfm
from repro.models.builder import materialize
from repro.models.config import LayerSpec
from repro.train.loop import init_model
from repro.train.step import make_serve_chunk_step


@pytest.mark.parametrize("layers", [1, 2])
def test_serve_step_updates_cache_in_place(layers):
    """Compiled with the cache donated, the step's output aliases the
    whole cache and its temporaries stay under one and a half caches:
    no whole-cache select or copy is left in the step."""
    cfg = dataclasses.replace(get_config("bmoe-paper", smoke=True),
                              num_layers=layers, num_blocks=layers)
    B, S, C = 4, 512, 4
    params = jax.eval_shape(lambda: init_model(cfg, seed=0))
    caches = jax.eval_shape(lambda: materialize(
        tfm.cache_decl(cfg, B, S), jax.random.PRNGKey(0)))
    nbytes = sum(a.size * a.dtype.itemsize
                 for a in jax.tree_util.tree_leaves(caches))
    step = jax.jit(make_serve_chunk_step(cfg), donate_argnums=1)
    batch = {"tokens": jax.ShapeDtypeStruct((B, C), jnp.int32),
             **{k: jax.ShapeDtypeStruct((B,), jnp.int32)
                for k in ("start", "pos", "lengths", "adv")}}
    mem = step.lower(params, caches, batch).compile().memory_analysis()
    assert mem.alias_size_in_bytes == nbytes
    assert mem.temp_size_in_bytes < 1.5 * nbytes


B, C, S = 4, 4, 40
# slot 0 ends its prompt mid-chunk and generates on; slot 1 is a prefill
# capped below C; slot 2 is idle; slot 3 decodes and stops mid-chunk.
# Slots 0 and 3 cross the end of a 32-row ring window.
POS = [29, 6, 11, 30]
LENGTHS = [2, 2, 0, 0]
ADV = [C, 2, 0, 3]

CASES = {
    "float32": ("bmoe-paper", {}, False),
    "int8": ("bmoe-paper", {"kv_cache_dtype": "int8"}, False),
    "local_attn": ("gemma3-27b", {}, False),
    "rglru": ("recurrentgemma-2b", {}, False),
    "ssm": ("mamba2-2.7b", {}, False),
    "remainder": ("bmoe-paper", {"num_layers": 3, "num_blocks": 2,
                                 "remainder": (LayerSpec("attn", "moe"),)},
                  False),
    "unrolled": ("bmoe-paper", {}, True),
}


def _randomize(tree, key):
    """Every cache leaf filled with values from ``key``."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))
    out = [jax.random.randint(k, a.shape, -127, 128).astype(a.dtype)
           if a.dtype == jnp.int8 else
           jax.random.uniform(k, a.shape, a.dtype, -1.0, 1.0)
           for a, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, out)


def _slot(caches, b):
    """Slot ``b``'s caches, one slot wide (stacked blocks: batch axis 1)."""
    out = {"blocks": jax.tree_util.tree_map(lambda a: a[:, b:b + 1],
                                            caches["blocks"])}
    if "remainder" in caches:
        out["remainder"] = jax.tree_util.tree_map(lambda a: a[b:b + 1],
                                                  caches["remainder"])
    return out


@pytest.mark.parametrize("case", list(CASES))
def test_serve_chunk_matches_one_slot_decode(case):
    name, overrides, unroll = CASES[case]
    cfg = dataclasses.replace(get_config(name, smoke=True), **overrides)
    params = init_model(cfg, seed=1)
    caches = _randomize(materialize(tfm.cache_decl(cfg, B, S),
                                    jax.random.PRNGKey(0)),
                        jax.random.PRNGKey(2))
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, cfg.vocab_size, (B, C)).astype(np.int32)
    start = rng.integers(0, cfg.vocab_size, B).astype(np.int32)
    step = jax.jit(make_serve_chunk_step(cfg, unroll=unroll))
    outs, new = step(params, caches, {
        "tokens": jnp.asarray(tokens), "start": jnp.asarray(start),
        "pos": jnp.asarray(POS, jnp.int32),
        "lengths": jnp.asarray(LENGTHS, jnp.int32),
        "adv": jnp.asarray(ADV, jnp.int32)})
    outs = np.asarray(outs)

    decode = jax.jit(lambda c, tok, pos: tfm.forward_decode(
        params, c, tok, pos, cfg, unroll=unroll))
    for b in range(B):
        ref, cur = _slot(caches, b), int(start[b])
        for t in range(ADV[b]):
            feed = int(tokens[b, t]) if t < LENGTHS[b] else cur
            logits, ref = decode(ref, jnp.full((1, 1), feed, jnp.int32),
                                 jnp.int32(POS[b] + t))
            cur = int(logits[0, -1].argmax())
            assert outs[t, b] == cur, (case, b, t)
        for got, want, before in zip(
                jax.tree_util.tree_leaves(_slot(new, b)),
                jax.tree_util.tree_leaves(ref),
                jax.tree_util.tree_leaves(_slot(caches, b))):
            got, want, before = map(np.asarray, (got, want, before))
            kept = want == before          # rows the one-slot decode left
            np.testing.assert_array_equal(got[kept], before[kept])
            # written rows: a four-slot and a one-slot product round apart
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
