"""Plain reference of one B-MoE training round (paper §V), and the
numbers that decide ``correct`` for a training cell.

One round on a batch ``x`` (B, in) with labels ``y``, written from the
paper's description and the configuration alone:

    logits = x Wg + bg
    the top-k experts per row; weights = softmax over their logits
    capacity c = ceil(capacity_factor * B * k / N), up to a multiple of
      8, at most B; an expert keeps the first c of its assignments in
      row order (row-major over (row, choice)) and drops the rest
    out_e(x) = relu(x W1_e + b1_e) W2_e + b2_e, for every expert
    y_hat = sum over kept choices of weight * out_e(x)
    loss = mean cross-entropy(softmax(y_hat), y)
    every parameter p <- p - lr * dloss/dp            (plain SGD)

Every expert is evaluated on every row, so no dispatch buffer is
involved; a dropped choice simply has weight 0.  The products run at
the configuration's ``matmul_precision``; the control runs the same
code in bfloat16.

``initial`` makes the starting weights from the seed with the
benchmark's own code, by the law the configuration states (normal,
1/sqrt(fan-in), gate 0.01, biases 0, keyed by each leaf's name); the
system's own first state is checked against it, never taken.
"""
from __future__ import annotations

import functools
import hashlib
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

PRECISION = {"default": jax.lax.Precision.DEFAULT,
             "highest": jax.lax.Precision.HIGHEST}


def _key(root, name: str):
    h = hashlib.sha256(name.encode()).digest()
    return jax.random.fold_in(root, int.from_bytes(h[:4], "big"))


def initial(c: Dict, seed: int):
    """(gate, experts) at the start of training, from ``seed``."""
    N, d, H, C = c["num_experts"], c["in_dim"], c["hidden"], c["num_classes"]
    kg, ke = jax.random.split(jax.random.PRNGKey(seed))
    normal = lambda k, shape, std: jax.random.normal(k, shape,
                                                     jnp.float32) * std
    gate = {"w": normal(_key(kg, "/w"), (d, N), 0.01),
            "b": jnp.zeros((N,), jnp.float32)}
    experts = {"w1": normal(_key(ke, "/w1"), (N, d, H), 1.0 / np.sqrt(d)),
               "b1": jnp.zeros((N, H), jnp.float32),
               "w2": normal(_key(ke, "/w2"), (N, H, C), 1.0 / np.sqrt(H)),
               "b2": jnp.zeros((N, C), jnp.float32)}
    return gate, experts


def capacity(c: Dict, batch: int) -> int:
    cap = math.ceil(c["capacity_factor"] * batch * c["top_k"]
                    / c["num_experts"])
    return max(min(-(-cap // 8) * 8, batch), 1)


def loss(params, x, y, *, c: Dict, precision: str, dtype=jnp.float32):
    gate, ex = params
    mm = functools.partial(jnp.einsum, precision=PRECISION[precision])
    cast = lambda a: a.astype(dtype)
    N, K = c["num_experts"], c["top_k"]
    B = x.shape[0]
    x = cast(x)
    logits = (mm("bd,dn->bn", x, cast(gate["w"])) + cast(gate["b"])).astype(
        jnp.float32)
    topv, topi = jax.lax.top_k(logits, K)
    w = jax.nn.softmax(topv, axis=-1).reshape(-1)             # (B*K,)
    eid = topi.reshape(-1)
    onehot = jax.nn.one_hot(eid, N, dtype=jnp.int32)
    position = ((jnp.cumsum(onehot, 0) - onehot) * onehot).sum(-1)
    keep = (position < capacity(c, B)).astype(jnp.float32)
    h = jax.nn.relu(mm("bd,ndh->nbh", x, cast(ex["w1"]))
                    + cast(ex["b1"])[:, None, :])
    out = (mm("nbh,nhc->nbc", h, cast(ex["w2"]))
           + cast(ex["b2"])[:, None, :]).astype(jnp.float32)
    rows = jnp.repeat(jnp.arange(B), K)
    y_hat = (out[eid, rows] * (w * keep)[:, None]).reshape(B, K, -1).sum(1)
    logp = jax.nn.log_softmax(y_hat, axis=-1)
    return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()


def make_step(c: Dict, precision: str, dtype=jnp.float32):
    """Jitted (params, x, y) -> (new params, loss, grads)."""
    def step(params, x, y):
        value, grads = jax.value_and_grad(loss)(params, x, y, c=c,
                                                precision=precision,
                                                dtype=dtype)
        new = jax.tree_util.tree_map(lambda p, g: p - c["lr"] * g,
                                     params, grads)
        return new, value, grads
    return jax.jit(step)


def _leaves(tree) -> Dict[str, np.ndarray]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): np.asarray(v, np.float64)
            for p, v in flat}


def norms(tree) -> Dict[str, float]:
    return {k: float(np.linalg.norm(v)) for k, v in _leaves(tree).items()}


def diff(a, b):
    return jax.tree_util.tree_map(lambda u, v: np.asarray(u, np.float64)
                                  - np.asarray(v, np.float64), a, b)


def worst_leaf_gap(got: Dict[str, float], want: Dict[str, float],
                   counted: List[str]) -> float:
    """Worst relative gap between two sets of leaf norms: |got - want|
    over the larger of the leaf's own reference norm and the median
    leaf's, over the counted leaves."""
    median = float(np.median([want[k] for k in counted]))
    return max(abs(got[k] - want[k]) / max(want[k], median) for k in counted)


def counted_leaves(ref_grads: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is more than a thousandth of the
    median leaf's: the others move by round-off alone."""
    median = float(np.median(list(ref_grads.values())))
    return sorted(k for k, v in ref_grads.items() if v > 1e-3 * median)


def trajectory(c: Dict, init, batches, *, precision: str,
               dtype=jnp.float32, rows: int = 0) -> Dict:
    """The first three steps from ``init`` on ``batches``: the losses,
    the parameters after one and after three steps, and the first
    gradient's norm per leaf.  ``rows`` > 0 keeps only that many rows
    of each batch (the half-batch fault planted in the reference)."""
    step = make_step(c, precision, dtype)
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype), init)
    out = {"losses": [], "p0": init}
    for i, (x, y) in enumerate(batches[:3]):
        if rows:
            x, y = x[:rows], y[:rows]
        params, value, grads = step(params, jnp.asarray(x), jnp.asarray(y))
        out["losses"].append(float(value))
        if i == 0:
            out["p1"], out["grads"] = params, norms(grads)
    out["p3"] = params
    return out


def numbers(c: Dict, program: Dict, reference: Dict) -> Dict[str, float]:
    """The three compared numbers for one run.  ``program`` holds the
    system's ``losses`` of the first three steps and its parameters
    ``p0``, ``p1`` (after one step) and ``p3`` (after three); its first
    gradient is worked out from its state after one step, as plain SGD
    applied it.  ``reference`` is a ``trajectory``."""
    counted = counted_leaves(reference["grads"])
    got_first = norms(jax.tree_util.tree_map(
        lambda a: a / c["lr"], diff(program["p0"], program["p1"])))
    return {
        "loss_gap": max(abs(g - w) / abs(w) for g, w in
                        zip(program["losses"], reference["losses"])),
        "first_grad_gap": worst_leaf_gap(got_first, reference["grads"],
                                         counted),
        "change_gap": worst_leaf_gap(
            norms(diff(program["p3"], program["p0"])),
            norms(diff(reference["p3"], reference["p0"])), counted),
    }
