"""B-MoE at LM scale: redundant expert execution + consensus vote as a
first-class feature of the MoE transformer (DESIGN.md §4).

Mesh layout: (data, replica, model) — the ``replica`` axis carries the
paper's "edges that all compute the activated experts": the batch is
sharded over ``data`` only, so every replica holds an identical copy of
its group's tokens and computes the routed experts redundantly (r x
compute, exactly the paper's redundancy cost).  The consensus vote is a
shard_map over the mesh that communicates *only* across ``replica``:

- mode="faithful" (the paper): all_gather the full expert-output buffer
  across replicas, replica-level majority vote per expert.
  Collective bytes ~ (r-1) x |buffer| per device.
- mode="digest" (beyond-paper): all_gather scalar per-expert digests
  (tiny), each replica checks itself against the majority digest, and
  the trusted value is recovered with one masked psum
  (sum(ok * y) / sum(ok) — honest copies are identical, so the mean of
  the agreeing copies IS the honest value).  Collective bytes
  ~ 2(r-1)/r x |buffer| — about r/2 x less traffic, same detection
  power against the paper's Gaussian-manipulation adversary.

An optional in-graph attack (malicious replica indices + noise) lets the
robustness be tested end-to-end under jit.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import ref as kref
from repro.launch.mesh import make_mesh


@dataclasses.dataclass(frozen=True)
class LMAttack:
    """In-graph adversary for LM-scale robustness tests/benchmarks."""
    malicious_replicas: tuple = ()
    noise_std: float = 1.0
    colluding: bool = True
    seed: int = 0


def _inject(y, attack: Optional[LMAttack]):
    if attack is None or not attack.malicious_replicas:
        return y
    rid = jax.lax.axis_index("replica")
    n_rep = jax.lax.axis_size("replica")
    mal = jnp.zeros((n_rep,), jnp.float32)
    mal = mal.at[jnp.array(attack.malicious_replicas, jnp.int32)].set(1.0)
    key = jax.random.PRNGKey(attack.seed)
    if not attack.colluding:
        key = jax.random.fold_in(key, rid)
    noise = jax.random.normal(key, y.shape, y.dtype)
    return y + attack.noise_std * noise * mal[rid]


def _vote_faithful(y, attack):
    """y: local (B, E, C, d) expert-output buffer block."""
    B, E, C, d = y.shape
    y = _inject(y, attack)
    ys = jax.lax.all_gather(y.reshape(B * E, C, d), "replica")  # (r,BE,C,d)
    pub = jnp.moveaxis(ys, 0, 1)                       # (BE, r, C, d)
    trusted, _support = kref.redundancy_vote_ref(pub)
    return trusted.reshape(B, E, C, d)


def _vote_digest(y, attack):
    """Digest vote + masked-psum recovery (beyond-paper)."""
    B, E, C, d = y.shape
    y = _inject(y, attack).reshape(B * E, C, d)
    # per-(group, expert) digest: projection onto a fixed pseudorandom
    # direction — Gaussian manipulation perturbs it w.p. 1
    v = jax.random.normal(jax.random.PRNGKey(0xB30E), (C, d), jnp.float32)
    dig = jnp.tensordot(y.astype(jnp.float32), v, axes=2)  # (BE,)
    digs = jax.lax.all_gather(dig, "replica")          # (r, BE) — tiny
    agree = (jnp.abs(digs[:, None, :] - digs[None, :, :]) <= 0.0)
    support = agree.sum(axis=1)                        # (r, BE)
    rid = jax.lax.axis_index("replica")
    majority = support.max(axis=0)                     # (BE,)
    # elect the lowest-indexed replica of the max-support coalition
    # (breaks r=2 ties deterministically, like the faithful argmax)
    winner = jnp.argmax(support == majority[None, :], axis=0)  # (BE,)
    ok = (jnp.abs(digs[rid] -
                  jnp.take_along_axis(digs, winner[None, :], axis=0)[0])
          <= 0.0).astype(y.dtype)
    n_ok = jax.lax.psum(ok, "replica")
    total = jax.lax.psum(y * ok[:, None, None], "replica")
    out = total / jnp.maximum(n_ok, 1.0)[:, None, None]
    return out.astype(y.dtype).reshape(B, E, C, d)


def make_trust(mesh: Optional[Mesh], rcfg, expert_sharded: bool,
               attack: Optional[LMAttack] = None):
    """Build the ``trust`` hook for repro.models.moe.moe_mlp.

    The hook receives the routed-expert output buffer (B, E, C, d);
    ``expert_sharded`` says whether its expert axis is sharded over
    "model" (llama4: 128 % 16 == 0) or replicated (qwen2-moe)."""
    if mesh is None or rcfg.mode == "off":
        return None
    if "replica" not in mesh.axis_names:
        raise ValueError("trusted mode needs a 'replica' mesh axis")
    batch = tuple(a for a in ("pod", "data") if a in mesh.axis_names) or None
    spec = P(batch, "model" if expert_sharded else None, None, None)
    body = _vote_faithful if rcfg.mode == "faithful" else _vote_digest
    return jax.shard_map(functools.partial(body, attack=attack), mesh=mesh,
                         in_specs=(spec,), out_specs=spec, check_vma=False)


def make_trusted_mesh(r: int, *, data: int = 16, model: int = 16,
                      multi_pod: bool = False):
    """(data/r, replica=r, model) mesh — same chip count as production."""
    if data % r:
        raise ValueError(f"redundancy r={r} must divide data={data}")
    if multi_pod:
        return make_mesh((2, data // r, r, model),
                         ("pod", "data", "replica", "model"))
    return make_mesh((data // r, r, model), ("data", "replica", "model"))
