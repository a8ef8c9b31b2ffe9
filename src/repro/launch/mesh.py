"""Production meshes.

Functions (never module-level constants) so importing this module never
touches jax device state.  The dry-run entrypoint sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax
import; everything else sees the real device count.

Every factory except ``make_production_mesh`` (a fixed physical pod
geometry) derives its axis widths from the *actual* device count:
excess devices fold into the data axis, and impossible splits raise
with the arithmetic spelled out instead of handing GSPMD a mesh the
model cannot shard over.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with every axis ``Auto``-typed — the one mesh
    constructor of the repo.  jax's own default is ``Explicit`` axes,
    under which ``with_sharding_constraint`` rejects the specs the
    sharding rules emit and indexing a sharded array raises; every step
    function here is written for compiler-propagated (``Auto``)
    shardings."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def _model_width(n: int, divides: Optional[int] = None,
                 cap: Optional[int] = None) -> int:
    """Largest divisor of ``n`` that also divides ``divides`` (when
    given) and is <= ``cap`` (when given).  Always >= 1 — leftover
    devices fold into the data axis instead of failing."""
    for m in range(min(n, cap or n), 0, -1):
        if n % m == 0 and (divides is None or divides % m == 0):
            return m
    return 1


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the pod axis folds
    into the batch sharding (dp = pod x data)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_trusted_mesh(r: int, *, multi_pod: bool = False):
    """B-MoE redundancy mesh: the data axis splits into (data/r groups,
    r replicas).  Axis widths derive from the actual device count —
    the replica axis is reserved first, the model axis takes the widest
    power up to 16 that fits, and every leftover device folds into the
    data axis (a 512-chip single-pod run uses all 512 chips as
    (16, r, 16)-ish instead of silently assuming a 16-wide data axis)."""
    n = len(jax.devices())
    pods = 2 if multi_pod else 1
    if n % pods:
        raise ValueError(f"multi_pod needs an even device count, got {n}")
    per_pod = n // pods
    if r < 1 or per_pod % r:
        raise ValueError(
            f"redundancy r={r} must divide the per-pod device count "
            f"{per_pod} ({n} devices / {pods} pod(s))")
    rest = per_pod // r
    model = _model_width(rest, cap=16)
    data = rest // model
    if multi_pod:
        return make_mesh((2, data, r, model),
                         ("pod", "data", "replica", "model"))
    return make_mesh((data, r, model), ("data", "replica", "model"))


def make_host_mesh(num_experts: Optional[int] = None):
    """Whatever fits the current host (CPU tests): 1 device -> (1, 1).

    With ``num_experts`` the model axis is the largest device-count
    divisor that also divides the expert count — what ``moe_mlp_ep``
    needs (``E % msize == 0``) — and excess devices fold into the data
    axis, instead of the old unconditional ``(1, n)`` that made expert
    parallelism raise whenever ``num_experts % n != 0``."""
    n = len(jax.devices())
    model = _model_width(n, divides=num_experts)
    return make_mesh((n // model, model), ("data", "model"))


def make_edge_mesh(num_experts: int, *, shards: Optional[int] = None):
    """B-MoE edge mesh: ``model`` is the edge-shard axis — each
    simulated edge owns a contiguous ``num_experts/shards`` expert
    slice, dispatch crosses shards via all_to_all, and commitments/
    audits are shard-local (see ``repro.core.bmoe``).  Leftover devices
    fold into a replicated ``data`` axis.  ``shards=None`` picks the
    widest edge axis the device and expert counts allow."""
    n = len(jax.devices())
    if shards is None:
        shards = _model_width(n, divides=num_experts)
    if shards < 1 or n % shards:
        raise ValueError(
            f"mesh_shards={shards} must divide the device count ({n})")
    if num_experts % shards:
        raise ValueError(
            f"num_experts ({num_experts}) % mesh_shards ({shards}) != 0 — "
            f"each edge shard must own a whole expert slice; pick shards "
            f"from the divisors of {num_experts}")
    return make_mesh((n // shards, shards), ("data", "model"))
