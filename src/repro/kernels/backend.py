"""Central kernel-backend selection.

Every Pallas kernel in this package takes an ``interpret`` flag that
defaults to ``None`` and resolves here, so there is exactly ONE place
that decides how a kernel executes:

- ``REPRO_KERNEL_BACKEND`` env var, when set, wins ("ref" | "pallas" |
  "interpret");
- otherwise "pallas" (compiled) on TPU, "ref" elsewhere.

A failure to enumerate devices propagates: there is no silent fallback
to the reference backend.  ``require_compiled`` is the guard a chip run
calls first — it refuses a non-TPU device and a TPU forced onto the
reference or interpreted kernels.

``ops`` keeps its per-call ``backend=`` override on top of this default.
"""
from __future__ import annotations

import os

BACKENDS = ("ref", "pallas", "interpret")


def default_backend() -> str:
    env = os.environ.get("REPRO_KERNEL_BACKEND")
    if env:
        if env not in BACKENDS:
            raise ValueError(f"REPRO_KERNEL_BACKEND={env!r}; expected one "
                             f"of {BACKENDS}")
        return env
    import jax
    return "pallas" if jax.devices()[0].platform == "tpu" else "ref"


def require_compiled() -> str:
    """Raise unless kernels run compiled on a TPU; returns the backend.

    A chip measurement on interpreted or reference kernels would measure
    the wrong program, so ``REPRO_KERNEL_BACKEND=ref|interpret`` is
    refused on a TPU here rather than honoured."""
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise RuntimeError(f"no TPU: jax.devices()[0].platform is "
                           f"{platform!r}")
    backend = default_backend()
    if backend != "pallas":
        raise RuntimeError(
            f"REPRO_KERNEL_BACKEND={backend!r} on a TPU: the chip path runs "
            f"compiled Pallas kernels only")
    return backend


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve a kernel's ``interpret`` flag: an explicit value wins;
    ``None`` defers to ``default_backend()`` — compiled on a "pallas"
    backend, interpreted everywhere else (the CPU validation mode)."""
    if interpret is None:
        return default_backend() != "pallas"
    return bool(interpret)
