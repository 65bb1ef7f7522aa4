"""Activation-sharding constraint context.

Model code is mesh-agnostic; it calls ``constrain(x, ("batch", None, "model"))``
with logical axis tokens. When a mesh context is active (set by the launcher
around tracing), these resolve to ``jax.lax.with_sharding_constraint`` hints;
otherwise they are identity — tests and the laptop-scale runners never see a mesh.

Tokens: "batch" -> the batch mesh axes, "model" -> the tensor-parallel axis,
None -> unconstrained. Non-divisible dims silently drop the constraint.
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

_tls = threading.local()


@dataclass
class _Ctx:
    mesh: object
    batch_axes: Tuple[str, ...]
    model_axis: str


@contextlib.contextmanager
def activation_mesh(mesh, *, batch_axes: Sequence[str] = ("data",),
                    model_axis: str = "model"):
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = _Ctx(mesh, tuple(batch_axes), model_axis)
    try:
        yield
    finally:
        _tls.ctx = prev


def active() -> Optional[_Ctx]:
    return getattr(_tls, "ctx", None)


def _axis_size(mesh, ax) -> int:
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    if isinstance(ax, tuple):
        return int(np.prod([shape[a] for a in ax]))
    return shape[ax]


def constrain(x, spec: Sequence) -> jax.Array:
    ctx = active()
    if ctx is None:
        return x
    assert len(spec) == x.ndim, (spec, x.shape)
    resolved = []
    for dim, s in zip(x.shape, spec):
        if s == "batch":
            ax = ctx.batch_axes if len(ctx.batch_axes) > 1 else ctx.batch_axes[0]
        elif s == "model":
            ax = ctx.model_axis
        else:
            ax = s
        if ax is not None and (dim < _axis_size(ctx.mesh, ax) or dim % _axis_size(ctx.mesh, ax)):
            ax = None
        resolved.append(ax)
    if not jax.sharding.get_abstract_mesh().empty:
        # tracing under a mesh context (``core.spmd``'s Auto-axes view of
        # an explicit-sharding mesh): the spec binds to that mesh's axes
        return jax.lax.with_sharding_constraint(x, P(*resolved))
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, P(*resolved)))
