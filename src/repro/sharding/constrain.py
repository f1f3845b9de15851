"""In-model sharding hints that are safe without a mesh.

``constrain(x, spec)`` applies `with_sharding_constraint` where dims are
UNCONSTRAINED unless marked. Any named axis absent from the ambient abstract
mesh, or that does not divide the dim, is dropped — so model code stays
mesh-agnostic (tests run with no mesh at all; phi4's 24 heads on a model=16
axis simply fall back to unconstrained).

Markers:
  None  -> UNCONSTRAINED (leave to propagation)
  "r"   -> force replicated
  "dp"  -> the data-parallel axes, default ("pod","data"); the co-learning
           participant step narrows this to ("data",) via `batch_axes`
           because its vmap already consumes the pod axis
  name / tuple of names -> those mesh axes
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import PartitionSpec as P

U = P.UNCONSTRAINED
_CTX = threading.local()


def _dp_axes():
    return getattr(_CTX, "dp", ("pod", "data"))


@contextlib.contextmanager
def batch_axes(axes):
    """Override the axes 'dp' resolves to (trace-time context)."""
    prev = _dp_axes()
    _CTX.dp = tuple(axes)
    try:
        yield
    finally:
        _CTX.dp = prev


def _resolve(dim, ax, mesh, axes):
    if ax == "r":
        return None, True
    if ax == "dp":
        ax = _dp_axes()
    if isinstance(ax, str):
        ax = (ax,)
    present = tuple(a for a in ax if a in axes)
    # drop leading axes until the product divides the dim
    while present:
        prod = 1
        for a in present:
            prod *= mesh.shape[a]
        if dim % prod == 0 and prod > 1:
            return (present if len(present) > 1 else present[0]), True
        present = present[1:]
    return U, False


def constrain(x, spec):
    mesh = jax.sharding.get_abstract_mesh()
    axes = set(mesh.axis_names)
    if not axes:
        return x
    out = []
    changed = False
    for dim, ax in zip(x.shape, spec):
        if ax is None:
            out.append(U)
            continue
        r, ch = _resolve(dim, ax, mesh, axes)
        out.append(r)
        changed |= ch
    if not changed:
        return x
    return jax.lax.with_sharding_constraint(x, P(*out))
