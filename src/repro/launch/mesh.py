"""Production mesh construction.

Kept as FUNCTIONS so importing this module never touches jax device state
(the dry-run must set XLA_FLAGS before first jax init; smoke tests must see
the single real CPU device).

Axes:
  single-pod : (data=16, model=16)            = 256 chips  (one v5e pod)
  multi-pod  : (pod=2, data=16, model=16)     = 512 chips

``data``  -- batch (DP) + parameter/optimizer sharding (FSDP/ZeRO-3); the
             paper's n redundancy workers are contiguous slices of it.
``model`` -- tensor parallel: attention heads / FFN hidden / experts / vocab.
``pod``   -- pure DP across pods (gradient all-reduce over DCN).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

SINGLE_POD = (16, 16)
MULTI_POD = (2, 16, 16)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """(data, rest) mesh over the real local devices.

    Raises when ``data`` asks for more devices than exist: a mesh that
    quietly shrank would run a different data-parallel layout than the
    one requested."""
    n = len(jax.devices())
    if not 1 <= data <= n:
        raise ValueError(f"make_host_mesh(data={data}) needs 1..{n} devices "
                         f"(have {n})")
    return _auto_mesh((data, n // data), ("data", "model"))


def _auto_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with Auto axes: the model code places activations
    with ``with_sharding_constraint``, which only accepts Auto axes (JAX
    0.9 makes Explicit the default)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_spec(mesh: Mesh, global_batch: int) -> P:
    """Largest (pod, data) prefix that divides the batch; P() if none."""
    axes = batch_axes(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    # try full (pod, data), then data alone
    for cand in (axes, axes[-1:],):
        total = math.prod(sizes[a] for a in cand)
        if global_batch % total == 0:
            return P(cand if len(cand) > 1 else cand[0])
    return P(None)


def named(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda s: isinstance(s, P))
