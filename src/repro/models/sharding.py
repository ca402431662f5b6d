"""Activation sharding constraints (logical-axis indirection).

Models call ``constrain(x, "dp", None, "tp")`` with *logical* axis names;
the mapping to mesh axes is resolved against the ambient mesh installed
by ``jax.set_mesh`` in the launcher:

    "dp" → ("pod", "data")  (whichever exist)   — batch / fsdp-gather dim
    "tp" → "model"                               — heads / ffn / vocab
    "sp" → "data"                                — sequence (long-context)

Outside any mesh (unit tests, single-device runs) this is a no-op, so
model code never depends on launch topology.  Dims whose size doesn't
divide the axis product are dropped (same rule as launch.partition.sanitize).
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def _ambient_mesh():
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return None
    return mesh


def _resolve(name, axis_names):
    if name is None:
        return None
    if name == "dp":
        axes = tuple(a for a in ("pod", "data") if a in axis_names)
        return axes if axes else None
    if name == "tp":
        return "model" if "model" in axis_names else None
    if name == "sp":
        return "data" if "data" in axis_names else None
    if name == "tpseq":   # Megatron-style sequence parallelism: the
        # residual stream's seq dim shards over the tensor axis between
        # layers; TP regions gather/scatter at entry/exit.
        return "model" if "model" in axis_names else None
    return name if name in axis_names else None


def constrain(x, *logical):
    """Apply with_sharding_constraint under the ambient mesh (or no-op).

    ``REPRO_DISABLE_CONSTRAINTS`` env var (comma list of logical names,
    or "all") disables selected constraints — used by §Perf ablations.
    """
    import os
    disabled = os.environ.get("REPRO_DISABLE_CONSTRAINTS", "")
    if disabled:
        names = set(disabled.split(","))
        if "all" in names or any(n in names for n in logical if n):
            return x
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    dims = []
    for dim_size, name in zip(x.shape, logical):
        ax = _resolve(name, mesh.axis_names)
        if ax is None:
            dims.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        total = 1
        for a in axes:
            total *= sizes[a]
        dims.append(ax if dim_size % total == 0 else None)
    dims += [None] * (x.ndim - len(dims))
    return jax.lax.with_sharding_constraint(x, P(*dims))
