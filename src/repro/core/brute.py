"""Brute-Force Matching (BFM) — paper Algorithm 2, vectorized.

The paper's doubly-nested ``Intersect-1D`` loop becomes a tiled all-pairs
broadcast compare: embarrassingly parallel on OpenMP threads there, on VPU
lanes here.  ``U`` is processed in tiles so the (n × tile) overlap mask is
the only O(n·m) intermediate and its size is bounded.

The Pallas TPU kernel for the same computation lives in
``repro.kernels.bfm`` — this module is the pure-jnp reference and the small-
problem fast path.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .hostread import host_sum
from .regions import Regions

Array = jax.Array


def _mask_block(s_lo, s_hi, u_lo, u_hi) -> Array:
    """(n, m) overlap mask for d-dim regions. Inputs (n,d)/(m,d)."""
    # (n, 1, d) vs (1, m, d) -> (n, m, d) -> all over d
    ok = jnp.logical_and(s_lo[:, None, :] < u_hi[None, :, :],
                         u_lo[None, :, :] < s_hi[:, None, :])
    return jnp.all(ok, axis=-1)


@jax.jit
def bfm_mask(S: Regions, U: Regions) -> Array:
    """Full (n, m) boolean overlap mask (small problems / oracle)."""
    return _mask_block(S.lo, S.hi, U.lo, U.hi)


@partial(jax.jit, static_argnames=("tile",))
def bfm_count_per_sub(S: Regions, U: Regions, tile: int = 4096) -> Array:
    """Per-subscription overlap counts K_s, computed in U-tiles.

    Returns int32 (n,).  Total K = sum (done by the caller in int64 —
    XLA int32 would overflow at paper scale).
    """
    m = U.n
    pad = (-m) % tile
    u_lo = jnp.pad(U.lo, ((0, pad), (0, 0)), constant_values=jnp.inf)
    u_hi = jnp.pad(U.hi, ((0, pad), (0, 0)), constant_values=-jnp.inf)
    u_lo = u_lo.reshape(-1, tile, U.d)
    u_hi = u_hi.reshape(-1, tile, U.d)

    def body(carry, uw):
        ulo, uhi = uw
        mask = _mask_block(S.lo, S.hi, ulo, uhi)
        return carry + jnp.sum(mask, axis=1, dtype=jnp.int32), None

    init = jnp.zeros((S.n,), jnp.int32)
    counts, _ = jax.lax.scan(body, init, (u_lo, u_hi))
    return counts


def bfm_count(S: Regions, U: Regions, tile: int = 4096) -> int:
    """Total number of overlapping (s, u) pairs (python int, exact)."""
    return host_sum(bfm_count_per_sub(S, U, tile=tile))


@partial(jax.jit, static_argnames=("max_pairs",))
def bfm_pairs(S: Regions, U: Regions, max_pairs: int):
    """Enumerate overlapping pairs into a static-capacity buffer.

    Returns ``(pairs, count)`` where ``pairs`` is int32 (max_pairs, 2)
    filled with (s_idx, u_idx) and padded with -1; ``count`` is the true
    number of overlaps (may exceed max_pairs — caller checks overflow).
    Report-exactly-once comes for free: each (s, u) cell of the mask is a
    distinct pair (paper §2 'reporting' requirement).
    """
    mask = _mask_block(S.lo, S.hi, U.lo, U.hi)
    count = jnp.sum(mask, dtype=jnp.int32)
    flat_idx = jnp.nonzero(mask.ravel(), size=max_pairs, fill_value=-1)[0]
    s_idx = jnp.where(flat_idx >= 0, flat_idx // U.n, -1).astype(jnp.int32)
    u_idx = jnp.where(flat_idx >= 0, flat_idx % U.n, -1).astype(jnp.int32)
    return jnp.stack([s_idx, u_idx], axis=1), count
