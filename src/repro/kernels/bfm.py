"""Tiled brute-force interval matching — the BFM/GBM hot loop as a
Pallas TPU kernel.

Paper Algorithm 2 is a branchy scalar double loop; the TPU form is a
2-D grid over (S-tiles × U-tiles).  Each program holds a (TS, d) block of
subscription bounds and a (d, TU) block of update bounds (the wrapper
transposes U, so both compares broadcast without a relayout), forms
the (TS, TU) overlap predicate with broadcast compares on the VPU (one
pair of compares per dimension, AND-reduced), and emits either the
per-tile intersection count (BFM counting mode — what the paper's
evaluation measures) or the boolean tile of the match mask (the DDM
block-mask planner used by block-sparse attention).

VMEM budget per program: TS·d + TU·d floats + TS·TU predicate ≈
2·(256·d)·4B + 256·256 ≈ 70 KiB for d≤4 — comfortably inside the ~16 MiB
VMEM of a v5e core, leaving room for double buffering.  TS=TU=256 keeps
the compare block a multiple of the (8, 128) VPU tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEF_TS = 256
DEF_TU = 256


def _overlap(s_lo_ref, s_hi_ref, u_lo_ref, u_hi_ref):
    """(TS, TU) overlap predicate: S bounds arrive as (TS, d) columns,
    U bounds as (d, TU) rows, so each dimension's compare is a plain
    column-by-row broadcast with no relayout."""
    ok = None
    for k in range(s_lo_ref.shape[-1]):
        slo = s_lo_ref[:, k:k + 1]
        shi = s_hi_ref[:, k:k + 1]
        ulo = u_lo_ref[k:k + 1, :]
        uhi = u_hi_ref[k:k + 1, :]
        dim_ok = (slo < uhi) & (ulo < shi)
        ok = dim_ok if ok is None else (ok & dim_ok)
    return ok


def _count_kernel(s_lo_ref, s_hi_ref, u_lo_ref, u_hi_ref, out_ref):
    ok = _overlap(s_lo_ref, s_hi_ref, u_lo_ref, u_hi_ref)
    # one (8, 128) int32 tile per program (Mosaic's smallest legal
    # output block), the count broadcast over it
    out_ref[...] = jnp.full(out_ref.shape, jnp.sum(ok.astype(jnp.int32)),
                            jnp.int32)


def _mask_kernel(s_lo_ref, s_hi_ref, u_lo_ref, u_hi_ref, out_ref):
    out_ref[...] = _overlap(s_lo_ref, s_hi_ref, u_lo_ref, u_hi_ref)


def _in_specs(ts: int, tu: int, d: int):
    s_spec = pl.BlockSpec((ts, d), lambda i, j: (i, 0))
    u_spec = pl.BlockSpec((d, tu), lambda i, j: (0, j))
    return [s_spec, s_spec, u_spec, u_spec]


@functools.partial(jax.jit,
                   static_argnames=("ts", "tu", "interpret"))
def bfm_tile_counts(s_lo, s_hi, u_lo, u_hi, *, ts: int = DEF_TS,
                    tu: int = DEF_TU, interpret: bool = False):
    """Per-tile overlap counts int32 (n/ts, m/tu). n%ts == m%tu == 0."""
    n, d = s_lo.shape
    m = u_lo.shape[0]
    assert n % ts == 0 and m % tu == 0, (n, ts, m, tu)
    gi, gj = n // ts, m // tu
    tiles = pl.pallas_call(
        _count_kernel,
        grid=(gi, gj),
        in_specs=_in_specs(ts, tu, d),
        out_specs=pl.BlockSpec((1, 8, 128), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((gi, 8, gj * 128), jnp.int32),
        interpret=interpret,
    )(s_lo, s_hi, u_lo.T, u_hi.T)
    return tiles[:, 0, ::128]


@functools.partial(jax.jit,
                   static_argnames=("ts", "tu", "interpret"))
def bfm_mask(s_lo, s_hi, u_lo, u_hi, *, ts: int = DEF_TS,
             tu: int = DEF_TU, interpret: bool = False):
    """Full (n, m) bool overlap mask, tiled. n%ts == m%tu == 0."""
    n, d = s_lo.shape
    m = u_lo.shape[0]
    assert n % ts == 0 and m % tu == 0, (n, ts, m, tu)
    grid = (n // ts, m // tu)
    return pl.pallas_call(
        _mask_kernel,
        grid=grid,
        in_specs=_in_specs(ts, tu, d),
        out_specs=pl.BlockSpec((ts, tu), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.bool_),
        interpret=interpret,
    )(s_lo, s_hi, u_lo.T, u_hi.T)
