"""SBM sweep phase as a Pallas TPU kernel — paper Alg. 6/7 at the
VMEM-block level.

After the endpoint sort, the sweep is two prefix sums over ±1 deltas plus
a pointwise report expression (see ``core.sbm``).  On TPU this maps to
the paper's own two-level scan, one level down the memory hierarchy: the
grid walks the endpoint stream in (1, C) VMEM blocks **sequentially**
(TPU grid order is sequential, which is what makes a carried scan legal);
each program computes the local inclusive scans of the update/
subscription active-deltas — Alg. 7 step ①, as a log-step shifted add
(``pltpu.roll``; Mosaic has no cumsum lowering) — adds the carry from all
previous blocks — step ② — and emits the per-endpoint report counts of
the seeded sweep — step ③.  The two carries (active update/sub counts)
live in SMEM scratch across grid steps.

Inputs are the lex-sorted endpoint flags, already padded to a multiple of
the block size with zero rows (zero flags contribute nothing).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lane_scan(x):
    """Inclusive prefix sum along the lanes of a (1, C) block: lg C
    rotate-and-add steps, each masked so nothing wraps around."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    shift = 1
    while shift < x.shape[1]:
        x = x + jnp.where(lane >= shift, pltpu.roll(x, shift, 1), 0)
        shift *= 2
    return x


def _sweep_kernel(is_lo_ref, is_upd_ref, out_ref, carry_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        carry_ref[0] = 0  # active updates before this block
        carry_ref[1] = 0  # active subscriptions before this block

    is_lo = is_lo_ref[...]                   # (1, C) int32
    is_upd = is_upd_ref[...]
    is_hi = 1 - is_lo
    is_sub = 1 - is_upd

    d_upd = is_upd * (is_lo - is_hi)
    d_sub = is_sub * (is_lo - is_hi)
    upd_local = _lane_scan(d_upd)            # step ① local scan
    sub_local = _lane_scan(d_sub)
    upd_active = upd_local + carry_ref[0]    # step ② seeded
    sub_active = sub_local + carry_ref[1]
    out_ref[...] = is_hi * (is_sub * upd_active + is_upd * sub_active)

    carry_ref[0] = carry_ref[0] + jnp.sum(d_upd)
    carry_ref[1] = carry_ref[1] + jnp.sum(d_sub)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def sbm_sweep(is_lo, is_upd, *, block: int = 2048,
              interpret: bool = False):
    """Per-endpoint report counts; 1-D int32 inputs, len % block == 0.

    Note: padded tail rows must have ``is_lo = is_upd = 0``; such rows are
    treated as (hi, sub) endpoints and contribute ``upd_active`` — so use
    the canonical padding (is_lo=1, is_upd=0: a sub-lo sentinel) from
    ``ops.sbm_sweep_contribs`` which contributes exactly zero.
    """
    tot = is_lo.shape[0]
    assert tot % block == 0, (tot, block)
    grid = (tot // block,)
    out = pl.pallas_call(
        _sweep_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, tot), jnp.int32),
        scratch_shapes=[pltpu.SMEM((2,), jnp.int32)],
        interpret=interpret,
    )(is_lo.reshape(1, -1), is_upd.reshape(1, -1))
    return out.reshape(-1)
