"""Seeded kernel-pass defects — real ``pallas_call`` wrappers with the
bugs the static audit exists to catch.  Each wrapper is traced
abstractly (never executed) and its captured specs audited.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.analysis import (audit_emit_route_parity, audit_kernel_capture,
                            trace_kernel)


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _oob_wrapper(x):
    # out_shape holds 2 blocks of 512 but the grid walks 4: the last
    # two grid steps write blocks [1024, 1536) and [1536, 2048) of a
    # (1, 1024) array
    return pl.pallas_call(
        _copy_kernel,
        grid=(4,),
        in_specs=[pl.BlockSpec((1, 512), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, 512), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, 1024), jnp.float32),
    )(x)


def _oob_index_map(report, target):
    caps = trace_kernel(_oob_wrapper,
                        jax.ShapeDtypeStruct((1, 2048), jnp.float32))
    for cap in caps:
        audit_kernel_capture(cap, report=report)


def _hazard_wrapper(x):
    # i // 2 maps grid steps (0, 1) and (2, 3) onto the same output
    # blocks: last-write-wins on TPU, a race anywhere else
    return pl.pallas_call(
        _copy_kernel,
        grid=(4,),
        in_specs=[pl.BlockSpec((1, 512), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, 512), lambda i: (0, i // 2)),
        out_shape=jax.ShapeDtypeStruct((1, 1024), jnp.float32),
    )(x)


def _write_hazard(report, target):
    caps = trace_kernel(_hazard_wrapper,
                        jax.ShapeDtypeStruct((1, 2048), jnp.float32))
    for cap in caps:
        audit_kernel_capture(cap, report=report)


def _vmem_wrapper(x):
    # the whole 64 MiB operand pinned VMEM-resident (plus the matching
    # output block): 128 MiB per program against the 32 MiB budget
    return pl.pallas_call(
        _copy_kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec(x.shape, lambda i: (0, 0))],
        out_specs=pl.BlockSpec(x.shape, lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
    )(x)


def _vmem_budget(report, target):
    caps = trace_kernel(_vmem_wrapper,
                        jax.ShapeDtypeStruct((4096, 4096), jnp.float32))
    for cap in caps:
        audit_kernel_capture(cap, report=report)


def _route_drift(report, target):
    # a byte model that drifted from the kernels: it forgets the packed
    # table the resident route copies into VMEM
    from repro.kernels import emit as emit_kernel
    from repro.kernels import ops

    real = ops.emit_route_bytes

    def drifted(n, m, *, block=emit_kernel.DEF_BLOCK):
        need = real(n, m, block=block)
        need["resident"] = need["streaming"]
        return need

    ops.emit_route_bytes = drifted
    try:
        audit_emit_route_parity(report, n=4000, m=3000, max_pairs=8192)
    finally:
        ops.emit_route_bytes = real


CASES = [
    dict(name="oob_output_index_map", pass_name="kernel",
         code="K_OOB_INDEX_MAP", audit=_oob_index_map),
    dict(name="write_write_hazard", pass_name="kernel",
         code="K_WRITE_HAZARD", audit=_write_hazard),
    dict(name="vmem_over_budget", pass_name="kernel",
         code="K_VMEM_BUDGET", audit=_vmem_budget),
    dict(name="emit_route_model_drift", pass_name="kernel",
         code="K_ROUTE_DRIFT", audit=_route_drift),
]
