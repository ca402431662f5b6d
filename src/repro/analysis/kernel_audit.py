"""Pass 2 — static audit of every ``pallas_call`` in ``kernels/``.

Works entirely from the ``KernelCapture`` records (grid, BlockSpecs,
scratch shapes, operand avals) taken while *tracing* the kernel wrappers
— the kernels never execute, so the audit covers the paper's N ≥ 1e6
regime in milliseconds:

``K_VMEM_BUDGET``
    True per-program VMEM footprint — every VMEM-resident input block
    (a ``BlockSpec`` without an explicit non-VMEM memory space; a spec
    with no ``block_shape`` pins the whole operand), every output
    block, and every VMEM scratch allocation — summed against the core
    budget.  This is the *real* number the BlockSpecs imply, not the
    route policy's model; the two are reconciled separately by
    ``audit_emit_route_parity``.

``K_OOB_INDEX_MAP``
    Every index map evaluated over the (possibly sampled) grid: each
    returned block index must keep ``(idx + 1) * block_dim`` inside the
    bound array for every dimension.

``K_WRITE_HAZARD``
    Two distinct grid steps mapping an output to the same block index —
    on TPU the grid is sequential so this is a silent last-write-wins,
    on other targets a data race.

``K_ROUTE_DRIFT``
    ``kernels.ops.emit_route_bytes`` (the byte model the route policy
    decides on) re-derived from the captured BlockSpecs/scratch of the
    *real* emit kernels; the model must equal the derived bytes for
    every route.
"""
from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.emit import VMEM_LIMIT_BYTES
from .capture import KernelCapture, trace_kernel
from .report import Report

# the scoped VMEM limit the emit kernels request from Mosaic
VMEM_BUDGET = VMEM_LIMIT_BYTES
GRID_SAMPLE_CAP = 4096          # full enumeration below this many steps


# ---------------------------------------------------------------------------
# BlockSpec / scratch byte accounting
# ---------------------------------------------------------------------------

def _memory_space_name(obj) -> str:
    ms = getattr(obj, "memory_space", None)
    return "" if ms is None else str(ms).lower()


def _spec_in_vmem(spec) -> bool:
    name = _memory_space_name(spec)
    if not name:                 # default memory space is VMEM
        return True
    return "vmem" in name


def _nbytes(shape, dtype) -> int:
    return int(np.prod([int(d) for d in shape], initial=1)
               * np.dtype(dtype).itemsize)


def block_bytes(spec, aval) -> int:
    """Bytes one grid step keeps live in VMEM for this operand."""
    if not _spec_in_vmem(spec):
        return 0
    bs = getattr(spec, "block_shape", None)
    shape = aval.shape if bs is None else tuple(
        int(b) for b in bs)
    return _nbytes(shape, aval.dtype)


def scratch_bytes(ref) -> int:
    name = _memory_space_name(ref)
    if "vmem" not in name:       # SMEM / semaphores don't charge VMEM
        return 0
    return _nbytes(ref.shape, ref.dtype)


def vmem_footprint(cap: KernelCapture) -> int:
    """Static per-program VMEM bytes implied by the captured specs."""
    nsp = cap.num_scalar_prefetch
    blocked_ops = cap.operands[nsp:]
    total = 0
    for spec, aval in zip(cap.in_specs, blocked_ops):
        total += block_bytes(spec, aval)
    for spec, aval in zip(cap.out_specs, cap.out_shapes):
        total += block_bytes(spec, aval)
    for ref in cap.scratch_shapes:
        total += scratch_bytes(ref)
    return total


# ---------------------------------------------------------------------------
# grid enumeration (sampled beyond GRID_SAMPLE_CAP steps)
# ---------------------------------------------------------------------------

def grid_points(grid: tuple, cap: int = GRID_SAMPLE_CAP):
    """All grid coordinates, or a boundary-heavy strided sample.

    Sampling always includes every axis's endpoints (index-map bugs
    live at the edges), so an out-of-bounds final block is never
    missed; interior coverage is strided to keep the product under
    ``cap``.
    """
    dims = [int(g) for g in grid]
    if not dims:
        return [()]
    total = int(np.prod(dims))
    if total <= cap:
        return list(itertools.product(*[range(g) for g in dims]))
    per_axis = max(2, int(cap ** (1.0 / len(dims))))
    axes = []
    for g in dims:
        if g <= per_axis:
            axes.append(list(range(g)))
            continue
        step = max(1, (g - 1) // (per_axis - 1))
        picks = sorted({0, g - 1, *range(0, g, step)})
        axes.append(picks)
    return list(itertools.product(*axes))


def _eval_index_map(spec, coords, scalar_args):
    fn = getattr(spec, "index_map", None)
    if fn is None:
        return None
    idx = fn(*coords, *scalar_args)
    if not isinstance(idx, tuple):
        idx = (idx,)
    return tuple(int(v) for v in idx)


def _check_bounds(spec, aval, idx, *, where: str, coords, target: str,
                  report: Report) -> None:
    bs = getattr(spec, "block_shape", None)
    if bs is None or idx is None:
        return
    for k, (bi, bd, dim) in enumerate(zip(idx, bs, aval.shape)):
        if bi < 0 or (bi + 1) * int(bd) > int(dim):
            report.add(
                "kernel", "K_OOB_INDEX_MAP", target,
                f"{where} index map at grid{tuple(coords)} returns block "
                f"{idx}: axis {k} spans "
                f"[{bi * int(bd)}, {(bi + 1) * int(bd)}) outside the "
                f"array dim {int(dim)}")
            return


def audit_kernel_capture(cap: KernelCapture, *, report: Report,
                         budget: int = VMEM_BUDGET,
                         grid_cap: int = GRID_SAMPLE_CAP) -> None:
    """Footprint + bounds + hazard checks for one captured kernel."""
    target = cap.target

    used = vmem_footprint(cap)
    if used > budget:
        report.add(
            "kernel", "K_VMEM_BUDGET", target,
            f"static VMEM footprint {used} bytes "
            f"({used / (1 << 20):.1f} MiB) exceeds the "
            f"{budget >> 20} MiB core budget — grid {cap.grid}, "
            f"{len(cap.in_specs)} in / {len(cap.out_specs)} out specs")

    nsp = cap.num_scalar_prefetch
    # index maps may consult scalar-prefetch operands; hand them zeros
    # of the right shape (repo maps only use the grid coordinates).
    scalar_args = [np.zeros(a.shape, np.dtype(a.dtype))
                   for a in cap.operands[:nsp]]
    blocked_ops = cap.operands[nsp:]
    pts = grid_points(cap.grid, grid_cap)
    sampled = len(pts) < int(np.prod([int(g) for g in cap.grid],
                                     initial=1))

    seen_out: dict[tuple, tuple] = {}
    hazards = 0
    for coords in pts:
        for spec, aval in zip(cap.in_specs, blocked_ops):
            idx = _eval_index_map(spec, coords, scalar_args)
            _check_bounds(spec, aval, idx, where="input", coords=coords,
                          target=target, report=report)
        out_key = []
        for spec, aval in zip(cap.out_specs, cap.out_shapes):
            idx = _eval_index_map(spec, coords, scalar_args)
            _check_bounds(spec, aval, idx, where="output", coords=coords,
                          target=target, report=report)
            out_key.append(idx)
        key = tuple(out_key)
        if key in seen_out and hazards < 3:
            hazards += 1
            report.add(
                "kernel", "K_WRITE_HAZARD", target,
                f"grid steps {seen_out[key]} and {tuple(coords)} both "
                f"write output block(s) {key}: sequential "
                "last-write-wins on TPU, a data race elsewhere")
        seen_out.setdefault(key, tuple(coords))

    note = target + (" (sampled grid)" if sampled else "")
    report.note_audit("kernel", note)


# ---------------------------------------------------------------------------
# route-model parity for the two emit kernels
# ---------------------------------------------------------------------------

def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def derived_table_bytes(cap: KernelCapture) -> int:
    """Route-relevant VMEM bytes from the captured emit-kernel specs.

    Counts what the route *policy* models: VMEM-resident input blocks
    plus VMEM scratch.  Output blocks and the scalar-prefetch operand
    are excluded (every route pays the same output block); ANY-space
    operands stay in HBM and charge only the VMEM scratch they are
    copied into; SMEM windows do not charge VMEM.
    """
    nsp = cap.num_scalar_prefetch
    total = 0
    for spec, aval in zip(cap.in_specs, cap.operands[nsp:]):
        total += block_bytes(spec, aval)
    for ref in cap.scratch_shapes:
        total += scratch_bytes(ref)
    return total


def audit_emit_route_parity(report: Report, *, n: int = 4000,
                            m: int = 3000, max_pairs: int = 8192,
                            block: int | None = None) -> None:
    """Assert ``emit_route_bytes`` matches the real kernels' specs.

    All three emit kernels are traced abstractly at ``(n, m,
    max_pairs)``; the policy's modeled bytes must equal the VMEM bytes
    their captured BlockSpecs and scratch imply.  Drift in either
    direction — a kernel change not reflected in the model, or a model
    change not reflected in the kernels — is ``K_ROUTE_DRIFT``.
    """
    from ..kernels import emit as emit_kernel
    from ..kernels import ops

    block = emit_kernel.DEF_BLOCK if block is None else block
    model = ops.emit_route_bytes(n, m, block=block)
    e = n + m
    tables = (_i32(e + 1), _i32(e), _i32(e), _i32(n), _i32(m))
    win = emit_kernel.stream_window(block)
    csr_args = (_i32(8, emit_kernel.table_len(e, win)),
                _i32(1, emit_kernel.perm_len(n)),
                _i32(1, emit_kernel.perm_len(m)), _i32())
    routes = (
        ("resident", functools.partial(
            emit_kernel.twopass_emit, n=n, m=m, max_pairs=max_pairs,
            block=block), tables),
        ("streaming", functools.partial(
            emit_kernel.twopass_emit_streaming, n=n, m=m,
            max_pairs=max_pairs, block=block), tables),
        ("csr", functools.partial(
            emit_kernel.csr_decode_window, n=n, m=m, nslots=max_pairs,
            block=block), csr_args),
    )
    for route, wrapped, args in routes:
        target = f"emit_route_parity:{route}"
        caps = trace_kernel(wrapped, *args)
        if len(caps) != 1:
            report.add(
                "kernel", "K_ROUTE_DRIFT", target,
                f"expected exactly one pallas_call while tracing the "
                f"{route} emit kernel, captured {len(caps)}")
            continue
        derived = derived_table_bytes(caps[0])
        if derived != model[route]:
            report.add(
                "kernel", "K_ROUTE_DRIFT", target,
                f"emit_route_bytes models {model[route]} bytes for the "
                f"{route} route but the captured BlockSpecs/scratch "
                f"imply {derived} at (n={n}, m={m}, "
                f"max_pairs={max_pairs}, block={block}) — the policy "
                "and the kernels have drifted apart")
        report.note_audit("kernel", target)
