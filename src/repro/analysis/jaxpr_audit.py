"""Pass 1 — jaxpr-level audit of every plan executable.

Each captured plan executable (see ``capture``) is re-traced abstractly
with ``jax.make_jaxpr`` — no device buffers, no execution — and its
jaxpr is walked (recursively through ``pjit``/scan/cond sub-jaxprs) for
statically-decidable hazards:

``J_INT32_INDEX``
    An int32 ``iota`` wider than ``INT32_MAX``.  Every XLA index-space
    builder the engine leans on — ``argsort``, ``arange``, ``nonzero``,
    the flat mask compaction — lowers to an int32 iota over the index
    domain, so an over-wide iota is exactly the "pair offsets overflow
    int32" defect of the paper's N ≥ 1e6 regime scaled further up.
    Detection is on the *scaled* trace: probe shapes are re-mapped to
    the matrix row's target sizes first (see ``scale_dims``).

``J_F64`` / ``J_WEAK_OUT`` / ``J_DTYPE_CONTRACT``
    Any float64 value inside a traced hot path (the whole repo contract
    is f32/int32); weak-typed outputs (silent promotion hazard for
    callers doing arithmetic on results); outputs whose dtype differs
    from the method's declared contract (pairs/ids are int32, counts
    int32/int64, masks bool).

``J_RANK_PROMOTION``
    The same trace repeated under ``jax.numpy_rank_promotion("raise")``;
    an error means some op relies on implicit rank promotion.

``J_CALLBACK``
    Host callbacks or device transfers (``pure_callback``,
    ``io_callback``, ``debug_callback``, ``device_put``, infeed/outfeed)
    anywhere in a jitted hot path.
"""
from __future__ import annotations

import functools

import jax
import numpy as np

from .capture import CapturedCall, abstractify
from .report import Report

INT32_MAX = np.iinfo(np.int32).max

# primitives that move data off the device or into Python at run time
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "python_callback", "host_callback_call", "outside_call",
    "device_put", "infeed", "outfeed", "copy_to_host_async",
})

_SUBJAXPR_SKIP_F64 = frozenset()   # (reserved: passes that allow f64)


def _subjaxprs_of(params):
    """Sub-jaxprs referenced from an eqn's params (pjit/scan/cond…)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for v in params.values():
        if isinstance(v, Jaxpr):
            yield v
        elif isinstance(v, ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, (tuple, list)):
            for w in v:
                if isinstance(w, Jaxpr):
                    yield w
                elif isinstance(w, ClosedJaxpr):
                    yield w.jaxpr


def walk_eqns(jaxpr):
    """Yield every eqn in ``jaxpr`` and (recursively) its sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs_of(eqn.params):
            yield from walk_eqns(sub)


def _benign_device_put(eqn) -> bool:
    """Constant placement, not a transfer.

    jnp constants inside jit lower to ``device_put`` eqns with no
    device target (``devices=[None]``, ``srcs=[None]``); an actual
    ``jax.device_put(x, device)`` in a traced path carries a concrete
    target and IS flagged.
    """
    if eqn.primitive.name != "device_put":
        return False
    devices = eqn.params.get("devices", [])
    srcs = eqn.params.get("srcs", [])
    return all(d is None for d in devices) and all(
        s is None for s in srcs)


def _avals(eqn):
    for v in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "dtype"):
            yield aval


def audit_closed_jaxpr(closed, *, target: str, report: Report,
                       out_dtypes: tuple | None = None) -> None:
    """Walk one traced jaxpr for the static hazard classes above."""
    jaxpr = closed.jaxpr

    for eqn in walk_eqns(jaxpr):
        prim = eqn.primitive.name
        if prim == "iota":
            dt = np.dtype(eqn.params.get("dtype", np.int32))
            shape = eqn.params.get("shape", ())
            dim = eqn.params.get("dimension", 0)
            if dt == np.int32 and shape and shape[dim] > INT32_MAX:
                report.add(
                    "jaxpr", "J_INT32_INDEX", target,
                    f"int32 iota over {shape[dim]} elements "
                    f"(> INT32_MAX = {INT32_MAX}): index computations on "
                    "this axis alias silently; widen to int64 or route "
                    "through the two-pass emit path")
        if prim in CALLBACK_PRIMS and not _benign_device_put(eqn):
            report.add(
                "jaxpr", "J_CALLBACK", target,
                f"host callback / device transfer primitive '{prim}' "
                "inside a jitted hot path — every call pays a host "
                "round-trip and blocks async dispatch")
        for aval in _avals(eqn):
            if aval.dtype == np.float64:
                report.add(
                    "jaxpr", "J_F64", target,
                    f"float64 value of shape {tuple(aval.shape)} in "
                    f"primitive '{prim}': the repo contract is "
                    "f32/int32 — check for a Python-float promotion")
                break  # one finding per eqn is enough

    for k, aval in enumerate(closed.out_avals):
        if getattr(aval, "weak_type", False):
            report.add(
                "jaxpr", "J_WEAK_OUT", target,
                f"output {k} is weak-typed {aval.dtype}: arithmetic on "
                "it can silently promote in callers; anchor the dtype "
                "with an explicit astype/asarray")
        if out_dtypes is not None and k < len(out_dtypes) \
                and out_dtypes[k] is not None \
                and np.dtype(aval.dtype) != np.dtype(out_dtypes[k]):
            report.add(
                "jaxpr", "J_DTYPE_CONTRACT", target,
                f"output {k} has dtype {np.dtype(aval.dtype).name} but "
                f"the declared contract is "
                f"{np.dtype(out_dtypes[k]).name}")


def _trace_checked(fn, args, kwargs, *, target: str, report: Report):
    """``make_jaxpr`` that converts trace-time int overflow to a finding.

    Once a dimension product crosses INT32_MAX, some index constants no
    longer *parse* as int32 — jit raises ``OverflowError`` before a
    jaxpr even exists.  That is the int32-width defect manifesting at
    trace time, so it is reported as ``J_INT32_INDEX`` rather than
    crashing the audit.
    """
    try:
        return jax.make_jaxpr(fn)(*args, **kwargs)
    except OverflowError as e:
        report.add(
            "jaxpr", "J_INT32_INDEX", target,
            "trace-time integer overflow while staging the jitted "
            f"computation ({str(e).splitlines()[0][:160]}) — an index "
            "constant at this scale no longer fits int32")
        return None


# ---------------------------------------------------------------------------
# probe → target shape scaling
# ---------------------------------------------------------------------------

def dim_expressions(n: int, m: int, cap: int) -> dict[str, "DimExpr"]:
    """Candidate symbolic meanings of a probe-trace dimension size."""
    return {
        "n": lambda s: s["n"],
        "m": lambda s: s["m"],
        "n+m": lambda s: s["n"] + s["m"],
        "n+m+1": lambda s: s["n"] + s["m"] + 1,
        "2n": lambda s: 2 * s["n"],
        "2m": lambda s: 2 * s["m"],
        "2(n+m)": lambda s: 2 * (s["n"] + s["m"]),
        "n*m": lambda s: s["n"] * s["m"],
        "cap": lambda s: s["cap"],
        "2cap": lambda s: 2 * s["cap"],
    }


def scale_dims(probe: dict[str, int], target: dict[str, int]):
    """``dim_map`` rewriting probe-trace dims to the target scale.

    Probe sizes are distinct primes, so every derived dimension of a
    captured argument (n, m, n+m, n+m+1, caps, products …) has exactly
    one candidate meaning; unmatched dims (small constants like 1, 2, d)
    pass through unchanged.  Returns ``(dim_map, unresolved)`` where
    ``unresolved`` collects dims > the largest probe size that matched
    nothing — a trace with unresolved large dims is audited at probe
    scale instead of silently mis-scaled.
    """
    exprs = dim_expressions(**probe)
    table: dict[int, int] = {}
    ambiguous: set[int] = set()
    for name, fn in exprs.items():
        pv, tv = fn(probe), fn(target)
        if pv in table and table[pv] != tv:
            ambiguous.add(pv)
        table[pv] = tv
    floor = max(probe.values())
    unresolved: set[int] = set()

    def dim_map(d: int) -> int:
        if d in ambiguous:
            unresolved.add(d)
            return d
        if d in table:
            return table[d]
        if d > floor:
            unresolved.add(d)
        return d

    return dim_map, unresolved


def audit_captured_call(call: CapturedCall, *, report: Report,
                        probe: dict[str, int] | None = None,
                        target_scale: dict[str, int] | None = None,
                        out_dtypes: tuple | None = None,
                        check_rank: bool = True) -> None:
    """Re-trace one captured executable abstractly and audit its jaxpr.

    With ``probe``/``target_scale`` the captured argument shapes are
    rewritten to the target problem size first, so int32-width findings
    reflect the matrix row's scale, not the tiny probe.
    """
    static_kw, traced_kw = call.split_kwargs()
    fn = functools.partial(call.fn, **static_kw) if static_kw else call.fn
    tgt = call.target

    dim_map = None
    if probe is not None and target_scale is not None:
        dim_map, unresolved = scale_dims(probe, target_scale)
        probe_dims = {d for a in jax.tree_util.tree_leaves(call.args)
                      if hasattr(a, "shape") for d in a.shape}
        # pre-scan: if any captured dim will not resolve, audit at
        # probe scale (never mis-scale silently)
        for d in probe_dims:
            dim_map(d)
        if unresolved:
            report.note_audit(
                "jaxpr", f"{tgt} (probe-scale only; unresolved dims "
                f"{sorted(unresolved)})")
            dim_map = None

    a_args = abstractify(call.args, dim_map)
    a_kw = abstractify(traced_kw, dim_map)

    closed = _trace_checked(fn, a_args, a_kw, target=tgt, report=report)
    if closed is None:
        report.note_audit("jaxpr", tgt)
        return
    audit_closed_jaxpr(closed, target=tgt, report=report,
                       out_dtypes=out_dtypes)

    if check_rank:
        try:
            with jax.numpy_rank_promotion("raise"):
                jax.eval_shape(fn, *a_args, **a_kw)
        except Exception as e:  # noqa: BLE001 — any trace error counts
            report.add(
                "jaxpr", "J_RANK_PROMOTION", tgt,
                "implicit rank promotion inside the jitted path "
                f"(trace under numpy_rank_promotion='raise' failed: "
                f"{str(e).splitlines()[0][:160]})")

    report.note_audit("jaxpr", tgt)


def audit_fn(fn, abstract_args, *, target: str, report: Report,
             static_kwargs: dict | None = None,
             out_dtypes: tuple | None = None,
             check_rank: bool = True) -> None:
    """Audit a bare function on explicit abstract args (no capture).

    Used for the module-level jits the pallas backend routes around the
    engine's ``_jitted`` (``kernels.ops``) and for corpus defects.
    """
    if static_kwargs:
        fn = functools.partial(fn, **static_kwargs)
    closed = _trace_checked(fn, abstract_args, {}, target=target,
                            report=report)
    if closed is None:
        report.note_audit("jaxpr", target)
        return
    audit_closed_jaxpr(closed, target=target, report=report,
                       out_dtypes=out_dtypes)
    if check_rank:
        try:
            with jax.numpy_rank_promotion("raise"):
                jax.eval_shape(fn, *abstract_args)
        except Exception as e:  # noqa: BLE001
            report.add(
                "jaxpr", "J_RANK_PROMOTION", target,
                "implicit rank promotion inside the jitted path "
                f"(trace under numpy_rank_promotion='raise' failed: "
                f"{str(e).splitlines()[0][:160]})")
    report.note_audit("jaxpr", target)
