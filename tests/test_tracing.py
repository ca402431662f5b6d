"""The program's own trace points: host spans (``ddm.pairs``, ``ddm.sync``)
in a profiler trace, device scopes in the compiled programs' op names,
plan executables named after their plan key, and the exact host sum
every blocking count read goes through."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import xplane
from bench.window import WINDOW_SPAN
from repro.core import MatchSpec, build_plan, engine, itm
from repro.core import distributed as dist
from repro.core.hostread import SYNC_SPAN, host_sum, to_host
from repro.core.regions import Regions, paper_workload
from repro.kernels import emit, ops

N_TOTAL = 600


def _trace(tmp_path, calls):
    """Run ``calls`` inside a ``bench.window`` span of a profiler trace;
    the reduced trace's host spans named ``ddm.*``."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            for call in calls:
                call()
    finally:
        jax.profiler.stop_trace()
    red = xplane.reduce_dir(str(tmp_path))
    return sorted((s, e, name) for name, s, e in red.host
                  if name.startswith("ddm."))


@pytest.mark.parametrize("spec", [
    MatchSpec(backend="xla", capacity="grow"),
    MatchSpec(backend="pallas", capacity="grow", emit_route="streaming",
              interpret=True),
], ids=["xla", "pallas-streaming"])
def test_reads_nest_in_pairs_two_a_call_more_on_a_re_emit(spec, tmp_path):
    S, U = paper_workload(seed=5, n_total=N_TOTAL, alpha=2.0)
    S2, U2 = paper_workload(seed=6, n_total=N_TOTAL, alpha=100.0)
    plan = build_plan(spec, S.n, U.n, 1, key="test_tracing")
    plan.pairs(S, U)                     # warm: compiles, sizes the cap
    cap = plan._cap
    spans = _trace(tmp_path, [lambda: plan.pairs(S, U),
                              lambda: plan.pairs(S2, U2)])   # K grows
    assert plan._cap > cap
    calls = [(s, e) for s, e, name in spans if name == "ddm.pairs"]
    syncs = [(s, e) for s, e, name in spans if name == SYNC_SPAN]
    assert len(calls) == 2
    per_call = [sum(1 for s, e in syncs if c0 <= s and e <= c1)
                for c0, c1 in calls]
    # every read nests in a call: K's two count arrays, read once in
    # steady state and once more after the grow policy's re-emit
    assert sum(per_call) == len(syncs)
    assert per_call == [2, 4]


def _op_names(lowered) -> set[str]:
    """Every name-stack component of the compiled program's op names."""
    text = lowered.compile().as_text()
    return {part for name in re.findall(r'op_name="([^"]*)"', text)
            for part in name.split("/")}


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


PASS1 = {"ddm.pass1.sort", "ddm.pass1.search", "ddm.pass1.scan"}
N, M = 96, 80


def _lower_twopass_tables():
    return ops._twopass_tables.lower(_f32(N), _f32(N), _f32(M), _f32(M),
                                     max_pairs=512)


def _lower_csr_tables():
    return ops._csr_tables.lower(_f32(N), _f32(N), _f32(M), _f32(M),
                                 max_pairs=512, block=256)


def _lower_hsbm_tables():
    return ops._hsbm_tables.lower(
        _f32(N), _f32(N), _f32(M), _f32(M), _f32(), _f32(), ncells=4,
        cap_s=64, suf_s=8, cap_u=64, suf_u=8, max_pairs=512)


def _lower_emit_streaming():
    e = N + M
    return emit.twopass_emit_streaming.lower(
        _i32(e + 1), _i32(e), _i32(e), _i32(N), _i32(M), n=N, m=M,
        max_pairs=512, block=256, interpret=True)


def _lower_verify():
    f = jax.jit(engine.sbm_verify_dims, static_argnames=("max_pairs",))
    return f.lower(Regions(_f32(N, 2), _f32(N, 2)),
                   Regions(_f32(M, 2), _f32(M, 2)), _i32(512, 2),
                   max_pairs=256)


def _lower_exchange():
    mesh = dist.resolve_mesh(None)
    p = int(np.prod(mesh.devices.shape))
    tot = 64 * p
    return dist._dist_count.lower(
        _f32(tot), _i32(tot), _i32(tot), _i32(tot), _f32(p - 1),
        nshards=p, cap=dist.bucket_cap(tot, p, 2.5), blk=64, mesh=mesh)


@pytest.mark.parametrize("lower,scopes", [
    (_lower_twopass_tables, PASS1),
    (_lower_csr_tables, PASS1 | {"ddm.emit.pack", "ddm.emit.pack.compact"}),
    (_lower_hsbm_tables, PASS1),
    (_lower_emit_streaming, {"ddm.emit.pack", "ddm.emit.pack.compact",
                             "ddm.emit.pack.meta"}),
    (_lower_verify, {"ddm.verify"}),
    (_lower_exchange, {"ddm.exchange"}),
], ids=["twopass_tables", "csr_tables", "hsbm_tables",
        "twopass_emit_streaming", "sbm_verify_dims", "dist_count"])
def test_layer_scopes_reach_the_compiled_op_names(lower, scopes):
    assert scopes <= _op_names(lower())


@pytest.mark.parametrize("spec,d", [
    (MatchSpec(algo="sbm", capacity="grow"), 1),
    (MatchSpec(algo="sbm", capacity="exact"), 2),
    (MatchSpec(algo="sbm_chunked"), 1),
    (MatchSpec(algo="sbm_binary"), 1),
    (MatchSpec(algo="hsbm", capacity="grow"), 1),
    (MatchSpec(algo="itm", capacity="grow"), 1),
    (MatchSpec(algo="bfm"), 1),
], ids=lambda x: getattr(x, "algo", str(x)))
def test_plan_programs_are_named_after_their_plan_key(spec, d,
                                                      monkeypatch):
    lowered = []

    def hook(plan, name, fn, static_argnames, cached):
        def call(*args, **kw):
            text = cached.lower(*args, **kw).as_text()
            lowered.append((name, re.search(r"module @(\S+)", text)[1]))
            return cached(*args, **kw)
        return call

    monkeypatch.setattr(engine, "_JIT_CAPTURE_HOOK", hook)
    S, U = paper_workload(seed=3, n_total=N_TOTAL, alpha=4.0, d=d)
    plan = engine.MatchPlan(spec, S.n, U.n, d)
    plan.count(S, U)
    plan.pairs(S, U)
    if spec.algo == "itm":
        plan.query(itm.build_tree(U), U, S.lo[:8], S.hi[:8])
    if spec.algo == "bfm":
        plan.mask(S, U)
    assert lowered
    for name, module in lowered:
        assert module == f"jit_plan_{name}"


@pytest.mark.parametrize("arrays", [
    [np.full(5, 2**30 + 7, np.int32), np.full(3, 2**30, np.int32)],
    [np.arange(-4, 9, dtype=np.int32)],
    [np.int32(12345)],
    [np.zeros(0, np.int32), np.full((2, 3), 2**31 - 1, np.int32)],
], ids=["past-2^31", "signed", "scalar", "empty-and-2d"])
def test_host_sum_is_the_exact_int64_sum(arrays):
    dev = [jnp.asarray(a) for a in arrays]
    old = int(sum(np.sum(np.asarray(a), dtype=np.int64) for a in dev))
    assert old == sum(int(x) for a in arrays for x in np.ravel(a))
    got = host_sum(*dev)
    assert type(got) is int and got == old
    np.testing.assert_array_equal(to_host(dev[-1]), arrays[-1])
    assert to_host(dev[0], np.int64).dtype == np.int64
