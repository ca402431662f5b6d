"""Trace reduction on a synthetic trace: idle share, kernel-name grouping,
nesting, per-match division, and the peak table."""
from types import SimpleNamespace

import pytest

from bench import cells, peaks, xplane

US = 1_000_000  # ps per microsecond


def _line(lid, name, t0_ns, events):
    evs = " ".join(f"events {{ metadata_id: {m} offset_ps: {o * US} "
                   f"duration_ps: {d * US} }}" for m, o, d in events)
    return f'lines {{ id: {lid} name: "{name}" timestamp_ns: {t0_ns} {evs} }}'


def _meta(names):
    return " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }}' for i, n in names.items())


def synthetic_trace():
    """Window 0..100 us on the host; on the device two matches, each a
    pass-1 module (a while loop with two nested fusions), an emit module
    (table packing, then the emit kernel) and a checksum module."""
    dev_names = {1: "jit__twopass_tables(11)", 2: "jit_twopass_emit(22)",
                 3: "jit_bench_checksum(33)",
                 4: "%while.3 = (s32[]) while(s32[] %p)",
                 5: "%fusion.7 = f32[8] fusion(f32[8] %a)",
                 6: "%fusion.9 = f32[8] fusion(f32[8] %b)",
                 7: "%emit_streaming.2 = (s32[1,8]) custom-call(s32[4] %c)",
                 8: "%reduce.1 = u32[] reduce(u32[8] %d)",
                 9: "%fusion.5 = s32[8] fusion(s32[8] %e)"}
    mods, ops = [], []
    for base in (10, 50):       # two matches
        mods += [(1, base, 12), (2, base + 14, 10), (3, base + 26, 2)]
        ops += [(4, base, 12), (5, base + 1, 5), (6, base + 7, 4),
                (9, base + 14, 3), (7, base + 17, 7), (8, base + 26, 2)]
    ops.append((5, 120, 5))     # after the window: clipped away
    host_names = {1: "bench.window", 2: "bench.match", 3: "bench.checksum"}
    host = [(1, 0, 100), (2, 8, 22), (2, 48, 22), (3, 33, 4)]
    device = (f'planes {{ id: 1 name: "/device:TPU:0" '
              f'{_line(1, "XLA Modules", 0, mods)} '
              f'{_line(2, "XLA Ops", 0, ops)} {_meta(dev_names)} }}')
    cpu = (f'planes {{ id: 2 name: "/host:CPU" '
           f'{_line(1, "python3", 0, host)} {_meta(host_names)} }}')
    from jax.profiler import ProfileData
    return xplane.reduce_profile(ProfileData.from_text_proto(device + cpu))


def test_window_busy_and_idle_share():
    red = synthetic_trace()
    assert red.window == (0, 100_000)
    assert red.n_devices == 1
    # per match: 12 + 10 + 2 us of programs, ops cover 12 + 3 + 7 + 2
    assert red.busy_s == pytest.approx(2 * 24e-6)
    assert red.idle_share == pytest.approx(1 - 48 / 100)


def test_names_nesting_and_modules():
    red = synthetic_trace()
    assert xplane.op_name("%emit_csr.12 = (s32[8]) custom-call()") == \
        "emit_csr"
    assert xplane.module_name("jit__build(2713628194)") == "jit__build"
    emit = red.select(op=lambda n: n.startswith("emit_"))
    assert red.seconds(emit) == pytest.approx(2 * 7e-6)
    # the while loop holds two fusions: only leaves count, never twice
    pass1 = red.select(op=lambda n: not n.startswith("emit_"),
                       module=lambda m: "bench_checksum" not in m)
    assert red.seconds(pass1) == pytest.approx(2 * (5 + 4 + 3) * 1e-6)
    assert red.run_seconds(lambda m: m == "jit__twopass_tables") == \
        pytest.approx(2 * 12e-6)
    top = dict(red.top_ops(10))
    assert top["jit_twopass_emit/emit_streaming"] == pytest.approx(14e-6)
    assert "jit__twopass_tables/while" not in top      # not a leaf


def test_idle_gaps_named_by_innermost_host_span():
    gaps = dict(synthetic_trace().idle_gaps(10))
    assert sum(gaps.values()) == pytest.approx(52e-6)
    assert gaps["bench.checksum"] == pytest.approx(2e-6)   # gap 34..36 us
    assert gaps["bench.match"] == pytest.approx(2 * 2e-6)
    assert gaps["host idle"] == pytest.approx(52e-6 - 6e-6)


def test_metric_readers_divide_per_match():
    ctx = SimpleNamespace(trace=synthetic_trace(),
                          counts={"matches": 2, "emit_bytes": 2 * 819 * 7},
                          device_kind="TPU v5 lite",
                          peak=lambda k: peaks.peak("TPU v5 lite", k))
    read = {n: cells.metric_reader(n)(ctx)
            for n in ("emit_ms", "pass1_ms", "emit_roofline",
                      "device_idle.match", "tree_build_ms")}
    assert read["emit_ms"] == pytest.approx(7e-3)
    assert read["pass1_ms"] == pytest.approx(12e-3)
    # 2 * 819 * 7 B at 819 GB/s = 14 ns over 14 us of emit
    assert read["emit_roofline"] == pytest.approx(0.1)
    assert read["device_idle.match"] == pytest.approx(52.0)
    assert read["tree_build_ms"] is None       # no rebuild to read


def test_peak_table_is_keyed_by_device_kind():
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    assert "TPU v5e" in peaks.PEAKS["TPU v5 lite"]["source"]
    with pytest.raises(KeyError, match="TPU v9"):
        peaks.peak("TPU v9", "hbm_bytes_per_s")
