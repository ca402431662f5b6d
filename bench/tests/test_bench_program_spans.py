"""Readers of the program's own host spans (``ddm.pairs``, ``ddm.sync``)
on a synthetic trace: nested reads, an idle gap that straddles a span
edge, a span past the window's end, and traces with no such spans."""
from types import SimpleNamespace

import pytest

from bench import cells, xplane
from bench.tests.test_bench_xplane import _line, _meta, synthetic_trace

READERS = ("host_syncs", "sync_idle_ms", "engine_host_ms")


def spans_trace(device: bool = True):
    """Window 0..100 us, two matches.  Device 0 runs ops at 10..30 and
    50..70 us, so it idles 0..10, 30..50 and 70..100.  Each match is a
    ``ddm.pairs`` span (5..40, 45..80) holding one read (20..35,
    60..75); the first read holds a nested one (31..33), and one more
    read (95..110) runs past the window's end."""
    host_names = {1: "bench.window", 2: "ddm.pairs", 3: "ddm.sync"}
    host = [(1, 0, 100), (2, 5, 35), (3, 20, 15), (3, 31, 2),
            (2, 45, 35), (3, 60, 15), (3, 95, 15)]
    cpu = (f'planes {{ id: 2 name: "/host:CPU" '
           f'{_line(1, "python3", 0, host)} {_meta(host_names)} }}')
    dev = ""
    if device:
        dev_names = {1: "jit__twopass_tables(11)",
                     2: "%fusion.7 = f32[8] fusion(f32[8] %a)"}
        mods = [(1, 10, 20), (1, 50, 20)]
        ops = [(2, 10, 20), (2, 50, 20)]
        dev = (f'planes {{ id: 1 name: "/device:TPU:0" '
               f'{_line(1, "XLA Modules", 0, mods)} '
               f'{_line(2, "XLA Ops", 0, ops)} {_meta(dev_names)} }}')
    from jax.profiler import ProfileData
    return xplane.reduce_profile(ProfileData.from_text_proto(dev + cpu))


def _read(trace, name, matches=2):
    ctx = SimpleNamespace(trace=trace, counts={"matches": matches})
    return cells.metric_reader(name)(ctx)


def test_host_syncs_counts_every_read_span():
    # four ddm.sync spans overlap the window, the nested one included
    assert _read(spans_trace(), "host_syncs") == pytest.approx(2.0)


def test_sync_idle_counts_idle_time_inside_reads_once():
    # idle 30..50 meets the read 20..35 at 30..35 (the nested read at
    # 31..33 counts once), idle 70..100 meets 60..75 at 70..75 and the clipped
    # read 95..100 at 95..100: 15 us over 2 matches
    assert _read(spans_trace(), "sync_idle_ms") == pytest.approx(7.5e-3)


def test_engine_host_time_is_pairs_less_the_reads_inside():
    # 35 + 35 us of ddm.pairs less the 15 + 15 us of reads inside them;
    # the read past both spans takes nothing off
    assert _read(spans_trace(), "engine_host_ms") == pytest.approx(20e-3)


def test_sync_idle_needs_a_device():
    trace = spans_trace(device=False)
    assert trace.n_devices == 0
    assert _read(trace, "sync_idle_ms") is None
    assert _read(trace, "host_syncs") == pytest.approx(2.0)


@pytest.mark.parametrize("name", READERS)
def test_no_program_spans_read_nothing(name):
    """A program without the spans (an older commit) gives no value."""
    assert _read(synthetic_trace(), name) is None
    assert _read(spans_trace(), name, matches=0) is None
