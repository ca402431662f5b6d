"""The copied generators and the NumPy references against brute force,
and the emit byte model against a hand count, at tiny sizes."""
import inspect

import numpy as np
import pytest

from bench import checksum, gen, reference
from bench.emit_bytes import emit_bytes

SEED = 2**40 + 17


def all_pairs(s_lo, s_hi, u_lo, u_hi):
    """Every overlapping (s, u) pair, one nested comparison."""
    ok = np.all((s_lo[:, None] < u_hi[None]) & (u_lo[None] < s_hi[:, None]),
                axis=-1)
    return np.argwhere(ok)


@pytest.mark.parametrize("alpha", [0.01, 3.0, 100.0])
def test_references_match_brute_force_1d(alpha):
    s_lo, s_hi, u_lo, u_hi = gen.paper_workload(gen.rng_for(SEED, 0), 1200,
                                                alpha)
    assert s_lo.dtype == np.float32 and np.all(s_hi > s_lo)
    rows = all_pairs(s_lo, s_hi, u_lo, u_hi)
    m = u_lo.shape[0]
    assert reference.ref_count_1d(s_lo[:, 0], s_hi[:, 0], u_lo[:, 0],
                                  u_hi[:, 0]) == len(rows)
    assert reference.ref_checksum_1d(s_lo, s_hi, u_lo, u_hi) == \
        checksum.host_checksum(rows)
    codes = np.sort(rows[:, 0] * m + rows[:, 1])
    assert np.array_equal(reference.ref_codes(s_lo, s_hi, u_lo, u_hi), codes)
    assert np.array_equal(reference.codes_of(rows, m), codes)
    ids = np.arange(s_lo.shape[0])
    assert np.array_equal(
        reference.brute_codes(ids, s_lo, s_hi, u_lo, u_hi), codes)


def test_references_match_brute_force_2d_and_box_queries():
    s_lo, s_hi, u_lo, u_hi = gen.paper_workload(gen.rng_for(SEED, 1), 1600,
                                                40.0, d=2)
    rows = all_pairs(s_lo, s_hi, u_lo, u_hi)
    m = u_lo.shape[0]
    assert np.array_equal(reference.ref_codes(s_lo, s_hi, u_lo, u_hi),
                          np.sort(rows[:, 0] * m + rows[:, 1]))
    q_lo, q_hi = gen.make_query_boxes(gen.rng_for(SEED, 2), 5, 2, 2e5)
    for lo, hi in zip(q_lo, q_hi):
        want = [i for i in range(m)
                if all(u_lo[i, k] < hi[k] and lo[k] < u_hi[i, k]
                       for k in range(2))]
        assert reference.brute_ids(u_lo, u_hi, lo, hi).tolist() == want


def test_checksum_catches_a_moved_or_dropped_pair():
    rows = np.array([[0, 1], [2, 3], [4, 5], [-1, -1]])
    base = checksum.host_checksum(rows)
    assert base[0] == 3
    for bad in ([[0, 1], [2, 4], [4, 5]], [[0, 1], [2, 3]],
                [[0, 1], [3, 3], [4, 5]]):
        assert checksum.host_checksum(np.array(bad)) != base
    assert checksum.host_checksum(rows[[2, 0, 1, 3]]) == base


def test_device_checksum_equals_host_checksum():
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    rows = rng.integers(0, 1 << 20, size=(1000, 2)).astype(np.int32)
    rows[700:] = -1
    dev = [int(x) for x in checksum.device_checksum_fn()(jnp.asarray(rows))]
    assert tuple(dev) == checksum.host_checksum(rows)


def test_generators_are_seeded_and_take_large_seeds():
    a = gen.paper_workload(gen.rng_for(2**62 + 5, 0), 1000, 5.0)
    b = gen.paper_workload(gen.rng_for(2**62 + 5, 0), 1000, 5.0)
    c = gen.paper_workload(gen.rng_for(2**62 + 6, 0), 1000, 5.0)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    idx, lo, hi = gen.make_moves(gen.rng_for(SEED, 3), 500, 64, 2,
                                 (1.0, 5e3))
    assert len(set(idx.tolist())) == 64 and lo.shape == (64, 2)
    assert np.all(hi - lo >= 1.0) and np.all(hi - lo <= 5e3 + 1)


def test_emit_bytes_is_a_hand_count_over_k_n_m_only():
    # 3 subscriptions, 2 updates, 4 pairs: 4 * (8 + 4) + 5 * 12
    assert emit_bytes(4, 3, 2) == 108
    assert emit_bytes(0, 0, 0) == 0
    assert list(inspect.signature(emit_bytes).parameters) == ["k", "n", "m"]
    with pytest.raises(ValueError):
        emit_bytes(-1, 3, 2)


def test_emit_bytes_is_the_same_for_every_route():
    """Every emit route yields the reference's K, and the byte count
    depends on nothing else."""
    import jax.numpy as jnp
    from repro.core.engine import MatchSpec, build_plan
    from repro.core.regions import Regions
    s_lo, s_hi, u_lo, u_hi = gen.paper_workload(gen.rng_for(SEED, 4), 600,
                                                20.0)
    k_ref = reference.ref_count_1d(s_lo[:, 0], s_hi[:, 0], u_lo[:, 0],
                                   u_hi[:, 0])
    S = Regions(jnp.asarray(s_lo), jnp.asarray(s_hi))
    U = Regions(jnp.asarray(u_lo), jnp.asarray(u_hi))
    seen = set()
    for route in ("resident", "streaming", "csr", "xla"):
        plan = build_plan(MatchSpec(backend="pallas", capacity="grow",
                                    interpret=True, emit_route=route,
                                    block=128), 300, 300, 1)
        _, k = plan.pairs(S, U)
        seen.add(emit_bytes(k, 300, 300))
    assert seen == {emit_bytes(k_ref, 300, 300)}
