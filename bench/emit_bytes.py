"""Least HBM traffic of the pair emit, counted from the problem alone.

Per pair the emit writes one (s, u) int32 row (8 B) and reads one int32
of a sort permutation (4 B); per emitter (n subscriptions + m updates)
it reads three int32 table words: offset, count and start (12 B).  The
count depends on K, n and m only, so it is the same whatever emit route
or implementation runs.
"""
from __future__ import annotations

PAIR_WRITE_BYTES = 8
PAIR_PERM_READ_BYTES = 4
EMITTER_TABLE_BYTES = 12


def emit_bytes(k: int, n: int, m: int) -> int:
    """Bytes the emit must move for K pairs over n + m emitters."""
    if min(k, n, m) < 0:
        raise ValueError(f"negative size: K={k}, n={n}, m={m}")
    return ((PAIR_WRITE_BYTES + PAIR_PERM_READ_BYTES) * k
            + EMITTER_TABLE_BYTES * (n + m))
