"""Order-free checksum of a pair set, the same on the device and in NumPy.

For each (s, u) pair the checksum adds ``h_i(s) * g_i(u)`` modulo 2**32
for two independent salt pairs i, with ``h_i`` forced odd (so a changed
``u`` always changes the sum) and ``g_i`` a murmur3 finaliser.  Over a
−1-padded ``(cap, 2)`` buffer the device program ``bench_checksum``
returns ``(valid rows, sum_0, sum_1)``; the reference computes the same
three numbers from the regions alone (``reference.ref_checksum_1d``).
"""
from __future__ import annotations

import numpy as np

SALTS = ((0x9E3779B9, 0x7F4A7C15), (0x85EBCA77, 0xC2B2AE3D))


def fmix32(x, xp=np):
    """murmur3's 32-bit finaliser on uint32 arrays of module ``xp``."""
    x = x ^ (x >> 16)
    x = x * xp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * xp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def h_s(s, i: int, xp=np):
    """Odd hash of subscription ids (uint32 in, uint32 out)."""
    return fmix32(s ^ xp.uint32(SALTS[i][0]), xp) | xp.uint32(1)


def g_u(u, i: int, xp=np):
    """Hash of update ids (uint32 in, uint32 out)."""
    return fmix32(u ^ xp.uint32(SALTS[i][1]), xp)


def host_checksum(rows: np.ndarray) -> tuple[int, int, int]:
    """``(valid rows, sum_0, sum_1)`` of a host ``(k, 2)`` pair buffer."""
    rows = np.asarray(rows)
    rows = rows[rows[:, 0] >= 0]
    s = rows[:, 0].astype(np.uint32)
    u = rows[:, 1].astype(np.uint32)
    sums = [int(np.sum(h_s(s, i) * g_u(u, i), dtype=np.uint32))
            for i in range(2)]
    return int(rows.shape[0]), sums[0], sums[1]


def device_checksum_fn():
    """The jitted device program ``bench_checksum(rows)``."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def bench_checksum(rows):
        valid = rows[:, 0] >= 0
        s = rows[:, 0].astype(jnp.uint32)
        u = rows[:, 1].astype(jnp.uint32)
        sums = [jnp.sum(jnp.where(valid, h_s(s, i, jnp) * g_u(u, i, jnp),
                                  jnp.uint32(0)), dtype=jnp.uint32)
                for i in range(2)]
        return jnp.sum(valid, dtype=jnp.int32), sums[0], sums[1]

    return bench_checksum
