"""DDM-planned block-sparse flash attention (Pallas TPU kernel).

Consumes the per-q-block [start, end) kv token windows produced by
``repro.sparse.planner`` (i.e. by the paper's interval matcher) plus the
sink prefix, and computes attention with an online-softmax accumulator —
each program owns one q block, walks the sink blocks then its kv window
in ``block_kv`` steps with dynamic ``pl.ds`` loads, so only
(block_q × block_kv) tiles are ever live in VMEM and nothing quadratic is
materialized.

Layout per program: q (bq, dh) VMEM block; k/v full arrays (the
test/validation sizes fit; a production variant would keep k/v in ANY
space and DMA tiles — same index arithmetic).  starts/ends ride along as
(nq,) int32 arrays.

Validated in interpret mode against ``ref.windowed_attention`` +
dense-masked attention in tests; ``repro.sparse.attention`` is the jnp
fallback used on non-TPU backends.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(qid_ref, starts_ref, ends_ref, q_ref, k_ref, v_ref, o_ref, *,
            bq: int, bkv: int, sink_end: int, scale: float):
    # NB: the q-block index arrives as a blocked (1,) input instead of
    # pl.program_id so the same kernel body works for any grid prefix
    # (the batch·head axis is grid dim 0).
    i = qid_ref[0]
    q = q_ref[0].astype(jnp.float32) * scale            # (bq, dh)
    dh = q.shape[-1]
    q_pos = i * bq + jax.lax.iota(jnp.int32, bq)        # (bq,)

    start = starts_ref[0]
    end = ends_ref[0]

    def attend(kv_off, carry):
        acc, m, l = carry
        kblk = k_ref[0, pl.ds(kv_off, bkv), :]
        vblk = v_ref[0, pl.ds(kv_off, bkv), :]
        s = q @ kblk.astype(jnp.float32).T               # (bq, bkv)
        kv_pos = kv_off + jax.lax.iota(jnp.int32, bkv)
        ok = (kv_pos[None, :] <= q_pos[:, None]) & \
             (kv_pos[None, :] < end)
        s = jnp.where(ok, s, NEG_INF)
        m2 = jnp.maximum(m, jnp.max(s, axis=1))
        alpha = jnp.exp(m - m2)
        p = jnp.exp(s - m2[:, None])
        l2 = l * alpha + jnp.sum(p, axis=1)
        acc2 = acc * alpha[:, None] + p @ vblk.astype(jnp.float32)
        return acc2, m2, l2

    acc = jnp.zeros((bq, dh), jnp.float32)
    m = jnp.full((bq,), NEG_INF, jnp.float32)
    l = jnp.zeros((bq,), jnp.float32)

    # sink prefix [0, sink_end): static trip count
    for j in range(sink_end // bkv):
        acc, m, l = attend(j * bkv, (acc, m, l))

    # DDM window [start, end): dynamic trip count
    start_blk = jnp.maximum(start, sink_end) // bkv
    n_blocks = (end - start_blk * bkv + bkv - 1) // bkv

    def body(j, carry):
        return attend(start_blk * bkv + j * bkv, carry)

    acc, m, l = jax.lax.fori_loop(0, n_blocks, body, (acc, m, l))
    safe_l = jnp.where(l > 0, l, 1.0)
    o_ref[0] = (acc / safe_l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bkv", "sink_end",
                                             "interpret"))
def _sparse_attn_bh(q, k, v, starts, ends, *, bq: int, bkv: int,
                    sink_end: int, interpret: bool):
    """q/k/v: (BH, S, dh) — grid (BH, nq)."""
    BH, Sq, dh = q.shape
    nq = Sq // bq
    scale = dh ** -0.5
    kern = functools.partial(_kernel, bq=bq, bkv=bkv, sink_end=sink_end,
                             scale=scale)
    qids = jnp.arange(nq, dtype=jnp.int32)
    return pl.pallas_call(
        kern,
        grid=(BH, nq),
        in_specs=[
            pl.BlockSpec((1,), lambda b, i: (i,)),
            pl.BlockSpec((1,), lambda b, i: (i,)),
            pl.BlockSpec((1,), lambda b, i: (i,)),
            pl.BlockSpec((1, bq, dh), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1,) + k.shape[1:], lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1,) + v.shape[1:], lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, dh), q.dtype),
        interpret=interpret,
    )(qids, starts, ends, q, k, v)


def sparse_attn_1h(q, k, v, starts, ends, *, bq: int = 128,
                   bkv: int = 128, sink_end: int = 0,
                   interpret: bool = False):
    """Single-head: q (Sq, dh), k/v (Skv, dh), starts/ends (nq,) int32."""
    Sq, dh = q.shape
    assert Sq % bq == 0, (Sq, bq)
    assert starts.shape == (Sq // bq,) and ends.shape == (Sq // bq,)
    out = _sparse_attn_bh(q[None], k[None], v[None], starts, ends,
                          bq=bq, bkv=bkv, sink_end=sink_end,
                          interpret=interpret)
    return out[0]


def sparse_attn(q, k, v, starts, ends, *, bq: int = 128, bkv: int = 128,
                sink_end: int = 0, interpret: bool = False):
    """Batched multi-head: q/k/v (B, S, H, dh) — batch·head = grid dim 0."""
    B, S, H, dh = q.shape
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, S, dh)  # noqa
    out = _sparse_attn_bh(fold(q), fold(k), fold(v), starts, ends,
                          bq=bq, bkv=bkv, sink_end=sink_end,
                          interpret=interpret)
    return out.reshape(B, H, S, dh).transpose(0, 2, 1, 3)
