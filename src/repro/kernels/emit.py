"""Fused two-pass emit — pass 2 of count-then-emit as one Pallas kernel.

Pass 1 of the exact pair enumeration (``core.sbm._twopass_phase1``)
produces per-emitter counts and saturated exclusive-scan output offsets
on the XLA side.  Pass 2 — the slot→(emitter, rank) lookup and the pair
write — is one Mosaic kernel here, shared by three emit routes that
differ only in where the tables live:

``twopass_emit`` (resident)
    The packed emitter table and both sort permutations are copied into
    VMEM once, at the first grid step, and every output tile reads them
    from there.  VMEM use ≈ 36·(n+m) bytes.

``twopass_emit_streaming``
    The packed table stays in HBM and each tile's window of it streams
    in by DMA (double-buffered: tile ``i + 1``'s window is in flight
    while tile ``i`` writes); only the two permutations are copied into
    VMEM.  VMEM use ≈ 4·(n+m) bytes.

``csr_decode_window`` (CSR route)
    Nothing is resident: table windows and permutation pieces both
    stream from HBM, so VMEM use is constant in n+m.  It decodes any
    window of slots on demand — the lazy ``kernels.ops.CSRPairs`` view.

The packed table (``pack_emitter_tables``) keeps only emitters with a
non-zero count, so compacted offsets are strictly increasing below the
saturation limit and each emitter ``k`` owns the contiguous slot run
``[offs[k], offs[k] + counts[k])``.  One output tile of ``B`` slots is
therefore covered by at most ``B + 1`` consecutive emitters, starting at
the owner of the tile's first slot.  The XLA side compacts the table
with one sort, finds the first and last owner of every tile with one
vectorized searchsorted over all of them (``tile_meta``) and passes
them, with the tile's 128-aligned table window base, as scalar-prefetch
operands.

The kernel walks those emitters in a scalar loop over the table window
(held in SMEM).  Emitter ``k``'s slots in the tile take a constant own
half (subscription ``e`` for class A, update ``e − n`` for class B) and
a contiguous run of the opposite side's sort permutation as the partner
half.  Mosaic has no cross-vreg vector gather and only tile-aligned
slices of memory, so a run is moved in pieces of at most 128 lanes:
load the 256-lane aligned permutation span that holds the piece, rotate
it into place (``pltpu.roll``) and merge it into the tile's output line
under a lane mask.  Slots no run covers (past K) keep the −1 pad.

Slot semantics match the XLA pass 2 bit-for-bit: slot ``t`` belongs to
the last emitter ``e`` with ``offs[e] <= t``; its rank is
``t − offs[e]``; ranks at or beyond the emitter's count emit the −1
pad.  Class-A emitters (``e < n``) own subscription ``e`` and read the
update id from the lo-sorted U permutation; class-B emitters own update
``e − n`` and read the subscription id from the lo-sorted S
permutation.  Compaction cannot change any emitted pair: zero-count
emitters share their offset with a successor, so they are never last.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_PAD_OFF = (1 << 30)  # > any slot id; padded offsets are never selected
DEF_BLOCK = 512
# table window: one output tile of B slots addresses <= B + 1 consecutive
# compacted emitters; +128 covers aligning the window base down to a
# lane multiple, and the total stays a lane multiple itself.
STREAM_WIN_EXTRA = 256
# lanes of one permutation piece; a piece is read from the aligned
# 2 x 128-lane span that holds it
PIECE = 128
SPAN = 2 * PIECE
# scoped VMEM the emit kernels request (v5e has 128 MiB per core); the
# route policy's table budget (``kernels.ops``) stays below it
VMEM_LIMIT_BYTES = 32 << 20
EMIT_MODES = ("resident", "streaming", "csr")
# tiles per pallas_call: the scalar-prefetch operand (3 words per tile)
# and the table windows share the core's 1 MiB of SMEM
MAX_TILES = 16384


def lane_pad(x: int, mult: int = 128) -> int:
    """Round ``x`` up to a lane multiple (the kernels' padded table size)."""
    return -(-x // mult) * mult


def stream_window(block: int) -> int:
    """Table window length (lanes) for an emit ``block``.

    The single source of truth for the window size: the kernels' SMEM
    window is ``(2, 8, stream_window(block))`` and the packed table is
    at least this wide.
    """
    return lane_pad(block) + STREAM_WIN_EXTRA


def table_len(e: int, win: int) -> int:
    """Lane length of the packed table for ``e`` emitters."""
    return max(lane_pad(e), win)


def perm_len(x: int) -> int:
    """Lane length a permutation of ``x`` ids is padded to: every
    aligned ``SPAN``-lane read of a piece stays in bounds."""
    return lane_pad(x) + SPAN


def emit_vmem_bytes(n: int, m: int, block: int, mode: str) -> int:
    """VMEM scratch bytes one emit kernel allocates (int32 words x 4).

    Every mode holds the two (1, block + 128) output lines.  ``resident``
    adds the packed (8, table_len) table and both padded permutations;
    ``streaming`` adds the permutations only; ``csr`` adds one
    ``SPAN``-lane piece buffer.  The route policy
    (``kernels.ops.emit_route_bytes``) charges exactly this, and the
    static auditor checks it against the kernels' captured scratch.
    """
    bl = lane_pad(block)
    words = 2 * (bl + PIECE)
    perms = perm_len(n) + perm_len(m)
    if mode == "resident":
        words += 8 * table_len(n + m, stream_window(bl)) + perms
    elif mode == "streaming":
        words += perms
    else:
        words += SPAN
    return 4 * words


def _empty_pairs():
    return jnp.zeros((0, 2), jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_a", "n_b"))
def remap_slot_pairs(pairs, sid, uid, *, n_a: int, n_b: int):
    """Map slot-space pair halves back to original region ids (hsbm).

    The hybrid grid+SBM pass 1 (``core.sbm._hsbm_phase1``) reuses every
    emit kernel unchanged by relabeling: its ``n_a``/``n_b`` flattened
    emitter-table rows play the roles of the flat path's n/m emitters,
    and the *shifted id tables* ``sid + n_a`` / ``uid + n_b`` play the
    sort permutations.  A kernel-emitted pair half is then either an
    own-emitter slot index (class-A s-half: ``< n_a``; class-B u-half:
    ``< n_b``) or a gathered shifted id (``>= n_a`` resp. ``>= n_b``) —
    the two ranges are disjoint by construction.  This helper undoes
    the encoding: −1 pads pass through, slot values gather the id
    table, shifted values subtract the shift.  Valid slots never
    gather a pad row of the id tables (emitter windows only cover real
    natives), so the result is exactly the original-id buffer the XLA
    hybrid pass 2 (``core.sbm._hsbm_emit``) writes.
    """
    c0, c1 = pairs[:, 0], pairs[:, 1]
    s_idx = jnp.where(
        c0 < 0, -1,
        jnp.where(c0 < n_a, jnp.take(sid, jnp.clip(c0, 0, n_a - 1)),
                  c0 - n_a))
    u_idx = jnp.where(
        c1 < 0, -1,
        jnp.where(c1 < n_b, jnp.take(uid, jnp.clip(c1, 0, n_b - 1)),
                  c1 - n_b))
    return jnp.stack([s_idx, u_idx], axis=1)


def pack_emitter_tables(offs, counts, starts, *, n: int, m: int,
                        min_len: int):
    """Compact + pack pass 1's emitter tables (XLA side, traceable).

    Zero-count emitters are dropped — they share their offset with a
    successor, so the slot lookup (*last* emitter at ``offs <= t``)
    never selects them — leaving compacted offsets strictly increasing
    below saturation, which bounds one B-slot tile's reach to B + 1
    consecutive entries.  Survivors pack into one (8, E_pad) int32
    array: rows 0–3 are saturated offsets / counts / input starts /
    original emitter id; rows 4–7 pad to the 8-sublane int32 tile
    height so HBM window slices stay tile-aligned.  ``min_len`` floors
    E_pad at the widest window a consumer will slice; pad entries
    carry offset ``_PAD_OFF``, count 0 and emitter id n + m, so they
    never write a slot.

    The compaction is one sort: each emitter's key is its index, plus
    ``n + m`` when it is dropped, so survivors come first in emitter
    order and carry their rows along: no scatter and no gather.
    """
    E = n + m
    with jax.named_scope("ddm.emit.pack"), \
            jax.named_scope("ddm.emit.pack.compact"):
        idx = jax.lax.iota(jnp.int32, E)
        key = jnp.where(counts > 0, idx, idx + E)
        # keys are unique, so the sort needs no stability
        cols = jnp.stack(jax.lax.sort((key, offs[:E], counts, starts),
                                      num_keys=1, is_stable=False))
        # lane padding: a key of E marks a pad entry like a dropped one
        cols = jnp.pad(cols, ((0, 0), (0, table_len(E, min_len) - E)),
                       constant_values=E)
        ok = cols[0] < E
        rows = [jnp.where(ok, cols[1], _PAD_OFF), jnp.where(ok, cols[2], 0),
                jnp.where(ok, cols[3], 0), jnp.where(ok, cols[0], E)]
        return jnp.pad(jnp.stack(rows), ((0, 4), (0, 0)))


def pad_perm(perm):
    """A sort permutation as a (1, perm_len) row, zero-padded."""
    return jnp.pad(perm, (0, perm_len(perm.shape[0]) - perm.shape[0])
                   ).reshape(1, -1)


def tile_meta(c_offs, w0, *, nt: int, block: int, win: int,
              limit: int | None = None):
    """Scalar-prefetch operand for ``nt`` tiles of ``block`` slots.

    Layout: ``[w0, base[nt], k_first[nt], k_last[nt]]``.  Tile ``i``
    covers slots ``[w0 + i·block, w0 + (i+1)·block)``; ``k_first`` /
    ``k_last`` are the owners of its first and last slot below
    ``limit`` (the saturation limit, when known), and ``base`` is the
    128-aligned start of the table window that holds them.  ``k_last``
    is clipped to that window, which only matters for slots at or past
    the limit — those are trimmed by every caller.  The first and last
    slots of all ``nt`` tiles share one search.
    """
    e_pad = c_offs.shape[0]
    with jax.named_scope("ddm.emit.pack"), \
            jax.named_scope("ddm.emit.pack.meta"):
        t0 = w0 + jnp.arange(nt, dtype=jnp.int32) * block
        t_end = t0 + (block - 1)
        if limit is not None:
            t_end = jnp.minimum(t_end, limit - 1)
        owner = jnp.searchsorted(c_offs, jnp.concatenate([t0, t_end]),
                                 side="right").astype(jnp.int32) - 1
        k_first = jnp.maximum(owner[:nt], 0)
        base = jnp.minimum((k_first // 128) * 128, e_pad - win)
        k_last = jnp.minimum(owner[nt:], base + win - 1)
        return jnp.concatenate([jnp.reshape(w0, (1,)), base, k_first,
                                k_last])


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

def _emit_kernel(meta_ref, tab_ref, perm_s_ref, perm_u_ref, s_out_ref,
                 u_out_ref, win_ref, sem_ref, s_line, u_line, *rest,
                 n: int, nt: int, block: int, win: int, mode: str):
    """One output tile of ``block`` slots per grid step.

    ``tab_ref`` / ``perm_*_ref`` are in HBM (``ANY``).  ``resident``
    copies all three into VMEM scratch (``rest``) at step 0; ``streaming``
    copies the permutations only; ``csr`` copies nothing and reads
    permutation spans through the ``rest[0]`` piece buffer.  ``win_ref``
    is the (2, 8, win) SMEM double buffer of table windows.
    """
    i = pl.program_id(0)
    if mode == "resident":
        tab_src, perm_s_src, perm_u_src = rest
    elif mode == "streaming":
        tab_src = tab_ref
        perm_s_src, perm_u_src = rest
    else:
        tab_src, perm_s_src, perm_u_src = tab_ref, perm_s_ref, perm_u_ref
        (piece_buf,) = rest

    def window_copy(tile, slot):
        base = pl.multiple_of(meta_ref[1 + tile], 128)
        return pltpu.make_async_copy(tab_src.at[:, pl.ds(base, win)],
                                     win_ref.at[slot], sem_ref.at[slot])

    @pl.when(i == 0)
    def _():
        if mode != "csr":
            copies = [(perm_s_ref, perm_s_src), (perm_u_ref, perm_u_src)]
            if mode == "resident":
                copies.append((tab_ref, tab_src))
            for src, dst in copies:
                cp = pltpu.make_async_copy(src, dst, sem_ref.at[2])
                cp.start()
                cp.wait()
        window_copy(0, 0).start()

    slot = jax.lax.rem(i, 2)

    @pl.when(i + 1 < nt)
    def _():
        window_copy(i + 1, 1 - slot).start()

    window_copy(i, slot).wait()

    t0 = meta_ref[0] + i * block
    base = meta_ref[1 + i]
    s_line[...] = jnp.full(s_line.shape, -1, jnp.int32)
    u_line[...] = jnp.full(u_line.shape, -1, jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, SPAN), 1)

    def load_span(src_ref, a):
        if mode == "csr":
            cp = pltpu.make_async_copy(src_ref.at[:, pl.ds(a, SPAN)],
                                       piece_buf, sem_ref.at[2])
            cp.start()
            cp.wait()
            return piece_buf[...]
        return src_ref[:, pl.ds(a, SPAN)]

    def emitter(k, carry):
        kk = k - base
        off = win_ref[slot, 0, kk]
        cnt = win_ref[slot, 1, kk]
        start = win_ref[slot, 2, kk]
        e = win_ref[slot, 3, kk]
        j0 = jnp.maximum(t0 - off, 0)       # first rank inside the tile
        p0 = jnp.maximum(off - t0, 0)       # its tile-relative slot
        length = jnp.minimum(cnt - j0, block - p0)

        def piece(q, c):
            src = start + j0 + q * PIECE
            dst = p0 + q * PIECE
            a = pl.multiple_of((src // PIECE) * PIECE, PIECE)
            cc = pl.multiple_of((dst // PIECE) * PIECE, PIECE)
            d = dst - cc
            mask = (lane >= d) & (lane < d + jnp.minimum(length - q * PIECE,
                                                         PIECE))
            # span lane r + x holds perm[src + x]; rotate it to lane d + x
            shift = jax.lax.rem(d - (src - a) + SPAN, SPAN)

            def write(perm_src, part_line, own_line, own):
                part = pltpu.roll(load_span(perm_src, a), shift, 1)
                part_line[:, pl.ds(cc, SPAN)] = jnp.where(
                    mask, part, part_line[:, pl.ds(cc, SPAN)])
                own_line[:, pl.ds(cc, SPAN)] = jnp.where(
                    mask, own, own_line[:, pl.ds(cc, SPAN)])

            @pl.when(e < n)
            def _():
                write(perm_u_src, u_line, s_line, e)

            @pl.when(e >= n)
            def _():
                write(perm_s_src, s_line, u_line, e - n)

            return c

        jax.lax.fori_loop(0, (length + PIECE - 1) // PIECE, piece, 0)
        return carry

    jax.lax.fori_loop(meta_ref[1 + nt + i], meta_ref[1 + 2 * nt + i] + 1,
                      emitter, 0)
    s_out_ref[...] = s_line[:, pl.ds(0, block)]
    u_out_ref[...] = u_line[:, pl.ds(0, block)]


def emit_call(meta, tab, perm_s_pad, perm_u_pad, *, n: int, nt: int,
              block: int, mode: str, interpret: bool = False):
    """The bare ``pallas_call``: ``nt`` tiles of ``block`` slots.

    ``tab`` from ``pack_emitter_tables``, the permutations from
    ``pad_perm``, ``meta`` from ``tile_meta``.  Returns the s and u
    halves as two (1, nt·block) int32 rows.
    """
    win = stream_window(block)
    scratch = [pltpu.SMEM((2, 8, win), jnp.int32),
               pltpu.SemaphoreType.DMA((3,)),
               pltpu.VMEM((1, block + PIECE), jnp.int32),
               pltpu.VMEM((1, block + PIECE), jnp.int32)]
    if mode == "resident":
        scratch.append(pltpu.VMEM(tab.shape, jnp.int32))
    if mode == "csr":
        scratch.append(pltpu.VMEM((1, SPAN), jnp.int32))
    else:
        scratch += [pltpu.VMEM(perm_s_pad.shape, jnp.int32),
                    pltpu.VMEM(perm_u_pad.shape, jnp.int32)]
    out_spec = pl.BlockSpec((1, block), lambda i, meta: (0, i))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=(out_spec, out_spec),
        scratch_shapes=scratch,
    )
    row = jax.ShapeDtypeStruct((1, nt * block), jnp.int32)
    return pl.pallas_call(
        functools.partial(_emit_kernel, n=n, nt=nt, block=block, win=win,
                          mode=mode),
        grid_spec=grid_spec,
        out_shape=(row, row),
        # steps run in order: step 0's copies and each step's window
        # prefetch feed the steps after it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=f"emit_{mode}",
    )(meta, tab, perm_s_pad, perm_u_pad)


def _emit_slots(tab, perm_s_pad, perm_u_pad, w0, *, n: int, nslots: int,
                block: int, mode: str, interpret: bool,
                limit: int | None = None):
    """Slots ``[w0, w0 + nslots)`` as an (nslots, 2) buffer, in calls
    of at most ``MAX_TILES`` tiles of ``block`` slots each."""
    win = stream_window(block)
    nt = -(-nslots // block)
    meta = tile_meta(tab[0], w0, nt=nt, block=block, win=win, limit=limit)
    rows = meta[1:].reshape(3, nt)
    halves = []
    for c0 in range(0, nt, MAX_TILES):
        nc = min(MAX_TILES, nt - c0)
        chunk = jnp.concatenate([meta[:1] + c0 * block,
                                 rows[:, c0:c0 + nc].reshape(-1)])
        halves.append(emit_call(chunk, tab, perm_s_pad, perm_u_pad, n=n,
                                nt=nc, block=block, mode=mode,
                                interpret=interpret))
    s_out = jnp.concatenate([h[0][0] for h in halves])[:nslots]
    u_out = jnp.concatenate([h[1][0] for h in halves])[:nslots]
    return jnp.stack([s_out, u_out], axis=1)


def _dense_emit(offs, counts, starts, perm_s, perm_u, *, n: int, m: int,
                max_pairs: int, block: int, mode: str, interpret: bool):
    if max_pairs == 0:
        return _empty_pairs()
    bl = min(lane_pad(block), max(128, lane_pad(max_pairs)))
    tab = pack_emitter_tables(offs, counts, starts, n=n, m=m,
                              min_len=stream_window(bl))
    return _emit_slots(tab, pad_perm(perm_s), pad_perm(perm_u),
                       jnp.int32(0), n=n, nslots=max_pairs, block=bl,
                       mode=mode, interpret=interpret, limit=max_pairs)


@functools.partial(jax.jit,
                   static_argnames=("n", "m", "max_pairs", "block",
                                    "interpret"))
def twopass_emit(offs, counts, starts, perm_s, perm_u, *, n: int, m: int,
                 max_pairs: int, block: int = DEF_BLOCK,
                 interpret: bool = False):
    """Pass-2 pair write, tables VMEM-resident: (max_pairs, 2) int32.

    ``offs`` is the (n+m+1,) saturated exclusive scan from pass 1,
    ``counts``/``starts`` the (n+m,) per-emitter tables, ``perm_s``/
    ``perm_u`` the lo-sort permutations.  Output slot order is identical
    to the XLA pass 2 in ``core.sbm._twopass_emit``, −1 padded.
    ``max_pairs == 0`` short-circuits to an empty (0, 2) buffer (a
    zero-size grid is not a legal ``pallas_call``), matching the
    engine's empty-set guarantees.
    """
    return _dense_emit(offs, counts, starts, perm_s, perm_u, n=n, m=m,
                       max_pairs=max_pairs, block=block, mode="resident",
                       interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("n", "m", "max_pairs", "block",
                                    "interpret"))
def twopass_emit_streaming(offs, counts, starts, perm_s, perm_u, *,
                           n: int, m: int, max_pairs: int,
                           block: int = DEF_BLOCK,
                           interpret: bool = False):
    """Streaming pass-2 pair write — bit-identical to ``twopass_emit``,
    with the packed table streamed from HBM per tile."""
    return _dense_emit(offs, counts, starts, perm_s, perm_u, n=n, m=m,
                       max_pairs=max_pairs, block=block, mode="streaming",
                       interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("n", "m", "nslots", "block",
                                    "interpret"))
def csr_decode_window(tab, perm_s_pad, perm_u_pad, w0, *, n: int, m: int,
                      nslots: int, block: int = DEF_BLOCK,
                      interpret: bool = False):
    """Decode ``nslots`` pair slots starting at dynamic slot ``w0``.

    ``tab`` is the packed compacted emitter table from
    ``pack_emitter_tables`` (built with ``min_len >=
    stream_window(lane_pad(block))``), ``perm_s_pad`` / ``perm_u_pad``
    the permutations padded by ``pad_perm``.  Returns the (nslots, 2)
    int32 slots ``[w0, w0 + nslots)`` of the dense pass-2 buffer,
    bit-identical to ``core.sbm._twopass_emit`` on every slot below the
    emit capacity (callers trim at the capacity; see
    ``kernels.ops.CSRPairs``).  ``w0`` is a traced operand: decoding a
    different window of the same size never retraces.
    """
    if nslots == 0:
        return _empty_pairs()
    e_pad = tab.shape[1]
    bl = min(lane_pad(block), max(128, lane_pad(nslots)))
    win = stream_window(bl)
    if e_pad < win:
        raise ValueError(
            f"packed table length {e_pad} is narrower than the decode "
            f"window {win}; pack with min_len >= stream_window("
            f"lane_pad(block)) (block={block})")
    return _emit_slots(tab, perm_s_pad, perm_u_pad,
                       jnp.asarray(w0, jnp.int32), n=n, nslots=nslots,
                       block=bl, mode="csr", interpret=interpret)
