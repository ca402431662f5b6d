"""Interval Tree Matching (ITM) — paper §3, pointer-free TPU adaptation.

The paper's interval tree is an augmented AVL (CLRS 14.3): each node keeps
its interval plus subtree ``minlower``/``maxupper`` bounds; queries prune
subtrees whose bounds cannot overlap the query.  Build once, then all m
queries run in parallel (paper Alg. 5 line 10: ``for all u in parallel``).

TPU adaptation (DESIGN.md §2): pointers and rotations are hostile to
SIMD/MXU hardware, and the tree is *static* after construction (the paper
itself never mutates it during matching).  So we store a perfectly
balanced BST over the lo-sorted intervals in **implicit Eytzinger layout**
(node k has children 2k/2k+1) in five flat arrays, padded to a full tree
with ±inf sentinels.  The in-order position of node k in a complete tree
of height h is closed-form::

    inorder(k) = (2*(k - 2^d) + 1) * 2^(h-1-d) - 1,   d = floor(lg k)

so construction is a sort + a gather + h bottom-up max/min levels — fully
jittable, O(n lg n) like the paper's.  Queries are the standard pruned DFS
with an explicit fixed-size stack (≤ h+2 entries) inside a
``lax.while_loop``, ``vmap``-ed over all queries: the paper's
embarrassingly-parallel query loop becomes VPU-lane parallelism.  The
divergence cost of vmapped tree walks (all lanes step until the slowest
finishes) is exactly the irregularity the paper predicts for SIMD targets
in §6 — quantified in our benchmarks.

Dynamic regions (paper §3 "dynamic interval management") are handled in
``core.dynamic`` by re-querying the already-built tree of the *other* set,
which the paper shows is the dominant cost; structural insert/delete is
replaced by periodic rebuild (sort + gather), the array-native equivalent.
"""
from __future__ import annotations

from typing import NamedTuple
from functools import partial

import jax
import jax.numpy as jnp

from .hostread import host_sum
from .regions import Regions

Array = jax.Array


class ITree(NamedTuple):
    """Implicit interval tree.  Arrays are 1-indexed, size M+1 = 2^h."""

    lo: Array        # node interval lower bound
    hi: Array        # node interval upper bound
    minlower: Array  # subtree min lo
    maxupper: Array  # subtree max hi
    ids: Array       # original region index (−1 for sentinel)

    @property
    def height(self) -> int:
        return int(self.lo.shape[0]).bit_length() - 1  # M+1 = 2^h


@partial(jax.jit, static_argnames=("n",))
def _build(lo_1d: Array, hi_1d: Array, n: int) -> ITree:
    h = max((n).bit_length(), 1)
    if (1 << h) - 1 < n:
        h += 1
    M = (1 << h) - 1
    order = jnp.argsort(lo_1d)
    pad = M - n
    slo = jnp.concatenate([lo_1d[order],
                           jnp.full((pad,), jnp.inf, lo_1d.dtype)])
    shi = jnp.concatenate([hi_1d[order],
                           jnp.full((pad,), -jnp.inf, hi_1d.dtype)])
    sid = jnp.concatenate([order.astype(jnp.int32),
                           jnp.full((pad,), -1, jnp.int32)])

    k = jnp.arange(1, M + 1, dtype=jnp.int32)
    d = jnp.floor(jnp.log2(k.astype(jnp.float32))).astype(jnp.int32)
    # guard against float log2 edge error at exact powers of two
    d = jnp.where((1 << (d + 1)) <= k, d + 1, d)
    d = jnp.where((1 << d) > k, d - 1, d)
    j = k - (1 << d)
    inorder = (2 * j + 1) * (1 << (h - 1 - d)) - 1

    one = jnp.full((1,), 0, jnp.int32)
    tree_lo = jnp.concatenate([jnp.full((1,), jnp.inf, slo.dtype),
                               slo[inorder]])
    tree_hi = jnp.concatenate([jnp.full((1,), -jnp.inf, shi.dtype),
                               shi[inorder]])
    tree_id = jnp.concatenate([one - 1, sid[inorder]])

    maxupper = tree_hi
    minlower = tree_lo
    for lvl in range(h - 2, -1, -1):
        lo_idx, hi_idx = 1 << lvl, 1 << (lvl + 1)
        kk = jnp.arange(lo_idx, hi_idx)
        mu = jnp.maximum(maxupper[kk],
                         jnp.maximum(maxupper[2 * kk], maxupper[2 * kk + 1]))
        ml = jnp.minimum(minlower[kk],
                         jnp.minimum(minlower[2 * kk], minlower[2 * kk + 1]))
        maxupper = maxupper.at[kk].set(mu)
        minlower = minlower.at[kk].set(ml)
    return ITree(tree_lo, tree_hi, minlower, maxupper, tree_id)


def build_tree(R: Regions, dim: int = 0) -> ITree:
    lo, hi = R.dim(dim)
    return _build(lo, hi, R.n)


# ---------------------------------------------------------------------------
# queries
# ---------------------------------------------------------------------------

def _query_count_one(tree: ITree, q_lo, q_hi) -> Array:
    """Number of tree intervals overlapping [q_lo, q_hi). Scalar int32."""
    M = tree.lo.shape[0] - 1
    h = (M + 1).bit_length() - 1
    stack = jnp.zeros((h + 2,), jnp.int32).at[0].set(1)

    def cond(st):
        _, sp, _ = st
        return sp > 0

    def body(st):
        stack, sp, cnt = st
        k = stack[sp - 1]
        sp = sp - 1
        prune = (tree.maxupper[k] <= q_lo) | (tree.minlower[k] >= q_hi)
        hit = (~prune) & (tree.lo[k] < q_hi) & (q_lo < tree.hi[k]) & \
            (tree.ids[k] >= 0)
        cnt = cnt + hit.astype(jnp.int32)
        has_kids = (2 * k) <= M
        push_l = (~prune) & has_kids
        # right subtree holds lo >= node.lo: skip it if q_hi <= node.lo
        push_r = (~prune) & has_kids & (q_hi > tree.lo[k])
        stack = stack.at[sp].set(jnp.where(push_l, 2 * k, stack[sp]))
        sp = sp + push_l.astype(jnp.int32)
        stack = stack.at[sp].set(jnp.where(push_r, 2 * k + 1, stack[sp]))
        sp = sp + push_r.astype(jnp.int32)
        return stack, sp, cnt

    _, _, cnt = jax.lax.while_loop(
        cond, body, (stack, jnp.int32(1), jnp.int32(0)))
    return cnt


@jax.jit
def itm_query_counts(tree: ITree, q_lo: Array, q_hi: Array) -> Array:
    """Per-query overlap counts — paper Alg. 5 with counting Report()."""
    return jax.vmap(lambda a, b: _query_count_one(tree, a, b))(q_lo, q_hi)


def _query_pairs_one(tree: ITree, q_lo, q_hi, cap: int):
    M = tree.lo.shape[0] - 1
    h = (M + 1).bit_length() - 1
    stack = jnp.zeros((h + 2,), jnp.int32).at[0].set(1)
    buf = jnp.full((cap,), -1, jnp.int32)

    def cond(st):
        _, sp, _, _ = st
        return sp > 0

    def body(st):
        stack, sp, cnt, buf = st
        k = stack[sp - 1]
        sp = sp - 1
        prune = (tree.maxupper[k] <= q_lo) | (tree.minlower[k] >= q_hi)
        hit = (~prune) & (tree.lo[k] < q_hi) & (q_lo < tree.hi[k]) & \
            (tree.ids[k] >= 0)
        buf = jax.lax.cond(
            hit & (cnt < cap),
            lambda b: b.at[cnt].set(tree.ids[k]),
            lambda b: b, buf)
        cnt = cnt + hit.astype(jnp.int32)
        has_kids = (2 * k) <= M
        push_l = (~prune) & has_kids
        push_r = (~prune) & has_kids & (q_hi > tree.lo[k])
        stack = stack.at[sp].set(jnp.where(push_l, 2 * k, stack[sp]))
        sp = sp + push_l.astype(jnp.int32)
        stack = stack.at[sp].set(jnp.where(push_r, 2 * k + 1, stack[sp]))
        sp = sp + push_r.astype(jnp.int32)
        return stack, sp, cnt, buf

    _, _, cnt, buf = jax.lax.while_loop(
        cond, body, (stack, jnp.int32(1), jnp.int32(0), buf))
    return buf, cnt


@partial(jax.jit, static_argnames=("cap",))
def itm_query_pairs(tree: ITree, q_lo: Array, q_hi: Array, cap: int):
    """Per-query matched region ids, −1 padded, capacity ``cap``."""
    return jax.vmap(lambda a, b: _query_pairs_one(tree, a, b, cap))(
        q_lo, q_hi)


@partial(jax.jit, static_argnames=("cap",))
def itm_query_pairs_dd(tree: ITree, o_lo: Array, o_hi: Array,
                       q_lo: Array, q_hi: Array, cap: int):
    """Batched d-dim overlap query: dim-0 tree walk, then verify dims 1+.

    ``tree`` indexes dim 0 of the regions whose full coords are
    ``o_lo``/``o_hi`` (n, d); ``q_lo``/``q_hi`` are (b, d) query boxes.
    Returns ``(ids, counts)``: (b, cap) region ids overlapping each query
    on *all* dimensions (−1 padded, order-unspecified) and (b,) verified
    counts.  ``cap`` must cover the dim-0 candidate count per query
    (size it from ``itm_query_counts`` on dim 0).
    """
    ids, _ = jax.vmap(
        lambda a, b: _query_pairs_one(tree, a, b, cap))(q_lo[:, 0],
                                                        q_hi[:, 0])
    valid = ids >= 0
    ic = jnp.maximum(ids, 0)
    ok = jnp.all(
        jnp.logical_and(o_lo[ic, 1:] < q_hi[:, None, 1:],
                        q_lo[:, None, 1:] < o_hi[ic, 1:]), axis=-1)
    ok = ok & valid
    return jnp.where(ok, ids, -1), jnp.sum(ok, axis=-1, dtype=jnp.int32)


def itm_count(S: Regions, U: Regions, swap: str = "auto") -> int:
    """Total K: build tree on one set, query the other (paper Alg. 5).

    ``swap='auto'`` builds the tree on the smaller set (paper §3's
    m ≪ n optimization).
    """
    assert S.d == 1
    build_on_S = S.n <= U.n if swap == "auto" else (swap == "S")
    T = build_tree(S if build_on_S else U)
    Q = U if build_on_S else S
    return host_sum(itm_query_counts(T, Q.lo[:, 0], Q.hi[:, 0]))
