"""GPipe-style pipeline parallelism over the 'pod' axis (optional
schedule; DESIGN.md §5).

The layer stack is split into ``n_stages`` contiguous stage groups; each
stage lives on one slice of the pipeline axis.  Microbatches stream
through under ``shard_map``: every clock tick each stage applies its
layers to its current microbatch and passes activations to the next
stage with ``ppermute`` (the classic bubble schedule: ``M + S − 1``
ticks for M microbatches, S stages; bubble fraction (S−1)/(M+S−1)).

This is the *inference/forward* pipeline used to validate the schedule
and its collectives against the single-device stack (bit-comparable in
fp32); the training default remains DP-across-pods with compressed
gradient all-reduce, which EXPERIMENTS §Perf shows is collective-cheaper
at our shapes than a 2-stage pipeline for these models.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

AXIS = "stage"


def pipeline_forward(stacked_params, x, layer_apply, *, mesh: Mesh,
                     n_microbatches: int):
    """Run x through L stacked layers split across the 'stage' axis.

    stacked_params: pytree with leading layer axis L (L % n_stages == 0).
    x: (B, ...) activations, B % n_microbatches == 0.
    layer_apply(p_layer, x_mb) -> x_mb.
    """
    n_stages = mesh.devices.size
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    assert L % n_stages == 0, (L, n_stages)
    B = x.shape[0]
    assert B % n_microbatches == 0
    mb = B // n_microbatches

    # reshape params to (stages, layers_per_stage, ...) and microbatches
    per = L // n_stages
    sp = jax.tree.map(
        lambda a: a.reshape((n_stages, per) + a.shape[1:]), stacked_params)
    xmb = x.reshape((n_microbatches, mb) + x.shape[1:])

    def stage_body(params_stage, xs):
        """One device: params for its `per` layers; xs: all microbatches
        (streamed: device 0 feeds them in)."""
        me = jax.lax.axis_index(AXIS)
        params_stage = jax.tree.map(lambda a: a[0], params_stage)

        def apply_stage(xin):
            def body(c, pl):
                return layer_apply(pl, c), None
            out, _ = jax.lax.scan(body, xin, params_stage)
            return out

        ticks = n_microbatches + n_stages - 1
        # carries must be stage-varying for the shard_map type system
        buf = jax.lax.pcast(jnp.zeros_like(xs[0]), AXIS, to="varying")
        outs = jax.lax.pcast(jnp.zeros_like(xs), AXIS, to="varying")

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any); others use received
            feed = jnp.where(t < n_microbatches,
                             xs[jnp.minimum(t, n_microbatches - 1)],
                             jnp.zeros_like(buf))
            cur = jnp.where(me == 0, feed, buf)  # feed varies via buf
            y = apply_stage(cur)
            # pass to next stage
            nxt = jax.lax.ppermute(
                y, AXIS, [(i, (i + 1) % n_stages) for i in
                          range(n_stages)])
            # last stage emits microbatch (t - (n_stages - 1))
            emit_idx = t - (n_stages - 1)
            emit = (me == n_stages - 1) & (emit_idx >= 0)
            idxc = jnp.clip(emit_idx, 0, n_microbatches - 1)
            outs = outs.at[idxc].set(jnp.where(emit, y, outs[idxc]))
            return (nxt, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs),
                                    jnp.arange(ticks))
        # broadcast final outputs from the last stage to all (mask+psum)
        outs = jnp.where(me == n_stages - 1, outs, jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, AXIS)
        return outs[None]

    f = jax.shard_map(
        stage_body, mesh=mesh,
        in_specs=(P(AXIS), P()),
        out_specs=P(AXIS),
    )
    outs = f(sp, xmb)            # (n_stages, nmb, mb, ...) replicated rows
    return outs[0].reshape((B,) + x.shape[1:])
