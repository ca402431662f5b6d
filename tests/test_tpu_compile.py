"""Every Pallas kernel on the DDM main path compiles for a TPU v5e.

Each test lowers a kernel for one chip of a described (not attached)
``v5e:2x2`` topology and compiles it with the installed TPU compiler —
the Mosaic checks that interpret mode never runs (tiling, gathers,
VMEM limits).  Sizes are the ones the route policy really sends each
kernel: the resident emit just under its VMEM bound, the streaming
emit at n+m = 1e6 and just under its bound, an 8192-slot csr decode at
n+m = 1e7, the sweep over 2e6 endpoints and the brute-force kernels at
4096 x 4096.  The emit kernels are compiled as their bare
``pallas_call`` at those shapes; the XLA-side table prep around them is
ordinary XLA.

The topology is described inside a fixture, so no worker touches the
TPU library while collecting tests.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bfm, emit, ops, sbm_sweep

BLOCK = 2048          # the engine's emit / sweep block (MatchSpec.block)
CAP = 1 << 20         # output slots of the dense emits (512 tiles)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _i32(*shape):
    return (shape, jnp.int32)


@pytest.mark.parametrize("mode,e", [
    ("resident", 465_408),      # the largest n+m the policy keeps resident
    ("streaming", 1_000_000),
    ("streaming", 4_189_440),   # the largest n+m the policy streams
])
def test_dense_emit_compiles(one_chip, mode, e):
    n, m = e // 2, e - e // 2
    assert ops.choose_emit_route(n, m, block=BLOCK) == mode
    nt = CAP // BLOCK
    win = emit.stream_window(BLOCK)

    def call(meta, tab, ps, pu):
        return emit.emit_call(meta, tab, ps, pu, n=n, nt=nt, block=BLOCK,
                              mode=mode)

    _compile(call, _i32(1 + 3 * nt), _i32(8, emit.table_len(e, win)),
             _i32(1, emit.perm_len(n)), _i32(1, emit.perm_len(m)),
             sharding=one_chip)


def test_csr_decode_compiles(one_chip):
    e = 10_000_000
    n, m = e // 2, e - e // 2
    assert ops.choose_emit_route(n, m, block=BLOCK) == "csr"
    nt = 8192 // BLOCK
    win = emit.stream_window(BLOCK)

    def call(meta, tab, ps, pu):
        return emit.emit_call(meta, tab, ps, pu, n=n, nt=nt, block=BLOCK,
                              mode="csr")

    _compile(call, _i32(1 + 3 * nt), _i32(8, emit.table_len(e, win)),
             _i32(1, emit.perm_len(n)), _i32(1, emit.perm_len(m)),
             sharding=one_chip)


def test_emit_packing_compiles_to_sorts(one_chip):
    """The a100 batch cell's emit program: the table compaction (scope
    ``ddm.emit.pack.compact``) compiles to a sort, with no scatter,
    gather or loop; the tile owners (``ddm.emit.pack.meta``) take one
    search loop for all 32,768 tiles, not one per kernel call."""
    n = m = 500_000
    e = n + m
    lowered = emit.twopass_emit_streaming.lower(
        *(jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
          for s in ((e + 1,), (e,), (e,), (n,), (m,))),
        n=n, m=m, max_pairs=1 << 26, block=BLOCK)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    pack = [ln for ln in text.splitlines() if "ddm.emit.pack" in ln]
    compact = [ln for ln in pack if "ddm.emit.pack.compact" in ln]
    assert any(re.search(r"\ssort\(", ln) for ln in compact)
    bad = [ln.split(" = ")[0].strip() for ln in compact
           if re.search(r"\s(scatter|gather|while)\(", ln)]
    assert not bad, bad
    assert not [ln for ln in pack if re.search(r"\sscatter\(", ln)]
    loops = [ln for ln in pack if re.search(r"\swhile\(", ln)]
    assert len(loops) == 1, loops


def test_sbm_sweep_compiles(one_chip):
    tot = 2_000_000 + (-2_000_000) % BLOCK
    _compile(lambda a, b: sbm_sweep.sbm_sweep(a, b, block=BLOCK),
             _i32(tot), _i32(tot), sharding=one_chip)


@pytest.mark.parametrize("kernel", [bfm.bfm_tile_counts, bfm.bfm_mask])
@pytest.mark.parametrize("d", [1, 2])
def test_bfm_compiles(one_chip, kernel, d):
    f32 = ((4096, d), jnp.float32)
    _compile(lambda *a: kernel(*a, ts=256, tu=256), f32, f32, f32, f32,
             sharding=one_chip)
