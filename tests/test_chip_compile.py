"""Compile the main path's kernels at real widths for a described TPU v5e.

Nothing runs: the TPU compiler (installed with jax) compiles for a chip
that is described, not attached, and refuses what the chip would refuse —
block shapes off the (8, 128) tiling, more VMEM than a kernel may use, a
program that does not fit the device. Shapes are the serve_1m deployment
(configs/hmgi.py): 1,048,576 × 384 int8 rows, K = 64, n_probe = 8,
top_k = 10, delta capacity 4096, query batch 256, query block 64.

The topology is described inside a module fixture (never at import: only
one process may load the TPU library at a time), and the tests skip where
it cannot be described. The kernels' interpret-mode switch is steered here,
since off-TPU the program would otherwise interpret them.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.common.shapes import pad_to_chunk
from repro.core import delta as delta_mod
from repro.core import ivf as ivf_mod
from repro.kernels.ivf_topk import ops as ivf_ops
from repro.kernels.ivf_topk.ivf_topk import scan_topk_pallas_batched

N, D, K, N_PROBE, TOP_K = 1 << 20, 384, 64, 8, 10
CAP = 2 * N // K + 1                       # ivf.build's default capacity
DELTA_CAP, BATCH, QUERY_BLOCK = 4096, 256, 64
HBM_BYTES = 16 * 10**9                     # one v5e chip


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled_for_tpu(topo):
    """Kernels compiled (not interpreted), no persistent compile cache, and
    fresh trace caches on both sides so no CPU-traced program leaks in or
    out of these tests."""
    from jax.experimental.compilation_cache import compilation_cache
    mp = pytest.MonkeyPatch()
    mp.setattr(ivf_ops, "_interpret_mode", lambda: False)
    mp.setattr(ivf_mod, "_interpret_mode", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    yield
    jax.clear_caches()
    mp.undo()
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *args, **kw):
    return jax.jit(fn).lower(*args, **kw).compile()


def _fits(compiled):
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    return used < HBM_BYTES, used


def _index_shapes(sharding):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    return ivf_mod.IVFIndex(
        s((K, D), jnp.float32), s((K, CAP, D), jnp.int8),
        s((K, CAP), jnp.float32), s((K, CAP), jnp.float32),
        s((K, CAP), jnp.int32), s((K,), jnp.int32), 8)


def test_probe_kernel_serve_1m(one_chip, compiled_for_tpu):
    """One query block's probe scan: 64 queries × n_probe·cap rows, padded
    to the TPU row block as ivf.search pads it."""
    block_n = ivf_mod._probe_block_n(N_PROBE * CAP)
    m = pad_to_chunk(N_PROBE * CAP, block_n)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    c = _compile(functools.partial(scan_topk_pallas_batched, chunk=16,
                                   block_n=block_n),
                 s((QUERY_BLOCK, D), jnp.float32),
                 s((QUERY_BLOCK, m, D), jnp.int8),
                 s((QUERY_BLOCK, m), jnp.float32),
                 s((QUERY_BLOCK, m), jnp.float32),
                 s((QUERY_BLOCK, m), jnp.float32))
    assert "tpu_custom_call" in c.as_text()
    ok, used = _fits(c)
    assert ok, used


@pytest.mark.parametrize("q", [64, BATCH])
def test_delta_scan_kernel(one_chip, compiled_for_tpu, q):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    store = delta_mod.DeltaStore(
        s((DELTA_CAP, D), jnp.float32), s((DELTA_CAP, D), jnp.int8),
        s((DELTA_CAP,), jnp.float32), s((DELTA_CAP,), jnp.float32),
        s((DELTA_CAP,), jnp.int32), s((DELTA_CAP,), jnp.int32),
        s((DELTA_CAP,), jnp.bool_), s((), jnp.int32), s((), jnp.int32),
        s((N,), jnp.bool_), s((N,), jnp.bool_))
    c = _compile(functools.partial(delta_mod._scan_delta, k=TOP_K),
                 store, s((q, D), jnp.float32))
    assert "tpu_custom_call" in c.as_text()
    assert _fits(c)[0]


def test_ivf_search_step(one_chip, compiled_for_tpu):
    """The jitted stable scan at the serving batch, query block 64."""
    q = jax.ShapeDtypeStruct((BATCH, D), jnp.float32, sharding=one_chip)
    c = _compile(functools.partial(ivf_mod.search, n_probe=N_PROBE, k=TOP_K,
                                   query_block=QUERY_BLOCK),
                 _index_shapes(one_chip), q)
    assert "tpu_custom_call" in c.as_text()
    ok, used = _fits(c)
    assert ok, used


def test_sharded_search_step(topo, compiled_for_tpu):
    """The row-sharded stable scan over a 4-chip ("data",) mesh: Mosaic
    kernel per shard, then the cross-shard all-gather merge."""
    mesh = Mesh(np.array(topo.devices), ("data",))
    sh = NamedSharding(mesh, P("data"))
    n_sh = len(topo.devices)
    shapes = jax.eval_shape(functools.partial(ivf_mod.shard_index,
                                              n_shards=n_sh),
                            _index_shapes(None))
    placed = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), shapes)
    q = jax.ShapeDtypeStruct((BATCH, D), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    c = _compile(functools.partial(ivf_mod.search_sharded, mesh=mesh,
                                   n_probe=N_PROBE, k=TOP_K), placed, q)
    txt = c.as_text()
    assert "tpu_custom_call" in txt and "all-gather" in txt
    assert _fits(c)[0]
