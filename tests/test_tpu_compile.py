"""Compile rehearsals for a described TPU v5e: the main-path Pallas kernels
at the chip smoke's widths, and the jitted edge-update step with the
Pallas path forced. Nothing runs; the chip's compiler must accept each
program. The topology is described inside a fixture (never at import), so
every xdist worker collects the same tests and only the worker that runs
this file loads the TPU compiler."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import edgepool as ep
from repro.core import radixgraph as rg
from repro.kernels import ops as kops
from repro.kernels.append import append_pallas, append_tile_rows
from repro.kernels.compact import compact_rows_pallas, defrag_rows_pallas

# the chip smoke's widths at its 2^20-vertex target scale (its default run
# is cut to 2^18, a 4x smaller pool): 2^23 blocks x 16 lanes, 4096-op
# batches, compaction tiers of 1024 x 256 and 16 x 4096, reads of 4096 x
# 4096, and the streaming rebuild's size segments (widths 16 .. 4096)
POOL_BLOCKS, BS, B = 1 << 23, 16, 4096


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    one = SingleDeviceSharding(topo.devices[0])
    return lambda sh, dt: jax.ShapeDtypeStruct(sh, dt, sharding=one)


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer the kernel dispatch as it resolves on a TPU backend."""
    monkeypatch.setattr(kops, "platform", lambda: "tpu")


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_append_compiles_for_tpu(shape):
    i32, f32 = jnp.int32, jnp.float32
    n_tiles = POOL_BLOCKS // append_tile_rows(POOL_BLOCKS)
    pool = [shape((POOL_BLOCKS, BS), t) for t in (i32, f32, i32)]
    ops_ = [shape((B,), t) for t in (i32, i32, jnp.bool_, i32, f32, i32,
                                     i32, i32, i32)]
    c = _compile(lambda *a: append_pallas(*a, interpret=False), *pool,
                 *ops_, shape((n_tiles,), i32), shape((), i32))
    assert c.memory_analysis() is not None


@pytest.mark.parametrize("K,D", [(1024, 256), (16, 4096), (4096, 4096)])
def test_compact_rows_compiles_for_tpu(shape, K, D):
    c = _compile(lambda d, w, t, s: compact_rows_pallas(d, w, t, s,
                                                        interpret=False),
                 shape((K, D), jnp.int32), shape((K, D), jnp.float32),
                 shape((K, D), jnp.int32), shape((K,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("K,D", [(4096, 16), (512, 128), (64, 1024),
                                 (32, 4096)])
def test_defrag_rows_compiles_for_tpu(shape, K, D):
    c = _compile(lambda d, w, t, s: defrag_rows_pallas(d, w, t, s,
                                                       interpret=False),
                 shape((K, D), jnp.int32), shape((K, D), jnp.float32),
                 shape((K, D), jnp.int32), shape((K,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_pool_write_is_in_place_for_tpu(shape):
    """The per-batch slot write into the (2^23, 16) pool compiles to row
    updates in place: an element scatter there relays a whole pool lane
    (512 MiB) out and back on every batch."""
    spec = ep.PoolSpec(n_blocks=POOL_BLOCKS, block_size=BS)
    pool = jax.tree.map(lambda s: shape(s.shape, s.dtype),
                        jax.eval_shape(lambda: ep.make_edge_pool(spec)))
    i32 = jnp.int32
    write = jax.jit(ep._scatter_entries, donate_argnums=(0,))
    c = write.lower(pool, shape((B,), i32), shape((B,), i32),
                    shape((B,), jnp.bool_), shape((B,), i32),
                    shape((B,), jnp.float32), shape((B,), i32)).compile()
    assert c.memory_analysis().temp_size_in_bytes < (POOL_BLOCKS * BS * 4) // 8
    assert " while(" not in c.as_text()


def test_edge_update_step_compiles_for_tpu(shape, on_tpu):
    """The whole jitted edge-update step at benchmark widths (pool 131072 x
    16, B=4096, n_cap=40960) with every Pallas kernel forced: all three
    main-path kernels land in the program, and no dense oracle sneaks in
    (an O(B x pool) temporary would be 32 GiB here)."""
    kw = dict(n_max=40960, expected_n=32768, pool_blocks=131072,
              block_size=BS, batch=B, k_max=256, dmax=4096,
              append_impl="pallas", compact_impl="pallas")
    holder = {}

    def build():
        holder["g"] = g = rg.RadixGraph(**kw)
        return g.state

    state = jax.tree.map(lambda s: shape(s.shape, s.dtype),
                         jax.eval_shape(build))
    g = holder["g"]
    step = jax.jit(rg.step_update_edges, static_argnums=(0, 1),
                   donate_argnums=(2,))
    lowered = step.lower(g.sort_spec, g.pool_spec, state,
                         shape((B, 2), jnp.uint32), shape((B, 2), jnp.uint32),
                         shape((B,), jnp.float32), shape((B,), jnp.bool_))
    text = lowered.as_text()
    for name in ("_append_kernel", "_compact_kernel", "_defrag_kernel"):
        assert f'kernel_name = "{name}"' in text, name
    mem = lowered.compile().memory_analysis()
    assert mem.temp_size_in_bytes < 4 << 30, mem
