"""Compile the main path for a described TPU v5e, without a chip.

The streaming Pallas kernels run compiled only on a TPU; everywhere else
they run in interpret mode (or fall through to ``ref.py``), which
accepts layouts and primitives that Mosaic refuses.  These tests lower
and compile, for a ``v5e:2x2`` topology described in a fixture:

* the four streaming kernels at the widths ``chip_smoke.py`` runs them,
  asserting the compiled text holds a Mosaic ``tpu_custom_call``;
* ``JaxPlane._window_fn`` (range and keyword) at G=64, P=1024, M=8, W=8;
* ``ShardedJaxPlane._sharded_window`` on a mesh of the 4 described
  devices, asserting the owner-keyed all-to-all is in the program.

Nothing runs; a compile that passes is not a chip run.  The topology is
described only inside the fixture (never at import), and these tests
compile in their own process — only one process at a time may load the
TPU library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.streaming.fused import DeviceState
from repro.streaming.planes import JaxPlane
from repro.streaming.sharded import ShardedJaxPlane, ShardedState

G, P, M, W, T1 = 64, 1024, 8, 8, 33
BATCH = 1 << 17
V5E_HBM_BYTES = 16 * 10**9    # one v5e chip (Google Cloud, "TPU v5e")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_cases():
    from repro.kernels.keyword_match import keyword_match
    from repro.kernels.knn_match import knn_match
    from repro.kernels.spatial_match import spatial_match
    from repro.kernels.stats_update import close_round
    return {
        "stats_update": (close_round, [(8, 1024, 1001)]),
        "spatial_match": (spatial_match, [(BATCH, 2), (2048, 4)]),
        "keyword_match": (keyword_match, [(16384, 2), (16384, 32),
                                          (2048, 4), (2048, 32)]),
        "knn_match": (functools.partial(knn_match, k=8),
                      [(BATCH, 2), (2048, 2)]),
    }


@pytest.mark.parametrize("name", ["stats_update", "spatial_match",
                                  "keyword_match", "knn_match"])
def test_streaming_kernel_compiles_for_v5e(name, one_chip,
                                           no_persistent_cache):
    fn, shapes = _kernel_cases()[name]
    text = _compile_text(fn, *(_spec(one_chip, s) for s in shapes))
    assert "tpu_custom_call" in text


def _window_args(sharding, keyword: bool):
    f32, i32 = jnp.float32, jnp.int32
    s = functools.partial(_spec, sharding)
    state = DeviceState(s((G, G), i32), s((P,), i32), s((P,)), s((P,)),
                        s((M,)), s((P, G + 1)), s((P, G + 1)),
                        s((P, T1)) if keyword else None)
    carry = (s((M,)), s((M,)), s((), f32))
    hists = s((W, G * G))
    kwh = s((W, G * G * T1)) if keyword else None
    sc = tuple(s((), f32) for _ in range(8))
    ep = tuple(s((), f32) for _ in range(5)) + (s((), i32),)
    return state, carry, hists, kwh, sc, ep, s((M,))


@pytest.mark.parametrize("keyword", [False, True], ids=["range", "keyword"])
def test_fused_window_compiles_for_v5e(keyword, one_chip,
                                       no_persistent_cache):
    plane = JaxPlane()
    fn = functools.partial(plane._window_fn, track_stats=True,
                           tuple_driven=True, keyword=keyword, batch=BATCH,
                           p_used=P)
    compiled = jax.jit(fn).lower(*_window_args(one_chip, keyword)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES


def test_sharded_window_compiles_for_4_chips(topo, no_persistent_cache):
    d = len(topo.devices)
    assert d == 4
    plane = ShardedJaxPlane(1)
    # steer the plane onto the described mesh (the program builds its
    # mesh from jax.devices(), which here are the CPU's)
    plane._mesh = Mesh(np.asarray(topo.devices), ("machines",))
    plane._d = d
    shard = NamedSharding(plane._mesh, PartitionSpec("machines"))
    repl = NamedSharding(plane._mesh, PartitionSpec())
    r = functools.partial(_spec, repl)
    sh = functools.partial(_spec, shard)
    slots = P // d
    f32, i32 = jnp.float32, jnp.int32
    state = ShardedState(r((G, G), i32), r((P,), i32), r((P,)), r((P,)),
                         r((M,)), sh((d, slots, G + 1)),
                         sh((d, slots, G + 1)), None,
                         sh((d, slots), i32), r((P,), i32), r((M,), i32))
    carry = (r((M,)), r((M,)), r((), f32))
    sc = tuple(r((), f32) for _ in range(8))
    ep = tuple(r((), f32) for _ in range(5)) + (r((), i32),)
    fn = functools.partial(plane._sharded_window, track_stats=True,
                           tuple_driven=True, keyword=False, batch=BATCH)
    text = _compile_text(fn, state, carry, sh((d, W, G * G)), None, sc, ep,
                         r((M,)))
    assert "all-to-all" in text
