"""Deviceless compiles for a described v5e chip.

The TPU compiler is installed here and compiles for a chip that is
described, not attached, so what the chip's compiler would refuse (a
kernel's tiling, its fast memory, a program that does not fit the
device) fails here at no chip time. Nothing runs: these say nothing
about results or speed.

The topology is described inside a fixture only: loading the TPU
library at import time would hold its lock in every test worker.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

K = 1 << 20  # the 1M-key deployment of chip_smoke.py


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_cache():
    """A deviceless compile can be written to the persistent cache but
    not read back without a chip: keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _flagship(parallelism: int, batch: int):
    import __graft_entry__ as ge

    program, cfg = ge._build_flagship(parallelism, batch, K)
    return program, cfg


def _step_args(program, batch: int, state_sharding, rows, scalar):
    """Shapes of ``program._step``'s arguments. ``state_sharding`` maps
    the state's shape tree to a tree of shardings."""
    state = jax.eval_shape(program.init_state)
    state = jax.tree_util.tree_map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        state, state_sharding(state),
    )
    cols = tuple(
        jax.ShapeDtypeStruct((batch,), dt, sharding=rows)
        for dt in (jnp.int64, jnp.int32, jnp.int64)
    )
    valid = jax.ShapeDtypeStruct((batch,), jnp.bool_, sharding=rows)
    ts = jax.ShapeDtypeStruct((batch,), jnp.int64, sharding=rows)
    wm = jax.ShapeDtypeStruct((), jnp.int64, sharding=scalar)
    return state, cols, valid, ts, wm


def test_flagship_step_compiles_for_v5e(one_chip, no_cache):
    batch = 4096
    program, _ = _flagship(1, batch)
    args = _step_args(
        program, batch,
        lambda st: jax.tree_util.tree_map(lambda _: one_chip, st),
        one_chip, one_chip,
    )
    compiled = jax.jit(program._step, donate_argnums=0).lower(*args).compile()
    mem = compiled.memory_analysis()
    # 1M keys of pane-ring state, plus temporaries, well inside 16 GB
    assert mem.argument_size_in_bytes > K * 8
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert total < 8 * 2**30, total


def test_pallas_rolling_kernel_compiles_for_v5e(one_chip, no_cache):
    from tpustream.ops.pallas_rolling import LANES, seq_rolling_reduce

    batch = 1 << 17
    def spec(rows, dtype):
        return jax.ShapeDtypeStruct((rows, LANES), dtype, sharding=one_chip)

    plane = spec(K // LANES, jnp.float32)
    keys = spec(batch // LANES, jnp.int32)
    vals = spec(batch // LANES, jnp.float32)
    compiled = seq_rolling_reduce.lower(plane, keys, vals, op="max").compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_flagship_step_compiles_over_four_chips(
    topo, no_cache, monkeypatch
):
    """keyBy as an all_to_all over a mesh of the 4 described chips. The
    program builds its mesh from ``jax.devices()`` (the CPU here), so the
    test hands ``make_mesh`` the described devices."""
    from tpustream.parallel.mesh import AXIS
    from tpustream.runtime import sharded

    mesh = jax.sharding.Mesh(np.array(topo.devices[:4]), (AXIS,))
    monkeypatch.setattr(sharded, "make_mesh", lambda n: mesh)
    batch = 4096
    program, _ = _flagship(4, batch)
    # the sharded jit reads only the state's structure: give it shapes,
    # not 1M keys of host arrays
    init = program.init_state
    monkeypatch.setattr(program, "init_state", lambda: jax.eval_shape(init))

    args = _step_args(
        program, batch,
        lambda st: jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, spec), program.state_specs(st),
            is_leaf=lambda x: isinstance(x, P),
        ),
        NamedSharding(mesh, P(AXIS)), NamedSharding(mesh, P()),
    )
    compiled = program.jitted_step().lower(*args).compile()
    text = compiled.as_text()
    assert "all-to-all" in text
    mem = compiled.memory_analysis()
    # per device: a quarter of the keyed state
    assert mem.argument_size_in_bytes < 2**30
