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
import re

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


def _cache_off():
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


@pytest.fixture
def no_cache():
    yield from _cache_off()


@pytest.fixture(scope="module")
def module_no_cache():
    yield from _cache_off()


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


@pytest.fixture(scope="module")
def flagship_v5e(one_chip, module_no_cache):
    """The one-chip flagship step compiled for a described v5e, with its
    pane-ring plane size in cells."""
    batch = 4096
    program, _ = _flagship(1, batch)
    args = _step_args(
        program, batch,
        lambda st: jax.tree_util.tree_map(lambda _: one_chip, st),
        one_chip, one_chip,
    )
    compiled = jax.jit(program._step, donate_argnums=0).lower(*args).compile()
    return compiled, program.ring.n_slots * program.local_key_capacity


def test_flagship_step_compiles_for_v5e(flagship_v5e):
    compiled, _ = flagship_v5e
    mem = compiled.memory_analysis()
    # 1M keys of pane-ring state, plus temporaries, well inside 16 GB
    assert mem.argument_size_in_bytes > K * 8
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert total < 8 * 2**30, total


_INSTR = re.compile(r"^\s*(ROOT )?%(\S+) = (\S+) ([\w-]+)\(")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) ")
_CALLS = re.compile(r"calls=%([\w.-]+)")


def _whole_plane_ops(text: str, cells: int):
    """``(scope, opcode, instruction)`` of every instruction of the
    compiled HLO text whose result is one whole flat pane-ring plane of
    ``cells`` cells (parameters, tuple plumbing and bitcasts aside). A
    fusion reads as the opcode of its fused computation's root."""
    from tpustream.obs.compilation import scope_map

    _, scopes = scope_map(text)
    roots, found, comp = {}, [], None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
        m = _INSTR.match(line)
        if not m:
            continue
        if m.group(1):
            roots[comp] = m.group(4)
        if re.match(rf"[a-z]\w*\[{cells}\]", m.group(3)):
            calls = _CALLS.search(line)
            found.append((m.group(2), m.group(4), calls and calls.group(1)))
    out = []
    for name, op, called in found:
        if op == "fusion":
            op = roots.get(called, op)
        if op not in ("parameter", "get-tuple-element", "tuple", "bitcast"):
            out.append((scopes.get(name), op, name))
    return out


def _retarget_plane_ops(ops):
    """Opcodes of the whole-plane results in the ``retarget`` scope."""
    return {op for scope, op, _ in ops if scope == "retarget"}


def test_flagship_retarget_updates_the_ring_in_place(flagship_v5e):
    """The pane ring's retarget writes only the stale rows of the
    donated planes: in its scope no op but ``dynamic-update-slice``
    produces a whole plane, nothing in the step copies a whole plane,
    and no temporary is as large as one plane."""
    compiled, cells = flagship_v5e
    ops = _whole_plane_ops(compiled.as_text(), cells)
    assert _retarget_plane_ops(ops) == {"dynamic-update-slice"}, ops
    assert not [o for o in ops if o[1] == "copy"], ops
    assert compiled.memory_analysis().temp_size_in_bytes < cells * 4


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


@pytest.fixture(scope="module")
def sharded_flagship_v5e(topo, module_no_cache):
    """The flagship step at parallelism 4, compiled over a mesh of the 4
    described chips, with its per-chip pane-ring plane size in cells.
    The program builds its mesh from ``jax.devices()`` (the CPU here),
    so the fixture hands ``make_mesh`` the described devices."""
    from tpustream.parallel.mesh import AXIS
    from tpustream.runtime import sharded

    mesh = jax.sharding.Mesh(np.array(topo.devices[:4]), (AXIS,))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sharded, "make_mesh", lambda n: mesh)
        batch = 4096
        program, _ = _flagship(4, batch)
        # the sharded jit reads only the state's structure: give it
        # shapes, not 1M keys of host arrays
        init = program.init_state
        mp.setattr(program, "init_state", lambda: jax.eval_shape(init))

        args = _step_args(
            program, batch,
            lambda st: jax.tree_util.tree_map(
                lambda spec: NamedSharding(mesh, spec),
                program.state_specs(st),
                is_leaf=lambda x: isinstance(x, P),
            ),
            NamedSharding(mesh, P(AXIS)), NamedSharding(mesh, P()),
        )
        compiled = program.jitted_step().lower(*args).compile()
    return compiled, program.ring.n_slots * program.local_key_capacity


def test_sharded_flagship_step_compiles_over_four_chips(sharded_flagship_v5e):
    """keyBy as an all_to_all over a mesh of the 4 described chips."""
    compiled, _ = sharded_flagship_v5e
    text = compiled.as_text()
    assert "all-to-all" in text
    mem = compiled.memory_analysis()
    # per device: a quarter of the keyed state
    assert mem.argument_size_in_bytes < 2**30


def test_sharded_retarget_updates_each_chips_ring_in_place(
    sharded_flagship_v5e,
):
    """Under ``shard_map`` too, each chip's retarget writes only its
    stale rows. (The jump sweep's loop still copies a chip's planes; it
    runs only in a step the fast path cannot take.)"""
    compiled, cells = sharded_flagship_v5e
    ops = _whole_plane_ops(compiled.as_text(), cells)
    assert _retarget_plane_ops(ops) == {"dynamic-update-slice"}, ops
