"""Pallas experiment: sequential in-VMEM keyed reduce (VERDICT r2 next #5).

The rolling fast path's measured floor on v5e is the sort + segmented
scan + plane gather/scatter pipeline (docs/architecture.md cost model):
~7.6 ms/step at B=131072, K=1M. But a rolling aggregate's PER-KEY state
at 1M keys is only 4 MB per 32-bit plane — it FITS VMEM. That admits a
radically different kernel: keep the whole keyed plane resident in VMEM
and process the batch with a sequential record-at-a-time loop — the
exact semantics Flink's runtime implements, with no sort, no segmented
scan, no HBM gathers and no scatters at all. Per record: one dynamic
VMEM read, one combine, one dynamic VMEM write, one emission store.

Whether this wins is purely a question of how fast Mosaic lowers
dynamic single-element VMEM access (the TPU is a tiled vector machine;
a scalar random access may cost a full (8,128)-tile operation). This
module exists to MEASURE that: `measure()` times the kernel against the
XLA primitives it would replace, and the integration decision is
recorded in docs/architecture.md. Run `python -m
tpustream.ops.pallas_rolling` on the target chip.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128


def _supported() -> bool:
    try:
        from jax.experimental import pallas as pl  # noqa: F401
        from jax.experimental.pallas import tpu as pltpu  # noqa: F401
    except Exception:  # pragma: no cover
        return False
    return True


@functools.partial(jax.jit, static_argnames=("op", "interpret"))
def seq_rolling_reduce(
    plane: jnp.ndarray,   # [K//LANES, LANES] f32 keyed state (identity-init)
    keys: jnp.ndarray,    # [B//LANES, LANES] int32 key ids
    vals: jnp.ndarray,    # [B//LANES, LANES] f32 values
    op: str = "max",
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Record-at-a-time keyed reduce with the state resident in VMEM.

    Returns (new_plane, emissions) where emissions[i] is the running
    aggregate of key[i] AFTER record i folds in — exactly the rolling
    emission contract (reference chapter2/README.md:52-66), in arrival
    order, no sort, no un-permute.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    reducer = {"max": jnp.maximum, "min": jnp.minimum,
               "sum": lambda a, b: a + b}[op]
    b_rows, _ = keys.shape

    def kernel(keys_ref, vals_ref, plane_ref, out_plane_ref, emis_ref):
        # plane is aliased in/out; copy-through once for safety when the
        # compiler did not alias (interpret mode)
        out_plane_ref[:] = plane_ref[:]
        lanes = jnp.int32(LANES)
        lane_iota = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

        from jax.experimental import pallas as pl

        # Mosaic constraint: the LANE dimension only takes static (or
        # 128-aligned) indices, so per-record updates are row-granular:
        # read the key's 128-lane plane row, merge the one lane with a
        # one-hot select, write the row back. The lane loop is a python
        # range -> static lane indices for the batch side; the plane row
        # index stays dynamic (sublane dim allows that).
        def row_body(r, carry):
            krow = keys_ref[pl.ds(r, 1), :]
            vrow = vals_ref[pl.ds(r, 1), :]
            emis_row = jnp.zeros((1, LANES), dtype=vals_ref.dtype)
            for c in range(LANES):
                k = krow[0, c]
                v = vrow[0, c]
                kr, kc = k // lanes, k % lanes
                prow = out_plane_ref[pl.ds(kr, 1), :]
                hot = lane_iota == kc
                cur = jnp.sum(jnp.where(hot, prow, 0).astype(jnp.float32))
                new = reducer(cur, v)
                out_plane_ref[pl.ds(kr, 1), :] = jnp.where(hot, new, prow)
                emis_row = jnp.where(lane_iota == c, new, emis_row)
            emis_ref[pl.ds(r, 1), :] = emis_row
            return carry

        # int32 bounds: pallas TPU has no 64-bit scalars (and the repo
        # runs with jax_enable_x64)
        jax.lax.fori_loop(
            jnp.int32(0), jnp.int32(b_rows), row_body, jnp.int32(0)
        )

    return pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct(plane.shape, plane.dtype),
            jax.ShapeDtypeStruct(vals.shape, vals.dtype),
        ),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(keys, vals, plane)


def oracle(plane: np.ndarray, keys: np.ndarray, vals: np.ndarray, op: str):
    """Record-at-a-time numpy reference."""
    red = {"max": max, "min": min, "sum": lambda a, b: a + b}[op]
    p = plane.reshape(-1).copy()
    k = keys.reshape(-1)
    v = vals.reshape(-1)
    emis = np.empty_like(v)
    for i in range(k.size):
        p[k[i]] = red(p[k[i]], v[i])
        emis[i] = p[k[i]]
    return p.reshape(plane.shape), emis.reshape(vals.shape)


def measure(B: int = 1 << 17, K: int = 1 << 20, iters: int = 20):
    """Time the Pallas kernel vs the XLA ops it would replace. Both
    variants chain ``iters`` steps inside ONE jitted ``lax.scan`` with a
    data dependency through the state, then fetch a scalar, so the
    host round trip of a per-call timing does not hide the kernel."""
    import time

    rng = np.random.default_rng(0)
    keys = jnp.asarray(
        rng.integers(0, K, B, dtype=np.int32).reshape(B // LANES, LANES)
    )
    vals = jnp.asarray(
        rng.random(B, dtype=np.float32).reshape(B // LANES, LANES)
    )
    plane0 = jnp.full((K // LANES, LANES), -jnp.inf, dtype=jnp.float32)

    # --- pallas sequential kernel ---------------------------------------
    @functools.partial(jax.jit, donate_argnums=0)
    def chunk_pallas(plane):
        def body(p, _):
            p2, emis = seq_rolling_reduce(p, keys, vals, op="max")
            return p2, emis[0, 0]
        return jax.lax.scan(body, plane, None, length=iters)

    p, es = chunk_pallas(plane0)
    _ = np.asarray(es[-1])  # compile + first chunk
    t0 = time.perf_counter()
    p, es = chunk_pallas(p)
    _ = np.asarray(es[-1]) + np.asarray(p[0, 0])
    dt_pallas = (time.perf_counter() - t0) / iters

    # --- XLA baseline: the ops the kernel replaces ----------------------
    from .segments import (
        inverse_permutation,
        segment_tails,
        segmented_scan,
        sort_by_key,
    )

    def xla_step(plane, keys_flat, vals_flat):
        perm, sk, sv, seg_starts = sort_by_key(
            keys_flat, jnp.ones_like(keys_flat, bool), max_key=K
        )
        sorted_vals = vals_flat[perm]
        (prefix,) = segmented_scan(
            (sorted_vals,), seg_starts, lambda a, b: (jnp.maximum(a[0], b[0]),)
        )
        safe = jnp.where(sv, sk, 0).astype(jnp.int32)
        stored = plane.reshape(-1)[safe]
        emis = jnp.maximum(stored, prefix)
        tails = segment_tails(seg_starts) & sv
        idx = jnp.where(tails, sk, K).astype(jnp.int32)
        new_plane = (
            plane.reshape(-1)
            .at[idx]
            .set(emis, mode="drop", unique_indices=True)
            .reshape(plane.shape)
        )
        inv = inverse_permutation(perm)
        return new_plane, emis, inv

    kf = keys.reshape(-1)
    vf = vals.reshape(-1)

    @functools.partial(jax.jit, donate_argnums=0)
    def chunk_xla(plane):
        def body(p, _):
            p2, emis, inv = xla_step(p, kf, vf)
            return p2, emis[0] + inv[0]
        return jax.lax.scan(body, plane, None, length=iters)

    # fresh plane: plane0 was DONATED to the pallas chunk above
    p2, es2 = chunk_xla(
        jnp.full((K // LANES, LANES), -jnp.inf, dtype=jnp.float32)
    )
    _ = np.asarray(es2[-1])
    t0 = time.perf_counter()
    p2, es2 = chunk_xla(p2)
    _ = np.asarray(es2[-1]) + np.asarray(p2[0, 0])
    dt_xla = (time.perf_counter() - t0) / iters

    return {
        "B": B,
        "K": K,
        "pallas_ms": dt_pallas * 1e3,
        "pallas_ev_per_s": B / dt_pallas,
        "xla_sortscan_ms": dt_xla * 1e3,
        "xla_ev_per_s": B / dt_xla,
    }


if __name__ == "__main__":
    print(measure())
