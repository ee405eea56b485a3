"""Typed accumulator leaves <-> scatter-friendly storage planes.

v5e has no native 64-bit lanes: XLA emulates int64 scatters at ~8k
updates/ms versus ~70k updates/ms for int32 (measured on this hardware),
an 8x cliff on the per-batch state merge. Window state therefore stores
each int64 leaf as TWO int32 "word planes" (lo, hi) so every scatter is
a fast 32-bit one; packing/unpacking are dense elementwise bit ops that
fuse for free, and all arithmetic (user combiners, finalize, the post
chain) runs on the reconstructed full-precision values.

float64 leaves keep a native f64 plane: this TPU's XLA rejects f64
bitcasts outright (x64 rewriter limitation, verified), so a float64
cannot be split into words on the device. Where a job's device work on
a float64 is only ordering or passing it through (the rolling
``max``/``min``/``max_by``/``min_by`` fields and keep-first fields,
``RollingProgram.image_fields``), the host makes its exact int64 image
at pack time (``records.f64_image``) and the leaf is an I64 one here: two
int32 planes on the fast scatter, at any key count (the chapter-2
running max holds 2^20 hosts on one chip). Float64 leaves the device
does arithmetic on (sums, averages, the windowed (count, sum) pair of
chapter2/.../ComputeCpuAvg.java:33-36) keep the emulated f64 plane.
"""

from __future__ import annotations

from typing import List, Sequence, Union

import jax.numpy as jnp
import numpy as np

from ..records import BOOL, F64, I64, STR


def _per_leaf(compact32, kinds) -> List[bool]:
    if isinstance(compact32, (list, tuple)):
        if len(compact32) != len(kinds):
            raise ValueError(
                f"per-leaf compact32 has {len(compact32)} entries for "
                f"{len(kinds)} leaf kinds"
            )
        return list(compact32)
    return [bool(compact32)] * len(kinds)


def plane_dtypes(
    kinds: Sequence[str], compact32: Union[bool, Sequence[bool]] = False
) -> List[np.dtype]:
    """Storage plane dtypes for a leaf-kind list (i64 -> two int32).

    ``compact32`` is the opt-in lossy accumulator mode
    (``StreamConfig.acc_dtype`` int32/float32): 64-bit leaves keep ONE
    32-bit plane (int64 wraps mod 2^32, float64 rounds to f32) so
    commutative combiners can use the non-unique scatter-reduce fast
    path directly on the plane. A per-leaf sequence restricts the mode
    to the leaves a combiner actually aggregates (pass-through record
    fields keep exact storage)."""
    out: List[np.dtype] = []
    for k, c32 in zip(kinds, _per_leaf(compact32, kinds)):
        if k == I64:
            if c32:
                out.append(np.dtype(np.int32))
            else:
                out.extend([np.dtype(np.int32), np.dtype(np.int32)])
        elif k == F64:
            out.append(np.dtype(np.float32) if c32 else np.dtype(np.float64))
        else:  # STR (interned id), BOOL
            out.append(np.dtype(np.int32))
    return out


def leaf_plane_slices(
    kinds: Sequence[str], compact32: Union[bool, Sequence[bool]] = False
) -> List[slice]:
    """Per-leaf slice into the flat plane list (i64 non-compact owns two
    planes, everything else one) — lets kernels touch only the planes of
    the leaves they actually update."""
    out: List[slice] = []
    start = 0
    for k, c32 in zip(kinds, _per_leaf(compact32, kinds)):
        n = 2 if (k == I64 and not c32) else 1
        out.append(slice(start, start + n))
        start += n
    return out


def pack_words(
    cols: Sequence[jnp.ndarray],
    kinds: Sequence[str],
    compact32: Union[bool, Sequence[bool]] = False,
) -> List[jnp.ndarray]:
    """Typed arrays -> storage plane arrays (i64 split as lo, hi)."""
    words: List[jnp.ndarray] = []
    for col, kind, c32 in zip(cols, kinds, _per_leaf(compact32, kinds)):
        if kind == I64:
            if c32:
                words.append(col.astype(jnp.int32))
            else:
                v = col.astype(jnp.int64)
                words.append((v & 0xFFFFFFFF).astype(jnp.uint32).astype(jnp.int32))
                words.append((v >> 32).astype(jnp.int32))
        elif kind == F64:
            words.append(col.astype(jnp.float32 if c32 else jnp.float64))
        elif kind == BOOL:
            words.append(col.astype(jnp.int32))
        else:
            words.append(col.astype(jnp.int32))
    return words


def unpack_words(
    words: Sequence[jnp.ndarray],
    kinds: Sequence[str],
    compact32: Union[bool, Sequence[bool]] = False,
) -> List[jnp.ndarray]:
    """Inverse of :func:`pack_words`."""
    cols: List[jnp.ndarray] = []
    w = 0
    for kind, c32 in zip(kinds, _per_leaf(compact32, kinds)):
        if kind == I64:
            if c32:
                cols.append(words[w].astype(jnp.int64))
                w += 1
            else:
                lo = words[w].astype(jnp.uint32).astype(jnp.int64)
                hi = words[w + 1].astype(jnp.int64)
                cols.append(lo | (hi << 32))
                w += 2
        elif kind == F64:
            cols.append(words[w].astype(jnp.float64))
            w += 1
        elif kind == BOOL:
            cols.append(words[w].astype(jnp.bool_))
            w += 1
        else:
            cols.append(words[w].astype(jnp.int32))
            w += 1
    return cols
