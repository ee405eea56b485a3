"""keyBy exchange against the ICI peak: the payload that leaves the
first chip per step, over the device time per step of the first chip's
``all_to_all`` operations in the step's ``exchange`` scope, over the
chip's ICI peak (``benchmark/ici_peaks.json``).

The payload is counted from the stream and the run's real step
boundaries, not from the program's buffer shapes: in each step pulled
inside the window, the records placed on shard 0 (the step's rows
``[0, batch / shards)``: the runner splits a batch over the mesh in
contiguous blocks) whose owner, interned key id ``% shards``, is
another shard, times ``RECORD_BYTES`` (channel id 4 + flow 8 + event
time 8). So it reads the same work whatever implements the exchange,
and rows the exchange moves beyond that (padding, extra columns) show
as a lower share. A program without the ``exchange`` scope reads
nothing."""

import importlib.util
import json
import os
import re
import types

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
PEAKS = os.path.join(os.path.dirname(_HERE), "ici_peaks.json")
STEP = "jit_step"
SCOPE = "exchange"
RECORD_BYTES = 4 + 8 + 8
A2A = re.compile(r"all[-_]to[-_]all")
_BLOCK = 1 << 16


def _scope_intervals():
    spec = importlib.util.spec_from_file_location(
        "bench_metric_fire_device_ms", os.path.join(_HERE, "fire_device_ms.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.scope_intervals


def a2a_intervals(ctx):
    """``(union, runs)`` of the ``all_to_all`` operations in the
    ``exchange`` scope over the step's runs in the window (first chip),
    as ``fire_device_ms.scope_intervals`` reads a scope; None without
    them."""
    job_obs = getattr(getattr(getattr(ctx.run, "env", None), "metrics",
                              None), "job_obs", None)
    scopes = (getattr(job_obs, "op_scopes", None) or {}).get(STEP) or {}
    keep = {op: s for op, s in scopes.items()
            if s == SCOPE and A2A.search(op)}
    if not keep:
        return None
    ns = types.SimpleNamespace
    only = ns(run=ns(env=ns(metrics=ns(job_obs=ns(op_scopes={STEP: keep})))),
              trace=ctx.trace)
    return _scope_intervals()(only, SCOPE)


def key_ids(stream, upto: int) -> np.ndarray:
    """Each channel's interned id (-1 if unseen): the program interns
    key strings in the order they first appear (``records.StringTable``),
    so a channel's id is the rank of its first appearance among events
    ``[0, upto)``. Stops once every channel has one."""
    ids = np.full(stream.n_keys, -1, dtype=np.int64)
    nxt = start = 0
    while nxt < stream.n_keys and start < upto:
        n = min(_BLOCK, upto - start)
        uniq, first = np.unique(stream.events(start, n)["key"],
                                return_index=True)
        new = ids[uniq] < 0
        fresh = uniq[new][np.argsort(first[new], kind="stable")]
        ids[fresh] = np.arange(nxt, nxt + len(fresh))
        nxt += len(fresh)
        start += n
    return ids


def offchip_bytes(stream, step_sizes, steps, batch_size: int, shards: int,
                  shard: int = 0) -> int:
    """Bytes of the records that the steps ``steps`` (indices into
    ``step_sizes``) place on ``shard`` and whose owner is another shard,
    at ``RECORD_BYTES`` a record."""
    steps = list(steps)
    if not steps:
        return 0
    ends = np.cumsum(np.asarray(step_sizes, dtype=np.int64))
    starts = ends - np.asarray(step_sizes, dtype=np.int64)
    local = batch_size // shards
    ids = key_ids(stream, int(ends[max(steps)]))
    n = 0
    for s in steps:
        lo = int(starts[s]) + shard * local
        hi = min(int(ends[s]), lo + local)
        if hi > lo:
            owner = ids[stream.events(lo, hi - lo)["key"]] % shards
            n += int(np.count_nonzero(owner != shard))
    return n * RECORD_BYTES


def share(ctx, peak_bytes_per_s: float):
    got = a2a_intervals(ctx)
    if got is None:
        return None
    u, n_runs = got
    a2a_s = float((u[:, 1] - u[:, 0]).sum()) / 1e9 / n_runs
    run = ctx.run
    cfg = run.env.config
    steps = range(run.s_ws, run.s_we)
    if not len(steps) or a2a_s <= 0:
        return None
    per_step = offchip_bytes(run.stream, run.step_sizes, steps,
                             cfg.batch_size, cfg.parallelism) / len(steps)
    return per_step / a2a_s / peak_bytes_per_s


def read(ctx):
    if ctx.trace is None:
        return None
    import jax

    with open(PEAKS) as fh:
        table = json.load(fh)["devices"]
    peak = table.get(jax.devices()[0].device_kind)
    if peak is None:
        return None
    return share(ctx, float(peak["ici_bytes_per_s"]))
