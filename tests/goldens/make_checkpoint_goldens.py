"""Regenerate the checked-in checkpoint-format golden fixtures.

Runs GOLDEN_JOB (a tiny deterministic checkpointed chapter-2 rolling
max) and keeps its final snapshot in both v12 forms plus a version
ladder:

* ``ckpt-fv12.npz`` — the INLINE self-contained form, exactly as this
  build writes it with ``checkpoint_incremental=False``
* ``ckpt-fv12m.npz`` + ``chunks/`` — the INCREMENTAL manifest form:
  the npz holds only ``__meta__``; every leaf lives in a content-hash
  chunk file the manifest references (only the chunks the final
  manifest needs are kept)
* ``ckpt-fv08.npz`` … ``ckpt-fv11.npz`` — the inline payload with the
  meta version rewritten down (the ``_rewrite_format_version``
  technique from tests/test_recovery.py: payload and checksum stay
  valid, ONLY the format version mismatches — simulating snapshots
  written by older builds)
* ``ckpt-fv13.npz`` — a version this build does not know yet

tests/test_schema_audit.py asserts the state-layout auditor's verdict
on each fixture matches what ``validate_checkpoint`` /
``latest_checkpoint`` / a real restore actually do. Regenerate (only
needed after a deliberate FORMAT_VERSION bump) with::

    JAX_PLATFORMS=cpu python tests/goldens/make_checkpoint_goldens.py
"""

import glob
import json
import os
import shutil
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))

# enough lines for several batch_size=2 interval-1 snapshots
LINES = [
    f"15634520{i % 60:02d} 10.8.22.{i % 3} cpu{i % 2} {(i * 7) % 100}.5"
    for i in range(12)
]


def build_env(ckdir, incremental):
    """The golden job: chapter-2 rolling max over a replay source, one
    snapshot per batch. Must stay byte-stable across regenerations
    (checkpoint_async=False: the barrier writes inline, so the run's
    final snapshot is always the last batch's)."""
    from tpustream import StreamExecutionEnvironment
    from tpustream.config import StreamConfig
    from tpustream.jobs.chapter2_max import build

    env = StreamExecutionEnvironment(StreamConfig(
        batch_size=2,
        checkpoint_dir=str(ckdir),
        checkpoint_interval_batches=1,
        checkpoint_async=False,
        checkpoint_incremental=incremental,
    ))
    build(env, env.from_collection(LINES)).collect()
    return env


def rewrite_format_version(path, version):
    import numpy as np

    from tpustream.runtime.checkpoint import _META_KEY

    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files if k != _META_KEY}
        meta = json.loads(bytes(z[_META_KEY]).decode())
    meta["version"] = version
    with open(path, "wb") as f:
        np.savez(f, **arrays, **{_META_KEY: np.frombuffer(
            json.dumps(meta).encode(), dtype=np.uint8)})


def _final_snapshot(ckdir):
    return sorted(glob.glob(os.path.join(ckdir, "ckpt-*.npz")))[-1]


def main():
    from tpustream.runtime.checkpoint import CHUNK_DIR, FORMAT_VERSION

    assert FORMAT_VERSION == 12, (
        f"FORMAT_VERSION is {FORMAT_VERSION}: the fixtures are the v12 "
        "snapshots this build restores by migration (tests/goldens/"
        "README.md); re-point the names/versions below and "
        "tests/test_schema_audit.py before regenerating"
    )
    # inline self-contained form + the version ladder derived from it
    d = tempfile.mkdtemp()
    build_env(d, incremental=False).execute("golden-checkpoint")
    current = os.path.join(HERE, "ckpt-fv12.npz")
    shutil.copy(_final_snapshot(d), current)
    for v in (8, 9, 10, 11, 13):
        p = os.path.join(HERE, f"ckpt-fv{v:02d}.npz")
        shutil.copy(current, p)
        rewrite_format_version(p, v)
    # incremental manifest form: the same job's final snapshot plus the
    # content-hash chunks its manifest references (and nothing else)
    d2 = tempfile.mkdtemp()
    build_env(d2, incremental=True).execute("golden-checkpoint")
    manifest = _final_snapshot(d2)
    shutil.copy(manifest, os.path.join(HERE, "ckpt-fv12m.npz"))
    import numpy as np

    from tpustream.runtime.checkpoint import _META_KEY

    with np.load(manifest) as z:
        meta = json.loads(bytes(z[_META_KEY]).decode())
    chunk_dst = os.path.join(HERE, CHUNK_DIR)
    shutil.rmtree(chunk_dst, ignore_errors=True)
    os.makedirs(chunk_dst)
    for ref in meta["chunks"]:
        name = f"{ref['chunk']}.npy"
        shutil.copy(
            os.path.join(d2, CHUNK_DIR, name),
            os.path.join(chunk_dst, name),
        )
    for n in sorted(os.listdir(HERE)):
        p = os.path.join(HERE, n)
        if n.endswith(".npz"):
            print(n, os.path.getsize(p), "bytes")
        elif os.path.isdir(p):
            print(f"{n}/ ({len(os.listdir(p))} chunks)")


if __name__ == "__main__":
    main()
