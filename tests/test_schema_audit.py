"""Schema inference (TSM025/TSM030–034) + checkpoint state-layout
audit (TSM040–047) — tpustream/analysis/{schema,state_audit}.py,
docs/analysis.md, docs/recovery.md.

Contracts pinned here:

* every schema rule and audit rule has a BROKEN construction that
  produces its exact TSM0xx code and a clean twin that does not;
* schema inference and ``env.analyze()`` are pure graph work — ZERO
  step programs compile during analysis (asserted by patching the one
  site that mints ``program_compiled``);
* the auditor's verdict on the checked-in format-version golden
  fixtures (tests/goldens/, v8–v13 plus the v12 incremental manifest
  form) exactly matches what ``validate_checkpoint`` /
  ``load_checkpoint`` / a real restore do;
* the supervisor's ``latest_checkpoint(audit=...)`` hook pre-empts a
  doomed restore with the audit reason in its ``checkpoint_skipped``
  breadcrumb and a ``checkpoint_audit`` breadcrumb per audit;
* the audit CLI mirrors the lint CLI's exit codes and JSON record
  shape.
"""

import importlib.util
import io
import json
import os
import shutil

import pytest

from tpustream import (
    CEP,
    OutputTag,
    Pattern,
    StreamExecutionEnvironment,
    Time,
    TimeCharacteristic,
    Tuple2,
    Tuple3,
)
from tpustream.analysis import CATALOG, ERROR, INFO, WARN, infer_schemas
from tpustream.analysis.state_audit import (
    AuditReport,
    audit_checkpoint,
    audit_manifest_only,
    expected_layout,
    read_manifest,
)
from tpustream.api.watermarks import BoundedOutOfOrdernessTimestampExtractor
from tpustream.config import ObsConfig, StreamConfig
from tpustream.jobs.chapter1_threshold import parse as parse1
from tpustream.jobs.chapter3_bandwidth import parse as parse3
from tpustream.runtime.checkpoint import (
    FORMAT_VERSION,
    latest_checkpoint,
    load_checkpoint,
    readable_version,
    validate_checkpoint,
)
from tpustream.runtime.supervisor import _layout_audit

GOLDENS = os.path.join(os.path.dirname(__file__), "goldens")


def _goldens_mod():
    """The fixture generator module (defines the golden job + LINES)."""
    spec = importlib.util.spec_from_file_location(
        "make_checkpoint_goldens",
        os.path.join(GOLDENS, "make_checkpoint_goldens.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def fixture(version: int) -> str:
    return os.path.join(GOLDENS, f"ckpt-fv{version:02d}.npz")


def codes(findings):
    return [f.code for f in findings]


def make_env(**cfg) -> StreamExecutionEnvironment:
    return StreamExecutionEnvironment(StreamConfig(**cfg))


def golden_env(tmp_path, **over) -> StreamExecutionEnvironment:
    """The exact job graph the golden fixtures were saved from
    (chapter-2 rolling max, batch_size=2), constructed but not run."""
    mod = _goldens_mod()
    from tpustream.jobs.chapter2_max import build

    env = StreamExecutionEnvironment(StreamConfig(
        batch_size=2,
        checkpoint_dir=str(tmp_path),
        checkpoint_interval_batches=1,
        **over,
    ))
    build(env, env.from_collection(mod.LINES)).collect()
    return env


class Ring:
    def __init__(self):
        self.events = []

    def record(self, kind, **payload):
        self.events.append((kind, payload))


class Extract(BoundedOutOfOrdernessTimestampExtractor):
    def __init__(self):
        super().__init__(Time.seconds(1))

    def extract_timestamp(self, element):
        return int(float(element.split(" ")[3]) * 1000)


# ---------------------------------------------------------------------------
# schema rules: broken construction -> exact code; clean twin -> silent
# ---------------------------------------------------------------------------


def test_tsm025_unreadable_source_is_visible_info():
    # an exec'd fn has no retrievable source: the purity rules are
    # skipped, but VISIBLY — one INFO TSM025, never a silent pass
    ns = {}
    exec("def mystery(v):\n    return v\n", ns)
    env = make_env()
    env.from_collection([]).map(parse1).map(ns["mystery"]).print()
    findings = env.analyze()
    assert "TSM025" in codes(findings)
    f = next(f for f in findings if f.code == "TSM025")
    assert f.severity == INFO
    assert "source unavailable" in f.message


def test_tsm025_silent_for_readable_functions():
    env = make_env()
    env.from_collection([]).map(parse1).key_by(0).max(2).print()
    assert "TSM025" not in codes(env.analyze())


def test_tsm030_float_key_column():
    env = make_env()
    env.from_collection([]).map(parse1).key_by(2).max(2).print()
    findings = env.analyze()
    assert "TSM030" in codes(findings)
    f = next(f for f in findings if f.code == "TSM030")
    assert f.severity == WARN
    assert "f64" in f.message


def test_tsm030_silent_for_string_key():
    env = make_env()
    env.from_collection([]).map(parse1).key_by(0).max(2).print()
    assert "TSM030" not in codes(env.analyze())


def test_tsm031_window_reduce_changes_schema():
    env = make_env()
    (
        env.from_collection([]).map(parse1).key_by(0)
        .time_window(Time.seconds(5))
        .reduce(lambda a, b: Tuple2(a.f0, a.f2 + b.f2))
        .print()
    )
    findings = env.analyze()
    assert "TSM031" in codes(findings)
    assert next(f for f in findings if f.code == "TSM031").severity == ERROR


def test_tsm031_rolling_reduce_changes_schema():
    env = make_env()
    (
        env.from_collection([]).map(parse1).key_by(0)
        .reduce(lambda a, b: Tuple2(a.f0, a.f2 + b.f2))
        .print()
    )
    assert "TSM031" in codes(env.analyze())


def test_tsm031_silent_for_schema_preserving_reduce():
    env = make_env()
    (
        env.from_collection([]).map(parse1).key_by(0)
        .time_window(Time.seconds(5))
        .reduce(lambda a, b: Tuple3(a.f0, a.f1, a.f2 + b.f2))
        .print()
    )
    assert "TSM031" not in codes(env.analyze())


def test_tsm032_fleet_parse_schema_mismatch():
    # the fleet graph parses [str, i64] but the TenantPlan template's
    # parse infers [str, str, f64]: tenants share ONE compiled program
    from tpustream.jobs.chapter6_tenant_fleet import make_fleet

    server = make_fleet({"tenant00": 90.0})
    env = StreamExecutionEnvironment(server.config)
    env.from_collection([]).map(parse3).filter(lambda v: v.f1 > 0).collect()
    env._tenancy = server
    findings = env.analyze()
    assert "TSM032" in codes(findings)
    f = next(f for f in findings if f.code == "TSM032")
    assert f.severity == ERROR
    assert "template" in f.message


def test_tsm032_key_field_resolves_to_non_str():
    from tpustream import JobServer, TenantPlan
    from tpustream.jobs.chapter6_tenant_fleet import build, make_rules

    plan = TenantPlan(
        parse=parse1, build=build, rules=make_rules(),
        tenant_capacity=8, key_field=2,  # f2 is the f64 usage column
    )
    server = JobServer(plan)
    server.add_tenant("t0", rules={"threshold": 90.0})
    env = StreamExecutionEnvironment(server.config)
    server.build_job(env)
    findings = env.analyze()
    assert "TSM032" in codes(findings)
    assert "key_field" in next(
        f for f in findings if f.code == "TSM032"
    ).message


def test_tsm032_silent_for_real_fleet():
    from tpustream.jobs.chapter6_tenant_fleet import lint_env

    assert "TSM032" not in codes(lint_env().analyze())


def test_tsm033_packed_wire_without_compress():
    env = make_env(packed_wire=True, h2d_compress=False)
    env.from_collection([]).map(parse3).key_by(0).sum(1).print()
    findings = env.analyze()
    assert "TSM033" in codes(findings)
    f = next(f for f in findings if f.code == "TSM033")
    assert f.severity == INFO
    assert "f1" in f.message  # names the pinned i64 column


def test_tsm033_silent_with_compression_or_no_i64():
    env = make_env(packed_wire=True, h2d_compress=True)
    env.from_collection([]).map(parse3).key_by(0).sum(1).print()
    assert "TSM033" not in codes(env.analyze())
    # no i64 column: nothing to narrow, even uncompressed
    env = make_env(packed_wire=True, h2d_compress=False)
    env.from_collection([]).map(parse1).key_by(0).max(2).print()
    assert "TSM033" not in codes(env.analyze())


def _late_and_timeout_job(env, late_id, timeout_id):
    """One chained pipeline producing a window late tag AND a CEP
    timeout tag — the two side-output producers with different record
    schemas."""
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    pattern = (
        Pattern.begin("a").where(lambda r: r.f2 > 0)
        .times(2).within(Time.seconds(10))
    )
    keyed = (
        env.from_collection([])
        .assign_timestamps_and_watermarks(Extract())
        .map(parse1)
        .key_by(0)
    )
    matches = CEP.pattern(keyed, pattern).select(
        _select_first, timeout_tag=OutputTag(timeout_id)
    )
    (
        matches.key_by(0)
        .time_window(Time.seconds(5))
        .allowed_lateness(Time.seconds(1))
        .side_output_late_data(OutputTag(late_id))
        .sum(2)
        .print()
    )
    return env


def _select_first(match):
    return match["a"][0]


def test_tsm034_tag_fed_disagreeing_schemas():
    # CEP timeout records are (n_matched, start_ts, captures...) i64-led
    # rows; window late records are the [str, str, f64] stream records —
    # one tag id receiving both is unreadable downstream
    env = _late_and_timeout_job(make_env(), "spill", "spill")
    findings = env.analyze()
    assert "TSM034" in codes(findings)
    f = next(f for f in findings if f.code == "TSM034")
    assert f.severity == WARN
    assert "spill" in f.message
    # the coarse collision rule fires too; TSM034 adds the schema detail
    assert "TSM003" in codes(findings)


def test_tsm034_silent_for_distinct_tags():
    env = _late_and_timeout_job(make_env(), "late", "to")
    assert "TSM034" not in codes(env.analyze())


def test_infer_schemas_chapter_goldens():
    """Pinned sink schemas for the tutorial jobs (golden: a schema
    change here is an API break, not a refactor)."""
    from tpustream.jobs.chapter1_threshold import build as build1
    from tpustream.jobs.chapter3_bandwidth import build as build3

    env = make_env()
    build1(env, env.from_collection([])).print()
    rep = infer_schemas(env)
    assert rep.complete
    assert rep.sink.kinds == ["str", "str", "f64"]
    assert [f.name for f in rep.sink.fields] == ["f0", "f1", "f2"]

    env = make_env()
    build3(env, env.from_collection([])).print()
    rep = infer_schemas(env)
    assert rep.sink.kinds == ["str", "i64"]
    # stage view: keyed by the str host column, windowed
    (stage,) = rep.stages
    assert stage.stateful_kind == "window"
    assert stage.mid.key_kind == "str"


def test_analyze_never_compiles(monkeypatch):
    """env.analyze() and infer_schemas() are pure graph work: the one
    site that mints ``program_compiled`` flight events must never run
    during analysis — even for CEP, fleet, and chained-window graphs."""
    from tpustream.obs.compilation import CompileObs

    compiles = []
    monkeypatch.setattr(
        CompileObs, "record_compile",
        lambda self, *a, **k: compiles.append((a, k)),
    )
    from tpustream.jobs.chapter6_tenant_fleet import lint_env

    envs = [
        _late_and_timeout_job(make_env(), "late", "to"),
        lint_env(),
    ]
    for env in envs:
        env.analyze()
        infer_schemas(env)
    assert compiles == []


# ---------------------------------------------------------------------------
# checkpoint state-layout audit vs the format-version golden fixtures
# ---------------------------------------------------------------------------


def test_audit_identical_job_is_compatible(tmp_path):
    env = golden_env(tmp_path)
    report = env.audit_checkpoint(fixture(12))
    assert isinstance(report, AuditReport)
    assert report.verdict == "compatible"
    # a v12 snapshot: its float64 max plane migrates to the image on
    # restore, which the audit names (visible, never blocking)
    assert set(codes(report.findings)) == {"TSM042", "TSM045"}
    assert all(f.severity == INFO for f in report.findings)
    assert report.reason is None
    # the expected tree is fully derived; the manifest matches its v12
    # form 1:1 (the usage plane in float64, not two int32 image planes)
    assert len(report.expected.legacy_leaves) == len(
        report.manifest.leaves) == 4
    assert len(report.expected.leaves) == 5
    assert report.expected.format_version == FORMAT_VERSION == 13


def test_audit_symbolic_shapes_name_the_key_axis(tmp_path):
    lay = expected_layout(golden_env(tmp_path))
    keyed = [l for l in lay.leaves if l.key_sharded]
    assert keyed and all(l.symbolic.startswith("(K") for l in keyed)
    assert lay.key_capacities == [1024]


def test_audit_grown_key_capacity_stays_compatible(tmp_path):
    # restore grows saved rows into the larger layout: supported path
    env = golden_env(tmp_path, key_capacity=4096)
    report = env.audit_checkpoint(fixture(12))
    assert report.verdict == "compatible"
    assert report.findings  # visible, not silent
    assert set(codes(report.findings)) == {"TSM042", "TSM043", "TSM045"}
    assert all(f.severity == INFO for f in report.findings)
    assert report.reason is None


def test_audit_missing_leaves_tsm040(tmp_path):
    # job grew a second keyed stage since the save: snapshot is short
    mod = _goldens_mod()
    env = make_env(batch_size=2)

    def parse_pair(value):
        from tpustream.javacompat import Double
        items = value.split(" ")
        return Tuple2(items[1], Double.parseDouble(items[3]))

    (
        env.from_collection(mod.LINES).map(parse_pair)
        .key_by(0).sum(1)
        .key_by(0).max(1)
        .collect()
    )
    report = env.audit_checkpoint(fixture(12))
    assert report.verdict == "incompatible"
    # (the fixture is a v12 snapshot: TSM045 notes the migration)
    assert codes(report.findings) == ["TSM040", "TSM045"]
    assert report.reason.startswith("TSM040")
    assert "stage1/" in report.reason  # names the missing tail


def test_audit_orphaned_leaves_tsm041(tmp_path):
    # job shrank to stateless since the save: snapshot has extra leaves
    from tpustream.jobs.chapter1_threshold import build as build1

    env = make_env()
    build1(env, env.from_collection([])).collect()
    report = env.audit_checkpoint(fixture(12))
    assert report.verdict == "incompatible"
    # (the fixture is a v12 snapshot: TSM045 notes the migration)
    assert codes(report.findings) == ["TSM041", "TSM045"]
    assert "orphaned" in report.reason


def test_audit_leaf_dtype_change_tsm042(tmp_path):
    # a snapshot whose value plane was written as float32 (a build with
    # a narrower state dtype): intact file, wrong leaf dtype
    import numpy as np

    from tpustream.runtime.checkpoint import _META_KEY, _checksum

    doctored = tmp_path / "ckpt-narrow.npz"
    with np.load(fixture(12)) as z:
        arrays = {k: z[k] for k in z.files}
    arrays["L0002"] = arrays["L0002"].astype(np.float32)
    leaves = [arrays[k] for k in sorted(arrays) if k.startswith("L")]
    meta = json.loads(bytes(arrays[_META_KEY]).decode())
    meta["checksum"] = _checksum(leaves)
    arrays[_META_KEY] = np.frombuffer(
        json.dumps(meta).encode(), dtype=np.uint8
    )
    with open(doctored, "wb") as f:
        np.savez(f, **arrays)

    env = golden_env(tmp_path / "ck")
    report = env.audit_checkpoint(str(doctored))
    assert report.verdict == "incompatible"
    # (the fixture is a v12 snapshot: TSM045 notes the migration)
    assert codes(report.findings) == ["TSM042", "TSM045"]
    f = next(f for f in report.findings if f.code == "TSM042")
    assert "float64" in f.message and "float32" in f.message
    assert report.reason.startswith("TSM042")


def test_audit_parallelism_rescale_is_not_blocking(tmp_path):
    # rescale-at-restore is a supported path: the audit must never
    # call it incompatible (on a 1-device test host the sharded layout
    # is underivable, so the verdict may degrade to "unknown")
    env = golden_env(tmp_path, parallelism=2)
    report = env.audit_checkpoint(fixture(12))
    assert report.verdict != "incompatible"
    assert "TSM047" in codes(report.findings)
    assert next(
        f for f in report.findings if f.code == "TSM047"
    ).severity == INFO


def test_audit_unreadable_snapshot_tsm046(tmp_path):
    p = tmp_path / "ckpt-garbage.npz"
    p.write_bytes(b"not a zip at all")
    report = audit_manifest_only(str(p))
    assert report.verdict == "incompatible"
    assert codes(report.findings) == ["TSM046"]
    env = golden_env(tmp_path)
    assert env.audit_checkpoint(str(p)).verdict == "incompatible"


def versioned(tmp_path, version: int) -> str:
    """The golden fixture of ``version``; past the newest fixture, the
    v12 payload rewritten to ``version`` (a future build's snapshot)."""
    path = fixture(version)
    if not os.path.exists(path):
        path = str(tmp_path / os.path.basename(path))
        shutil.copy(fixture(12), path)
        _goldens_mod().rewrite_format_version(path, version)
    return path


@pytest.mark.parametrize("version", [8, 9, 10, 11, 13, 14])
def test_audit_version_verdict_matches_real_restore(tmp_path, version):
    """TSM045 parity: every surface agrees whether a snapshot of another
    format version restores — the auditor, validate_checkpoint, the
    loader and a real restore. This build writes v13 and migrates v12
    (fv13 holds the v12 payload under this build's number, and restores
    by its leaves); v8-v11 and a future v14 cannot restore."""
    path = versioned(tmp_path, version)
    env = golden_env(tmp_path / "ck")
    report = env.audit_checkpoint(path)
    if readable_version(version):
        assert report.verdict == "compatible"
        assert validate_checkpoint(path) is None
        load_checkpoint(path)
        env.restore_from_checkpoint(path)
        env.execute("golden-resume")
        return
    assert report.verdict == "incompatible"
    assert "TSM045" in codes(report.findings)
    f = next(f for f in report.findings if f.code == "TSM045")
    assert f"v{version}" in f.message
    if version > FORMAT_VERSION:
        # a snapshot from the FUTURE: no migration narrative exists
        assert "future format" in f.message
    else:
        # the narrative names what changed in between (MIGRATIONS)
        assert f"v{version + 1}:" in f.message

    # restore-path parity
    assert f"format version {version}" in validate_checkpoint(path)
    with pytest.raises(ValueError, match="format version"):
        load_checkpoint(path)
    env.restore_from_checkpoint(path)
    with pytest.raises(ValueError, match="format version"):
        env.execute("doomed-restore")


def test_audit_compatible_verdict_matches_real_restore(tmp_path):
    """The v12 fixture audits compatible AND actually restores: the
    job resumes from the snapshot's source position and completes."""
    env = golden_env(tmp_path)
    assert env.audit_checkpoint(fixture(12)).verdict == "compatible"
    assert validate_checkpoint(fixture(12)) is None
    env.restore_from_checkpoint(fixture(12))
    env.execute("golden-resume")  # snapshot is at end-of-source: no-op run


def test_latest_checkpoint_skips_future_format(tmp_path):
    # a future v14 sorts newest; validation rejects it and recovery
    # falls back to the newest snapshot this build restores
    for v in (12, 13):
        shutil.copy(fixture(v), tmp_path / os.path.basename(fixture(v)))
    future = versioned(tmp_path, FORMAT_VERSION + 1)
    ring = Ring()
    picked = latest_checkpoint(str(tmp_path), flight=ring)
    assert picked == str(tmp_path / "ckpt-fv13.npz")
    (skip,) = [p for k, p in ring.events if k == "checkpoint_skipped"]
    assert skip["path"] == future
    assert f"format version {FORMAT_VERSION + 1}" in skip["reason"]


def test_supervisor_audit_hook_preempts_doomed_restore(tmp_path):
    """A checksum-valid, version-current snapshot whose leaf tree does
    not fit the current job is skipped BEFORE the restore attempt, with
    the TSM040 reason on the checkpoint_skipped breadcrumb."""
    shutil.copy(fixture(12), tmp_path / "ckpt-fv12.npz")
    from tpustream.jobs.chapter1_threshold import build as build1

    env = make_env()
    build1(env, env.from_collection([])).collect()
    ring = Ring()
    audit = _layout_audit(env, env._sinks, ring)
    picked = latest_checkpoint(str(tmp_path), flight=ring, audit=audit)
    assert picked is None  # nothing restorable survives
    audits = [p for k, p in ring.events if k == "checkpoint_audit"]
    assert audits and audits[0]["verdict"] == "incompatible"
    assert "TSM041" in audits[0]["codes"]
    (skip,) = [p for k, p in ring.events if k == "checkpoint_skipped"]
    assert skip["reason"].startswith("audit: TSM041")


def test_supervisor_audit_passes_compatible_snapshot(tmp_path):
    shutil.copy(fixture(12), tmp_path / "ckpt-fv12.npz")
    env = golden_env(tmp_path / "ck")
    ring = Ring()
    audit = _layout_audit(env, env._sinks, ring)
    picked = latest_checkpoint(str(tmp_path), flight=ring, audit=audit)
    assert picked == str(tmp_path / "ckpt-fv12.npz")
    audits = [p for k, p in ring.events if k == "checkpoint_audit"]
    # the v12 fixture's only findings are the migration notes (INFO)
    assert audits[0]["verdict"] == "compatible"
    assert audits[0]["codes"] == ["TSM042", "TSM045"]
    assert not [p for k, p in ring.events if k == "checkpoint_skipped"]


def test_audit_crash_never_blocks_recovery(tmp_path, monkeypatch):
    # the restore path stays authoritative: an auditor bug lets the
    # snapshot through instead of wedging the supervisor
    shutil.copy(fixture(12), tmp_path / "ckpt-fv12.npz")
    env = golden_env(tmp_path / "ck")
    monkeypatch.setattr(
        "tpustream.analysis.state_audit.audit_checkpoint",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("auditor bug")),
    )
    ring = Ring()
    audit = _layout_audit(env, env._sinks, ring)
    assert latest_checkpoint(
        str(tmp_path), flight=ring, audit=audit
    ) == str(tmp_path / "ckpt-fv12.npz")


def test_read_manifest_never_loads_arrays():
    m = read_manifest(fixture(12))
    assert m.meta["version"] == 12
    assert [(l.dtype, l.shape) for l in m.leaves] == [
        ("int32", (1024,)), ("int32", (1024,)),
        ("float64", (1024,)), ("bool", (1024,)),
    ]


def test_manifest_form_fixture_matches_inline(tmp_path):
    """The v12 INCREMENTAL manifest fixture (meta-only npz + content-
    hash chunks) audits identically to the inline form, validates its
    whole chunk chain, and loads byte-identical leaves."""
    import numpy as np

    m = read_manifest(os.path.join(GOLDENS, "ckpt-fv12m.npz"))
    assert m.meta["version"] == 12
    # leaf headers come from the chunk refs, same surface as inline
    assert [(l.dtype, l.shape) for l in m.leaves] == [
        (l.dtype, l.shape) for l in read_manifest(fixture(12)).leaves
    ]
    env = golden_env(tmp_path)
    report = env.audit_checkpoint(os.path.join(GOLDENS, "ckpt-fv12m.npz"))
    assert report.verdict == "compatible"
    assert validate_checkpoint(os.path.join(GOLDENS, "ckpt-fv12m.npz")) is None
    inline = load_checkpoint(fixture(12))
    manifest = load_checkpoint(os.path.join(GOLDENS, "ckpt-fv12m.npz"))
    assert len(inline.leaves) == len(manifest.leaves)
    for a, b in zip(inline.leaves, manifest.leaves):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# audit CLI
# ---------------------------------------------------------------------------


def test_audit_cli_compatible_with_job(tmp_path):
    from tpustream.analysis.audit import main as audit_main

    out = io.StringIO()
    rc = audit_main(
        [fixture(12), "--job", "tpustream.jobs.chapter2_max"], out=out
    )
    assert rc == 0
    assert "compatible" in out.getvalue()


def test_audit_cli_version_gap_exits_2():
    from tpustream.analysis.audit import main as audit_main

    out = io.StringIO()
    rc = audit_main([fixture(11)], out=out)
    assert rc == 2
    assert "TSM045" in out.getvalue()


def test_audit_cli_json_record_shape():
    from tpustream.analysis.audit import main as audit_main

    out = io.StringIO()
    rc = audit_main([fixture(8), "--format", "json"], out=out)
    assert rc == 2
    doc = json.loads(out.getvalue())
    assert doc["verdict"] == "incompatible"
    assert doc["reason"].startswith("TSM045")
    assert doc["manifest"]["meta_version"] == 8
    for rec in doc["findings"]:
        assert set(rec) == {"code", "severity", "node", "message", "fix_hint"}
        assert rec["code"] in CATALOG


# ---------------------------------------------------------------------------
# obs integration: the native-parse flavor breadcrumb
# ---------------------------------------------------------------------------


def test_flight_names_native_parse_flavor():
    from tpustream import native

    env = make_env(obs=ObsConfig(enabled=True))
    handle = env.from_collection(
        ["1563452051 10.8.22.1 cpu2 99.2"]
    ).map(parse1).collect()
    res = env.execute("flavor-breadcrumb")
    assert handle.items == [("10.8.22.1", "cpu2", 99.2)]
    events = res.metrics.job_obs.flight.events()
    kinds = [e["kind"] for e in events]
    if native.available():
        (ev,) = [e for e in events if e["kind"] == "native_parse_ready"]
        assert ev["flavor"] == native.build_flavor()
        assert ev["flavor"] in ("default", "asan")
    else:
        assert "native_parse_unavailable" in kinds
