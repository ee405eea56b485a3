"""Per-job metrics: batches, records, emissions, latencies, overflow.

The reference has no observability beyond the print sink
(SURVEY.md §5 "tracing/profiling: none in-repo"); this provides the
structured per-batch counters SURVEY.md asks the build to add, plus an
optional ``jax.profiler`` trace hook.

Counter provenance: ``window_fires``/``late_dropped``/overflow counters
are accumulated ON DEVICE inside the jitted step (so they are exact even
when the executor never inspects per-step emissions, e.g. a job without
a late side output) and folded into this object once per job by
``Runner.finalize_metrics``. ``records_*`` and latency samples are
host-side.

``Metrics`` is now a compatibility facade over
:class:`tpustream.obs.registry.MetricsRegistry`: every legacy counter
field is a property backed by a job-scope registry Counter (attribute
reads/writes like ``metrics.records_in += n`` behave exactly as the old
dataclass ints did), and the three sample lists are list subclasses
that mirror each appended sample into a job-scope Histogram. Callers of
``summary()`` / ``overflow_counts()`` / the field names see no change;
callers that want per-operator series, spans, or exposition go through
``metrics.job_obs`` (a :class:`tpustream.obs.runtime.JobObs`, the null
twin unless the job ran with ``StreamConfig.obs.enabled``) or
``metrics.registry``.
"""

from __future__ import annotations

import time
from typing import List, Optional

from ..obs.registry import MetricsRegistry
from ..obs.runtime import NULL_JOB_OBS
from ..obs.snapshot import job_snapshot


def _percentile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(q * (len(sorted_vals) - 1) + 0.5))
    return sorted_vals[idx]


class _Samples(list):
    """A plain float list (callers slice it, sort it, feed it to numpy)
    that also mirrors every appended sample into a registry Histogram."""

    __slots__ = ("_hist",)

    def __init__(self, hist):
        super().__init__()
        self._hist = hist

    def append(self, v) -> None:
        list.append(self, v)
        self._hist.observe(v)

    def extend(self, vs) -> None:
        vs = list(vs)
        list.extend(self, vs)
        self._hist.observe_many(vs)


class Metrics:
    """Flat per-job counters/samples (the seed dataclass surface),
    backed by a metrics registry."""

    _COUNTER_FIELDS = (
        "batches",
        "records_in",
        "records_emitted",
        "window_fires",
        "late_dropped",
        # data-plane poison lines diverted to env.dead_letters instead of
        # failing the job (StreamConfig.dead_letter)
        "records_quarantined",
        # device-side overflow/loss counters (see StreamConfig.strict_overflow)
        "alert_overflow",
        "exchange_overflow",
        "buffer_overflow",
        "evicted_unfired",
        # CEP: completed pattern matches / within()-expired partials
        # (device-accumulated, folded at finalize like window_fires)
        "cep_matches",
        "cep_timeouts",
    )

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 job_name: str = "job"):
        self.registry = registry if registry is not None else MetricsRegistry()
        group = self.registry.group(job=job_name)
        self._counters = {n: group.counter(n) for n in self._COUNTER_FIELDS}
        self.step_times_s = _Samples(group.histogram("step_time_s"))
        self.host_times_s = _Samples(group.histogram("host_time_s"))
        # wall-clock batch-arrival -> emission-dispatch latency, sampled on
        # every step that emitted at least one record
        self.emit_latencies_s = _Samples(group.histogram("emit_latency_s"))
        # replaced with a live JobObs by execute_job when
        # StreamConfig.obs.enabled; every Runner hot-path obs call routes
        # through it (or its no-op null twin)
        self.job_obs = NULL_JOB_OBS

    def overflow_counts(self) -> dict:
        """The loss counters a strict job must keep at zero."""
        return {
            "alert_overflow": self.alert_overflow,
            "exchange_overflow": self.exchange_overflow,
            "buffer_overflow": self.buffer_overflow,
            "evicted_unfired": self.evicted_unfired,
        }

    def summary(self) -> dict:
        total_step = sum(self.step_times_s)
        lat = sorted(self.emit_latencies_s)
        return {
            "batches": self.batches,
            "records_in": self.records_in,
            "records_emitted": self.records_emitted,
            "window_fires": self.window_fires,
            "late_dropped": self.late_dropped,
            "records_quarantined": self.records_quarantined,
            "alert_overflow": self.alert_overflow,
            "exchange_overflow": self.exchange_overflow,
            "buffer_overflow": self.buffer_overflow,
            "evicted_unfired": self.evicted_unfired,
            "cep_matches": self.cep_matches,
            "cep_timeouts": self.cep_timeouts,
            "device_time_s": total_step,
            "host_time_s": sum(self.host_times_s),
            "emit_latency_p50_ms": _percentile(lat, 0.50) * 1000.0,
            "emit_latency_p99_ms": _percentile(lat, 0.99) * 1000.0,
        }

    def obs_snapshot(self, meta: Optional[dict] = None) -> dict:
        """Full observability snapshot (all registry series + trace ring
        when the job ran with obs enabled; the job-scope series this
        facade maintains otherwise)."""
        if self.job_obs.enabled:
            return self.job_obs.snapshot(meta)
        return job_snapshot(self.registry, None, meta=meta)

    def to_prometheus_text(self) -> str:
        return self.registry.to_prometheus_text()


def _counter_property(name: str) -> property:
    def fget(self):
        return self._counters[name].value

    def fset(self, v):
        self._counters[name].set_total(v)

    return property(fget, fset)


for _name in Metrics._COUNTER_FIELDS:
    setattr(Metrics, _name, _counter_property(_name))
del _name


class Stopwatch:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.elapsed = time.perf_counter() - self.t0
        return False


def start_device_trace(logdir: str) -> None:
    import jax

    jax.profiler.start_trace(logdir)


def stop_device_trace() -> None:
    import jax

    jax.profiler.stop_trace()
