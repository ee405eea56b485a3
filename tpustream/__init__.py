"""tpustream — a TPU-native streaming monitoring/alerting framework.

Provides the dataflow surface of the reference Flink DataStream tutorial
(`/root/reference`, Jax-Rene/monitor-systam-flink-quickstart) — lazy job
graphs, map/filter/keyBy, rolling aggregates, tumbling/sliding/session
time windows, reduce/aggregate/process window functions, processing- and
event-time with bounded-out-of-orderness watermarks, allowed lateness and
late-data side outputs, parallel print sinks — executed not by a JVM
record-at-a-time runtime but as micro-batched SPMD XLA computations:

  * keyed state lives in dense TPU-HBM arrays indexed by interned key ids,
  * ``keyBy`` is an ICI ``all_to_all`` under ``shard_map`` over a device mesh,
  * sliding windows are pane-ring accumulators composed by an MXU matmul,
  * the event-time clock is a device-carried watermark scalar implementing
    the monotone ``max_seen_ts - delay`` contract of Flink's
    BoundedOutOfOrdernessTimestampExtractor
    (reference: chapter3/README.md:380-396).

Double precision is enabled globally so windowed aggregates reproduce the
reference's Java ``double`` golden outputs bit-for-bit (e.g.
``86.26666666666667`` in chapter2/README.md:162).
"""

import os as _os

if _os.environ.get("TPUSTREAM_LANE_WORKER") == "1":
    # Ingest-lane worker process (runtime/ingest.py spawns with this set):
    # the worker only runs the columnar parse plane
    # (hostparse + records + native), so the package skips jax and the
    # full API surface — worker start-up is a numpy import, not a jax
    # one. Everything a worker unpickles (PExpr plans, StringTables)
    # lives in modules importable under this gate.
    #
    # Escape hatch: under the "spawn" start method the child re-executes
    # the user's __main__, whose top-level ``from tpustream import ...``
    # must still resolve — resolve the public names lazily (normal
    # submodule imports, so class identities stay canonical) so the gate
    # never breaks a user script, it only defers the jax cost.
    _LAZY_API = {
        "Tuple2": "api.tuples", "Tuple3": "api.tuples",
        "Tuple4": "api.tuples",
        "Time": "api.timeapi", "TimeCharacteristic": "api.timeapi",
        "StreamExecutionEnvironment": "api.environment",
        "AssignerWithPeriodicWatermarks": "api.watermarks",
        "BoundedOutOfOrdernessTimestampExtractor": "api.watermarks",
        "Watermark": "api.watermarks",
        "AggregateFunction": "api.functions",
        "FilterFunction": "api.functions",
        "KeySelector": "api.functions", "MapFunction": "api.functions",
        "ProcessWindowFunction": "api.functions",
        "ReduceFunction": "api.functions",
        "OutputTag": "api.output",
        "Finding": "analysis", "PlanAnalysisError": "analysis",
        "BroadcastStream": "broadcast", "RuleDescriptor": "broadcast",
        "RuleParam": "broadcast", "RuleSet": "broadcast",
        "RuleUpdate": "broadcast",
        "CEP": "cep", "Pattern": "cep",
        "PatternSelectFunction": "cep",
        "StreamConfig": "config",
        "RestartStrategies": "runtime.supervisor",
        "JobServer": "tenancy", "TenantPlan": "tenancy",
        "TenantQuota": "tenancy",
    }

    def __getattr__(name):
        target = _LAZY_API.get(name)
        if target is None:
            raise AttributeError(name)
        import importlib

        import jax as _jax

        _jax.config.update("jax_enable_x64", True)
        mod = importlib.import_module("." + target, __name__)
        val = getattr(mod, name)
        globals()[name] = val
        return val
else:
    import jax as _jax

    # Java doubles / epoch-millisecond int64 timestamps need x64. TPU
    # benchmark configs opt back into f32/i32 accumulators via
    # StreamConfig.
    _jax.config.update("jax_enable_x64", True)
    # Persistent XLA compilation cache, set in this one place. JAX reads
    # JAX_COMPILATION_CACHE_DIR itself; when neither it nor the caller
    # names a directory, the cache sits at a fixed path in the checkout,
    # where the next run finds it (a temp, pid or timestamp name would
    # never be found again).
    if _jax.config.jax_compilation_cache_dir is None:
        _jax.config.update(
            "jax_compilation_cache_dir",
            _os.path.join(
                _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
                ".jax_cache",
            ),
        )

    from .api.tuples import Tuple2, Tuple3, Tuple4  # noqa: E402
    from .api.timeapi import Time, TimeCharacteristic  # noqa: E402
    from .api.environment import StreamExecutionEnvironment  # noqa: E402
    from .api.watermarks import (  # noqa: E402
        AssignerWithPeriodicWatermarks,
        BoundedOutOfOrdernessTimestampExtractor,
        Watermark,
    )
    from .api.functions import (  # noqa: E402
        AggregateFunction,
        FilterFunction,
        KeySelector,
        MapFunction,
        ProcessWindowFunction,
        ReduceFunction,
    )
    from .api.output import OutputTag  # noqa: E402
    from .analysis import Finding, PlanAnalysisError  # noqa: E402
    from .broadcast import (  # noqa: E402
        BroadcastStream,
        RuleDescriptor,
        RuleParam,
        RuleSet,
        RuleUpdate,
    )
    from .cep import CEP, Pattern, PatternSelectFunction  # noqa: E402
    from .config import StreamConfig  # noqa: E402
    from .runtime.supervisor import RestartStrategies  # noqa: E402
    from .tenancy import JobServer, TenantPlan, TenantQuota  # noqa: E402

__version__ = "0.1.0"

__all__ = [
    "AggregateFunction",
    "AssignerWithPeriodicWatermarks",
    "BoundedOutOfOrdernessTimestampExtractor",
    "BroadcastStream",
    "CEP",
    "FilterFunction",
    "Finding",
    "JobServer",
    "KeySelector",
    "MapFunction",
    "OutputTag",
    "Pattern",
    "PatternSelectFunction",
    "PlanAnalysisError",
    "ProcessWindowFunction",
    "ReduceFunction",
    "RestartStrategies",
    "RuleDescriptor",
    "RuleParam",
    "RuleSet",
    "RuleUpdate",
    "StreamConfig",
    "StreamExecutionEnvironment",
    "TenantPlan",
    "TenantQuota",
    "Time",
    "TimeCharacteristic",
    "Tuple2",
    "Tuple3",
    "Tuple4",
    "Watermark",
]
