"""Compile/recompile registry: device-side visibility into XLA builds.

Every executable the runtime builds goes through one jitted step per
program (the executor's ``_counted_step`` wrapper around the program's
``jitted_step``). With obs enabled that compile is made EXPLICIT: the
step is lowered and compiled ahead of time (``jax.jit(...).lower(*args)
.compile()``), so the wall time, XLA cost analysis and the *cause* of
the rebuild land in the MetricsRegistry and the FlightRecorder before
the executable ever runs — instead of hiding inside the first dispatch.

Per-operator series (labels ``{job, operator}``):

* ``operator_compile_count``       — every XLA build of the step
* ``operator_recompile_count``     — builds after the first (total)
* ``operator_recompile_cause``     — the same, labelled ``{cause=...}``
* ``operator_compile_wall_ms``     — histogram of lower+compile wall time
* ``operator_compile_flops`` / ``operator_compile_bytes_accessed``
  — from ``Compiled.cost_analysis()`` where the backend provides it
* ``operator_compile_output_bytes`` / ``_temp_bytes`` / ``_argument_bytes``
  / ``_code_bytes`` — from ``Compiled.memory_analysis()`` likewise

Recompile causes are threaded from the call site that nulled the step:
``key_capacity_growth`` (``_grow_key_capacity``), ``batch_shape_change``
(a new input signature / h2d layout demotion), ``config_change``
(checkpoint-restore capacity reconciliation), ``initial`` for the very
first build.

The instrumentation is strictly observational: the AOT ``Compiled``
object exists only to be timed and analysed, and every actual step runs
through the plain ``jax.jit`` dispatch — the byte-identical execution
path the uninstrumented runtime uses. The dispatch reuses the executable
the AOT compile built (one XLA build per signature), and that build
reads and writes the persistent compilation cache like any other. So a
``compile_wall_ms`` reading times either a build (a cache miss) or a
load from the cache (a hit); each ``program_compiled`` event says which
in its ``cache`` field, and ``compile_cache_hits`` counts the loads.

The AOT path is also belt-and-braces: if ``lower()``/``compile()``
raises, the wrapper permanently falls back to counting builds by the
plain dispatch's wall time — execution semantics are never at risk for
the sake of a metric. The fallback itself is a flight-recorder event.

With the profiler bridge on, each build also yields the program's
op-to-scope map (:func:`scope_map`): the ``jax.named_scope`` blocks of
the step (``STEP_SCOPES``) land in each HLO instruction's
``op_name`` metadata, so a device trace's per-op events can be summed
by phase. The map is published on the job's obs as
``JobObs.op_scopes[<module>]`` (``jit_step``), newest build per module.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional, Tuple

CAUSE_INITIAL = "initial"
CAUSE_KEY_GROWTH = "key_capacity_growth"
CAUSE_BATCH_SHAPE = "batch_shape_change"
CAUSE_CONFIG = "config_change"


# the named scopes the step programs open (runtime/executor.py
# _counted_step, runtime/window_program.py _step/_sweep, ops/rolling.py's
# rolling step, and on a mesh runtime/sharded.py's keyBy exchange and
# all-reduces), innermost wins
STEP_SCOPES = ("unpack", "retarget", "scatter", "fire", "sweep", "compact",
               "exchange", "allreduce", "combine", "state")
_OP_NAME = 'op_name="'


def scope_map(hlo_text: str) -> Tuple[Optional[str], Dict[str, str]]:
    """``(module, {instruction: scope})`` from one HLO module's text:
    each instruction whose ``op_name`` metadata runs through one of
    ``STEP_SCOPES`` maps to the innermost of them. The last component
    of an ``op_name`` is the primitive itself (``.../scatter`` is an
    unscoped scatter op), so only the components before it count."""
    module = None
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        if module is None and line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            continue
        i = line.find(_OP_NAME)
        if i < 0:
            continue
        lhs = line.split(" = ", 1)[0].split()
        if not lhs:
            continue
        j = i + len(_OP_NAME)
        path = line[j:line.find('"', j)].split("/")[:-1]
        for part in reversed(path):
            if part in STEP_SCOPES:
                out[lhs[-1].lstrip("%")] = part
                break
    return module, out


def _signature(args) -> tuple:
    """Hashable key over the array avals of a call: (shape, dtype,
    weak_type) per leaf, type name for non-array leaves. Collisions the
    key cannot see (e.g. sharding drift) surface as a TypeError from the
    compiled executable and trigger the jit fallback."""
    sig = []
    for leaf in _tree_leaves(args):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            sig.append(
                (tuple(shape), str(dtype), bool(getattr(leaf, "weak_type", False)))
            )
        else:
            sig.append(("py", type(leaf).__name__))
    return tuple(sig)


_CACHE_HITS = [0]
_CACHE_LISTENING = [False]


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _CACHE_HITS[0] += 1


def _cache_hits() -> int:
    """Persistent-cache hits JAX has reported in this process since the
    first call (the listener is installed then, once)."""
    if not _CACHE_LISTENING[0]:
        import jax.monitoring

        jax.monitoring.register_event_listener(_on_event)
        _CACHE_LISTENING[0] = True
    return _CACHE_HITS[0]


def _tree_leaves(args):
    import jax

    return jax.tree_util.tree_leaves(args)


def _cost_entry(compiled) -> Optional[dict]:
    """First cost-analysis dict, tolerant of the list-vs-dict return
    shape across jax versions; None when the backend has nothing."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    return ca if isinstance(ca, dict) else None


_MEMORY_FIELDS = (
    ("output_size_in_bytes", "compile_output_bytes"),
    ("temp_size_in_bytes", "compile_temp_bytes"),
    ("argument_size_in_bytes", "compile_argument_bytes"),
    ("generated_code_size_in_bytes", "compile_code_bytes"),
)


class CompileObs:
    """Per-runner compile instrumentation bundle (one per OperatorObs)."""

    def __init__(self, op_obs, flight, meta: Optional[Dict[str, Any]] = None,
                 op_scopes: Optional[dict] = None):
        """``op_scopes``: the job's ``JobObs.op_scopes`` to publish each
        build's op-to-scope map into (profiler bridge on), else None."""
        self._obs = op_obs
        self._flight = flight
        self._meta = dict(meta or {})
        self._op_scopes = op_scopes
        self.compile_count = op_obs.counter("compile_count")
        self.recompile_count = op_obs.counter("recompile_count")
        self.compile_wall_ms = op_obs.histogram("compile_wall_ms")
        self.compile_cache_hits = op_obs.counter("compile_cache_hits")
        self._n = 0

    def instrument(self, fn, cause: str, donate_argnums=0) -> "InstrumentedStep":
        return InstrumentedStep(fn, self, cause, donate_argnums=donate_argnums)

    def record_compile(self, cause: str, wall_ms: float, compiled=None,
                       cache: Optional[str] = None) -> None:
        """``cache``: "hit" when the build was a persistent-cache load,
        "miss" when XLA built it, None when unknown (fallback path)."""
        self.compile_count.inc()
        if cache == "hit":
            self.compile_cache_hits.inc()
        if self._n > 0:
            self.recompile_count.inc()
            self._obs.scoped(cause=cause).counter("operator_recompile_cause").inc()
        event: Dict[str, Any] = {
            "operator": self._obs.name,
            "cause": cause,
            "wall_ms": round(wall_ms, 3),
            "cache": cache,
            "compile_index": self._n,
        }
        event.update(self._meta)
        self._n += 1
        self.compile_wall_ms.observe(wall_ms)
        if compiled is not None:
            cost = _cost_entry(compiled)
            if cost:
                flops = cost.get("flops")
                accessed = cost.get("bytes accessed")
                if flops is not None:
                    self._obs.gauge("compile_flops").set(float(flops))
                    event["flops"] = float(flops)
                if accessed is not None:
                    self._obs.gauge("compile_bytes_accessed").set(float(accessed))
            try:
                mem = compiled.memory_analysis()
            except Exception:
                mem = None
            if mem is not None:
                for attr, gauge in _MEMORY_FIELDS:
                    v = getattr(mem, attr, None)
                    if v is not None:
                        self._obs.gauge(gauge).set(int(v))
                        event[gauge.replace("compile_", "")] = int(v)
            if self._op_scopes is not None:
                # the text keeps its metadata on a persistent-cache load
                # too (checked on the v5e and the CPU, jax 0.9)
                try:
                    module, ops = scope_map(compiled.as_text())
                except Exception:
                    module = None
                if module is not None:
                    self._op_scopes[module] = ops
        self._flight.record("program_compiled", **event)

    def record_fallback(self, exc: BaseException, where: str) -> None:
        self._obs.counter("compile_instrument_fallback").inc()
        self._flight.record(
            "compile_instrument_fallback",
            operator=self._obs.name,
            where=where,
            error=repr(exc),
        )


class InstrumentedStep:
    """Callable twin of ``jax.jit(fn, donate_argnums=...)`` that makes
    every build explicit: each new input signature is lowered and
    compiled ahead of time so the wall clock, cost analysis and cause
    can be recorded — then the call runs through the jit's own dispatch,
    which finds that executable already built and keeps its donation
    semantics untouched.

    The signature cache mirrors jit's own: one recorded build per
    distinct input aval signature. The first build carries the cause the
    executor threaded in; any further signature within the SAME step
    object can only come from changed input shapes/dtypes, so those
    builds record ``batch_shape_change``.
    """

    def __init__(self, fn, compile_obs: CompileObs, cause: str, donate_argnums=0):
        import jax

        self._jit = jax.jit(fn, donate_argnums=donate_argnums)
        self._obs = compile_obs
        self._next_cause = cause
        self._seen: set = set()
        self._fallback = False

    def __call__(self, *args):
        if not self._fallback:
            sig = _signature(args)
            if sig not in self._seen:
                cause = self._next_cause
                self._next_cause = CAUSE_BATCH_SHAPE
                try:
                    hits = _cache_hits()
                    t0 = time.perf_counter()
                    compiled = self._jit.lower(*args).compile()
                    wall_ms = (time.perf_counter() - t0) * 1e3
                    cache = "hit" if _cache_hits() > hits else "miss"
                except Exception as e:
                    # AOT path unavailable here: count the build the
                    # plain dispatch below performs (trace+compile+run
                    # wall time, no cost analysis) and stop trying
                    self._obs.record_fallback(e, where="lower")
                    self._fallback = True
                    t0 = time.perf_counter()
                    out = self._jit(*args)
                    self._obs.record_compile(
                        cause, (time.perf_counter() - t0) * 1e3, None
                    )
                    return out
                self._seen.add(sig)
                self._obs.record_compile(cause, wall_ms, compiled, cache)
                del compiled  # analysed, never executed (see class doc)
        return self._jit(*args)
