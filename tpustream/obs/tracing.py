"""Structured step tracing: lightweight span events per executor step.

Each hot-path phase (``parse``, ``pack``, ``h2d``, ``dispatch``,
``fetch``, ``emit``) records one span per *batch/step* — never per
record — into a bounded ring buffer. With double-buffered uploads
(``StreamConfig.h2d_depth`` > 1) the ``h2d`` span times the async
``device_put`` issue for a staged batch — overlap shows up as ``h2d``
spans of step N+1 landing before the ``fetch`` span of step N closes.
At ``h2d_depth`` 1 there is no separate ``device_put`` (the transfer
rides the step call inside ``dispatch``) and no ``h2d`` spans are
recorded — enable the ``jax.profiler`` bridge to see the device-side
split in that mode.

The bridge wraps each span in ``jax.profiler.TraceAnnotation`` so a
``jax.profiler.trace(...)`` capture shows host spans aligned with XLA
device activity. It is opt-in (``ObsConfig.profiler_bridge``) because
annotations add a little per-span overhead even when no trace is
active.

Every span carries the id of the step it serves. A step is numbered
when its batch is pulled from the source (:meth:`StepTracer.new_step`);
that batch's ``source``, ``parse``, ``pack``, ``h2d``, ``dispatch`` and
``emit`` spans carry the one id, a ``fetch`` carries the last step of
its group, and steps with no batch (deferred fires, end of stream) take
fresh ids when they are dispatched. ``parse.native`` and
``parse.remap`` are sub-spans nested inside ``parse`` (the native
tokenize + C-side intern, and the id remap into the plan's tables), and
``emit.format`` nests inside ``emit`` (converting the main stream's
fetched columns into Python values, before the sinks take the rows).
Under the bridge each annotation carries ``step=`` (and ``emit`` its
``rows=``) as trace-event stats, so ring spans and trace events can be
joined one to one.

``NULL_TRACER`` is the disabled twin: same surface, no state, no work.
"""

from __future__ import annotations

import itertools
import time
from typing import List, Optional

# "lane_parse" is the ingest-lane worker's parse span (runtime/ingest.py
# re-records it at the merge point with the worker-measured duration);
# "source" is the executor's wait for its next source batch, the two
# "parse." kinds nest inside "parse", and "emit.format" inside "emit".
# New kinds are appended LAST so the profiler's binding-stage gauge
# keeps its historical index values for the original stages.
SPAN_KINDS = ("parse", "pack", "h2d", "dispatch", "fetch", "emit",
              "lane_parse", "source", "parse.native", "parse.remap",
              "emit.format")
# spans that time a wait (source) or part of another span (parse.*,
# emit.format): the profiler leaves them out of stage shares and
# occupancy, so no host time is counted twice and idle time does not
# read as work
NON_STAGE_KINDS = ("source", "parse.native", "parse.remap", "emit.format")


class _Span:
    """Context manager handed out by :meth:`StepTracer.span`."""

    __slots__ = ("_tracer", "kind", "step", "operator", "_t0", "_ann")

    def __init__(self, tracer: "StepTracer", kind: str, step: int, operator: str):
        self._tracer = tracer
        self.kind = kind
        self.step = step
        self.operator = operator
        self._t0 = 0.0
        self._ann = None

    def __enter__(self) -> "_Span":
        # the bridged annotation reads its start when it is built, not
        # in __enter__, and its end first thing in __exit__: the ring's
        # clock is read right beside each, so whatever __enter__ does
        # stays out of the gap between the twins' clock reads. A read
        # on the annotation's other side bounds that gap, which a thread
        # descheduled there widens (StepTracer.bridge_skew_max_s)
        tracer = self._tracer
        if tracer._annotate is not None:
            name = f"tpustream.{self.kind}"
            before = time.perf_counter()
            ann = (
                tracer._annotate(name, step=self.step)
                if self.step >= 0 else tracer._annotate(name)
            )
            self._t0 = time.perf_counter()
            ann.__enter__()
            self._ann = ann
            if self._t0 - before > tracer.bridge_skew_max_s:
                tracer.bridge_skew_max_s = self._t0 - before
        else:
            self._t0 = time.perf_counter()
        return self

    def note(self, step: Optional[int] = None, **stats) -> None:
        """Set the span's step once it is known (a wait learns which
        step it served when the wait ends), and add stats to the bridged
        annotation (``rows=`` on ``emit``)."""
        if step is not None:
            self.step = step
            stats["step"] = step
        if self._ann is not None and stats:
            self._ann.set_metadata(**stats)

    def __exit__(self, *exc) -> None:
        ann = self._ann
        if ann is not None:
            ann_exit = ann.__exit__
            t1 = time.perf_counter()
            ann_exit(*exc)
            skew = time.perf_counter() - t1
            self._ann = None
            if skew > self._tracer.bridge_skew_max_s:
                self._tracer.bridge_skew_max_s = skew
        else:
            t1 = time.perf_counter()
        self._tracer._record(self.kind, self.step, self.operator, self._t0, t1 - self._t0)


class StepTracer:
    """Bounded ring buffer of ``(kind, step, operator, t_start, dur_s)``
    span events.

    ``capacity`` bounds memory for arbitrarily long jobs; the ring keeps
    the most recent ``capacity`` spans while ``total_spans`` counts every
    span ever recorded (so a snapshot reveals truncation).
    """

    enabled = True
    # bridged spans: the widest bracket of reads around an annotation's
    # own clock read, which bounds how far any span and its trace event
    # can disagree
    bridge_skew_max_s = 0.0

    def __init__(self, capacity: int = 4096, profiler_bridge: bool = False):
        self.capacity = max(1, int(capacity))
        self._ring: List[tuple] = []
        self._pos = 0
        self.total_spans = 0
        self._epoch = time.perf_counter()
        # step ids: drawn by the source pull and by runners dispatching
        # a step that no pulled batch numbered (count's next() is atomic
        # under the GIL, so the parse-ahead thread may draw too)
        self._step_ids = itertools.count(1)
        # span-drop accounting, wired by JobObs post-construction: a
        # Counter incremented per overwritten span, and a one-shot
        # callable fired on the FIRST drop (flight breadcrumb) so ring
        # overflow is never silent.
        self.drop_counter = None
        self.on_first_drop = None
        self._annotate = None
        if profiler_bridge:
            try:
                from jax.profiler import TraceAnnotation

                self._annotate = TraceAnnotation
            except Exception:
                self._annotate = None

    def span(self, kind: str, step: int = -1, operator: str = "") -> _Span:
        return _Span(self, kind, step, operator)

    def new_step(self) -> int:
        """A fresh step id (1-based, unique within the job)."""
        return next(self._step_ids)

    @property
    def bridged(self) -> bool:
        """Whether spans also enter the profiler's trace."""
        return self._annotate is not None

    def _record(self, kind: str, step: int, operator: str, t0: float, dur: float) -> None:
        ev = (kind, step, operator, t0 - self._epoch, dur)
        if len(self._ring) >= self.capacity:
            self._ring[self._pos] = ev
            self._pos = (self._pos + 1) % self.capacity
            if self.drop_counter is not None:
                self.drop_counter.inc()
            if self.on_first_drop is not None:
                hook, self.on_first_drop = self.on_first_drop, None
                try:
                    hook()
                except Exception:
                    pass
        else:
            self._ring.append(ev)
        self.total_spans += 1

    def raw_tail(self, n: int) -> List[tuple]:
        """The newest ``n`` retained raw span tuples
        ``(kind, step, operator, t_start_rel_s, dur_s)``, oldest first.
        The continuous profiler's incremental drain — no dict formatting
        on the consume path. ``t_start_rel_s`` is relative to
        :attr:`epoch` (registry-clock seconds at tracer construction)."""
        ordered = self._ring[self._pos:] + self._ring[: self._pos]
        if n < len(ordered):
            return ordered[len(ordered) - n:]
        return ordered

    @property
    def epoch(self) -> float:
        return self._epoch

    def events(self) -> List[dict]:
        """Spans in arrival order, oldest retained first."""
        ordered = self._ring[self._pos :] + self._ring[: self._pos]
        return [
            {
                "kind": k,
                "step": s,
                "operator": op,
                "t_start_s": round(t0, 6),
                "dur_s": round(d, 6),
            }
            for (k, s, op, t0, d) in ordered
        ]

    def snapshot(self) -> dict:
        return {
            "capacity": self.capacity,
            "total_spans": self.total_spans,
            "dropped_spans": max(0, self.total_spans - len(self._ring)),
            "events": self.events(),
        }


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def note(self, step=None, **stats) -> None:
        pass

    def __exit__(self, *exc):
        pass


_NULL_SPAN = _NullSpan()


class _NullTracer:
    """Disabled twin: ``span()`` hands back one shared no-op context
    manager, so tracing-off costs one method call per span site per
    step."""

    enabled = False
    bridged = False
    bridge_skew_max_s = 0.0
    capacity = 0
    total_spans = 0
    epoch = 0.0

    __slots__ = ()

    def span(self, kind: str, step: int = -1, operator: str = "") -> _NullSpan:
        return _NULL_SPAN

    def new_step(self) -> int:
        return -1

    def raw_tail(self, n: int) -> list:
        return []

    def events(self) -> list:
        return []

    def snapshot(self) -> dict:
        return {"capacity": 0, "total_spans": 0, "dropped_spans": 0, "events": []}


NULL_TRACER = _NullTracer()
