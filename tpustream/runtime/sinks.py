"""Sinks: Flink-style print with subtask prefixes, collect, callables.

The ``print()`` sink reproduces the reference's observable format
byte-for-byte (``3> (10.8.22.1,cpu0,80.5)``, chapter1/README.md:80-84):
tuples render Java-``Tuple.toString`` style, doubles as
``Double.toString`` round-trips, and the ``n>`` prefix is the 1-based
owning subtask — the key-owner shard for keyed streams, a rotating
assignment for stateless ones. Like Flink, the prefix is omitted when
print parallelism is 1.
"""

from __future__ import annotations

import itertools
from typing import Callable, List, Optional, Sequence

import numpy as np

from ..api.tuples import TUPLE_CLASSES, _java_str
from ..obs.registry import NULL_COUNTER
from ..records import BOOL, F64, STR, f64_from_image


class EmissionFormatter:
    """Turns emission columns (numpy, already masked/compacted) into Python
    row values using the planned field kinds and string tables.

    Each column converts once (``tolist``, or one gather through its
    string table), and :func:`iter_rows` builds the rows from the
    converted lists as a sink takes them, so a row costs a few
    interpreter steps rather than a cast and a dispatch per field.

    ``images`` names the float64 columns that arrive as their int64
    image (``records.f64_image``); they turn back into the exact
    float64 values here."""

    def __init__(self, kinds: List[str], tables: List[Optional[object]],
                 images: Sequence[int] = ()):
        self.kinds = kinds
        self.tables = tables
        self.images = frozenset(images)

    def columns(self, cols: List[np.ndarray]) -> List[list]:
        """Each column as a list of Python values of its kind: ``str``
        (None for a negative id), ``float``, ``bool`` or ``int``."""
        return [
            _column_values(
                kind,
                f64_from_image(col) if i in self.images else np.asarray(col),
                table,
            )
            for i, (kind, col, table) in enumerate(
                zip(self.kinds, cols, self.tables)
            )
        ]


def _column_values(kind: str, col: np.ndarray, table) -> list:
    if kind == F64:
        return col.astype(np.float64, copy=False).tolist()
    if kind == BOOL:
        return col.astype(np.bool_, copy=False).tolist()
    ints = col.astype(np.int64, copy=False).tolist()
    return table.lookup_many(ints) if kind == STR else ints


def iter_rows(columns: List[list]):
    """The rows of converted columns, in order: the bare value at arity
    1, a ``TupleN`` at arity 2-4, a plain tuple above (e.g. CEP timeout
    records). Built one at a time as they are taken: a step's ~10k rows
    alive at once would be ~10k objects for Python's cyclic collector
    to promote, and its full collections then stall the loop."""
    if not columns:
        return iter(())
    if len(columns) == 1:
        return iter(columns[0])
    cls = TUPLE_CLASSES.get(len(columns))
    if cls is None:
        return zip(*columns)
    return map(cls.trusted, *columns)


def _body(value) -> str:
    if isinstance(value, str):
        return value
    if hasattr(value, "_FIELDS"):
        return repr(value)
    return _java_str(value)


class PrintSink:
    # per-sink emitted-record counter; the executor swaps in a real
    # registry Counter when StreamConfig.obs.enabled (otherwise every
    # emit pays one no-op call)
    obs_counter = NULL_COUNTER

    def __init__(self, parallelism: int = 1, stream=None):
        import sys

        self.parallelism = max(1, parallelism)
        self.stream = stream or sys.stdout
        self._rr = 0
        self.lines: List[str] = []  # retained for tests/inspection

    @property
    def reads_subtask(self) -> bool:
        return self.parallelism > 1

    def emit(self, value, subtask: Optional[int] = None) -> None:
        self.emit_many(
            (value,), None if subtask is None else (subtask,)
        )

    def emit_many(self, values, subtasks=None) -> int:
        """Print each value on its own line and return how many;
        ``subtasks`` (one per value) names each line's ``n>`` prefix,
        and without them the prefixes rotate over the parallelism."""
        lines = [_body(v) for v in values]
        if not lines:
            return 0
        p = self.parallelism
        if p > 1:
            if subtasks is None:
                rr = self._rr
                subtasks = range(rr, rr + len(lines))
                self._rr = (rr + len(lines)) % p
            lines = [
                f"{(st % p) + 1}> {body}"
                for st, body in zip(subtasks, lines)
            ]
        self.lines.extend(lines)
        print("\n".join(lines), file=self.stream)
        self.obs_counter.inc(len(lines))
        return len(lines)


class CollectSink:
    obs_counter = NULL_COUNTER
    reads_subtask = False

    def __init__(self, handle):
        self.handle = handle

    def emit(self, value, subtask: Optional[int] = None) -> None:
        self.handle.append(value)
        self.obs_counter.inc()

    def emit_many(self, values, subtasks=None) -> int:
        append = self.handle.append
        n = 0
        for n, v in enumerate(values, 1):
            append(v)
        self.obs_counter.inc(n)
        return n


class FnSink:
    obs_counter = NULL_COUNTER
    reads_subtask = False

    def __init__(self, fn: Callable):
        self.fn = fn

    def emit(self, value, subtask: Optional[int] = None) -> None:
        self.fn(value)
        self.obs_counter.inc()

    def emit_many(self, values, subtasks=None) -> int:
        fn = self.fn
        n = 0
        for n, v in enumerate(values, 1):
            fn(v)
        self.obs_counter.inc(n)
        return n


class RetryingSink:
    """Wraps any sink's ``emit`` with capped exponential backoff
    (StreamConfig.sink_retries / sink_retry_base_ms / sink_retry_max_ms).
    A transient sink failure — a flaky downstream the reference would
    model as an external system — retries ``attempts`` times, delaying
    ``min(base * 2^i, max)`` ms between tries, before escalating to the
    supervisor (runtime/supervisor.py). ``fault`` is the optional
    fault-injection hook (tpustream/testing/faults.py, point
    ``sink_emit``), checked per ATTEMPT so injected failures exercise
    the real retry path.

    The executor assigns ``sink.obs_counter`` directly on its sinks, so
    that attribute delegates to the wrapped sink; ``retry_counter``
    counts performed retries (wired by the Runner when obs is on).
    """

    retry_counter = NULL_COUNTER

    def __init__(
        self,
        inner,
        attempts: int = 0,
        base_ms: float = 10.0,
        max_ms: float = 1000.0,
        fault: Optional[Callable] = None,
    ):
        self.inner = inner
        self.attempts = max(0, int(attempts))
        self.base_ms = float(base_ms)
        self.max_ms = float(max_ms)
        self.fault = fault

    @property
    def obs_counter(self):
        return self.inner.obs_counter

    @obs_counter.setter
    def obs_counter(self, counter) -> None:
        self.inner.obs_counter = counter

    @property
    def reads_subtask(self) -> bool:
        return self.inner.reads_subtask

    def emit_many(self, values, subtasks=None) -> int:
        """Row by row through :meth:`emit`: each row retries (and meets
        an injected fault) on its own."""
        if subtasks is None:
            subtasks = itertools.repeat(None)
        n = 0
        for n, (v, st) in enumerate(zip(values, subtasks), 1):
            self.emit(v, subtask=st)
        return n

    def emit(self, value, subtask: Optional[int] = None) -> None:
        import time

        for attempt in range(self.attempts + 1):
            try:
                if self.fault is not None:
                    self.fault("sink_emit")
                self.inner.emit(value, subtask=subtask)
                return
            except Exception:
                if attempt >= self.attempts:
                    raise
                self.retry_counter.inc()
                delay_ms = min(self.base_ms * (2.0 ** attempt), self.max_ms)
                if delay_ms > 0:
                    time.sleep(delay_ms / 1000.0)


class LedgerSink:
    """Conservation-ledger shim: delegates ``emit`` and folds each row
    that actually landed into the sink's ledger account
    (obs/ledger.py). Wraps OUTSIDE RetryingSink so a row is folded
    exactly once, after every retry resolved — a raising emit folds
    nothing, which is exactly what the emit-edge invariant needs.

    For sinks with retained contents the fold reads the appended tail
    element (PrintSink stores the *prefixed* line, not the raw value),
    keeping the rolling digest re-derivable from the contents alone.
    """

    def __init__(self, inner, acct):
        self.inner = inner
        self.acct = acct

    @property
    def obs_counter(self):
        return self.inner.obs_counter

    @obs_counter.setter
    def obs_counter(self, counter) -> None:
        self.inner.obs_counter = counter

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def emit(self, value, subtask: Optional[int] = None) -> None:
        self.inner.emit(value, subtask=subtask)
        if self.acct.contents_fn is not None:
            self.acct.fold_tail()
        else:
            self.acct.fold_value(value)
