"""Stream sources.

* ``SocketTextSource`` — nc-compatible line socket, the reference's only
  source (chapter1/.../Main.java:17, run against ``nc -lk 8080`` per
  chapter1/README.md:65-68). A feeder thread drains the socket into a
  queue; the executor pulls size- or deadline-bounded batches, so the
  device pipeline sees fixed-shape micro-batches.

* ``ReplaySource`` — deterministic test source (SURVEY.md §4): replays a
  recorded list of lines with a *virtual* processing-time clock, driven by
  ``AdvanceProcessingTime`` control tokens, so the transcripts'
  "wait ~1 minute" steps (chapter2/README.md:160) become instantaneous
  and exactly reproducible.
"""

from __future__ import annotations

import queue
import socket
import threading
import time as _time
from dataclasses import dataclass, field
from typing import Iterable, Iterator, List, Optional

import numpy as np

# Wall-anchored monotonic clock for INTERNAL timestamps (ingestion
# stamps, processing-time idle ticks). Anchoring time.monotonic() to the
# wall clock once at import gives epoch-ms values comparable across the
# process, while steps in the system clock (NTP corrections, manual
# resets) can never run the stamp stream backwards — a backwards step
# used to produce negative queue/e2e latencies and non-monotone
# proc-time clocks. Event-time semantics (record-embedded timestamps,
# watermarks) stay genuinely wall-clock and are unaffected.
_EPOCH_MS = _time.time() * 1000.0 - _time.monotonic() * 1000.0


def monotonic_epoch_ms() -> int:
    """Epoch milliseconds from a monotonic clock anchored at import."""
    return int(_EPOCH_MS + _time.monotonic() * 1000.0)


@dataclass
class SourceBatch:
    """One host-side pull from a source.

    Either ``lines`` (decoded Python strings) or ``raw`` (a
    newline-separated byte buffer of ``n_raw`` lines, never both) —
    the raw form feeds the native columnar parser without ever
    materializing per-line Python objects, which is what lets the host
    side keep up with the device at millions of events/sec on one core.

    ``markers`` is the obs control lane riding the data path: latency
    markers (obs/latency.py) attached by the executor's stamper wrap,
    which cross every pack/dispatch/fetch/emit edge exactly like the
    batch's records do. Under a multi-tenant fleet each marker carries
    a tenant label (the JobServer's round-robin provider), so the
    source batch is also where per-tenant end-to-end latency samples
    are born (docs/multitenancy.md). With
    ``ObsConfig.trace_sample_rate > 0`` a sampled batch also carries a
    ``RecordTrace`` probe — a marker promoted to a full flight-path
    trace that accumulates one span per hop (source, lane parse,
    merge, pack, h2d, device step, fetch, sinks) for the unified
    Perfetto timeline (obs/tracing_export.py). Markers and traces are
    control events: excluded from operator semantics, so job output is
    byte-identical with or without them.
    """

    lines: List[str]
    proc_ts: np.ndarray                 # int64 epoch ms per line
    advance_proc_to: Optional[int] = None  # force the proc-time clock forward
    final: bool = False                 # end of stream
    raw: Optional[bytes] = None         # newline-separated buffer
    n_raw: int = 0                      # line count of ``raw``
    markers: Optional[list] = None      # obs LatencyMarkers riding this
                                        # batch (None unless the obs
                                        # stamper is installed)
    # set by the executor when it pulls the batch: the step id its
    # spans carry (obs/tracing.py; -1 with tracing off) and how long
    # the executor waited for it (its ``source`` span)
    step: int = -1
    wait_s: float = 0.0

    @property
    def n_records(self) -> int:
        return self.n_raw if self.raw is not None else len(self.lines)


@dataclass(frozen=True)
class AdvanceProcessingTime:
    """Control token for ReplaySource: advance the virtual clock to ``ms``.

    Stands in for the golden transcripts' wall-clock waits; processing-time
    windows whose end <= ms fire deterministically.
    """

    ms: int


class Source:
    # NOTE: there is deliberately no boundedness flag — every source ends
    # by yielding a ``final`` batch (socket close, iterator exhaustion, or
    # replay end), and the executor then emits the Flink end-of-source
    # MAX watermark / final processing-time tick uniformly.

    # Whether a fresh ``batches()`` call re-yields the SAME stream from
    # the start — the property supervised restart (runtime/supervisor.py)
    # needs to resume exactly-once from a checkpoint's source position.
    # The deterministic replay sources are; a consumed iterator or a live
    # socket is not (the supervisor then refuses to restart, with a
    # flight breadcrumb, instead of silently resuming a different
    # stream).
    replayable = True

    # Whether the stream can be SPLIT across ingest lanes
    # (StreamConfig.ingest_lanes > 1, runtime/ingest.py): the producer
    # frames each SourceBatch as one newline-delimited byte block and
    # deals blocks round-robin to lane worker processes. Any source
    # whose batches carry raw bytes or decodable lines qualifies (the
    # replay/iterable sources do); a line-mode socket does not — its
    # per-line Python queue IS the single-stream ceiling the lanes
    # exist to break, so the analyzer (TSM016) demands ``raw=True``
    # there instead of silently re-serializing.
    splittable = True

    def batches(self, batch_size: int, max_delay_ms: float) -> Iterator[SourceBatch]:
        raise NotImplementedError  # pragma: no cover

    def queue_depth(self) -> Optional[int]:
        """Pending items buffered inside the source, for the obs layer's
        backpressure gauge. None for sources with no internal queue
        (replay/iterable sources hand batches straight through)."""
        return None


class ReplaySource(Source):
    def __init__(self, items: Iterable, start_ms: int = 0, ms_per_record: int = 0):
        self.items = list(items)
        self.start_ms = start_ms
        self.ms_per_record = ms_per_record

    def batches(self, batch_size: int, max_delay_ms: float) -> Iterator[SourceBatch]:
        now = self.start_ms
        lines: List[str] = []
        times: List[int] = []

        def flush(advance: Optional[int] = None, final: bool = False):
            nonlocal lines, times
            b = SourceBatch(lines, np.asarray(times, dtype=np.int64), advance, final)
            lines, times = [], []
            return b

        for item in self.items:
            if isinstance(item, AdvanceProcessingTime):
                now = max(now, item.ms)
                yield flush(advance=now)
                continue
            lines.append(item)
            times.append(now)
            now += self.ms_per_record
            if len(lines) >= batch_size:
                yield flush()
        yield flush(final=True)


class ReplayBytesSource(Source):
    """Replays pre-rendered newline-separated byte buffers through the
    raw ingest lane (native parse, no per-line Python objects).

    ``buffers`` is a list of ``(raw_bytes, n_lines)`` pairs; the whole
    list replays ``loop`` times. The virtual processing-time clock
    advances ``ms_per_batch`` per buffer (0 = constant clock), mirroring
    ReplaySource's deterministic stamping."""

    def __init__(
        self,
        buffers: List[tuple],
        start_ms: int = 0,
        ms_per_batch: int = 0,
        loop: int = 1,
    ):
        self.buffers = list(buffers)
        self.start_ms = start_ms
        self.ms_per_batch = ms_per_batch
        self.loop = loop

    def batches(self, batch_size: int, max_delay_ms: float) -> Iterator[SourceBatch]:
        now = self.start_ms
        for _ in range(self.loop):
            for raw, n in self.buffers:
                yield SourceBatch(
                    [],
                    np.full(n, now, dtype=np.int64),
                    raw=raw,
                    n_raw=n,
                )
                now += self.ms_per_batch
        # final flush carries no clock advance, exactly like ReplaySource
        yield SourceBatch([], np.empty(0, dtype=np.int64), final=True)


class IterableSource(Source):
    """Wraps any (possibly infinite) iterator of lines; wall-clock stamped."""

    replayable = False  # the iterator is consumed as it streams

    def __init__(self, it: Iterable):
        self._it = iter(it)

    def batches(self, batch_size: int, max_delay_ms: float) -> Iterator[SourceBatch]:
        lines: List[str] = []
        now = monotonic_epoch_ms
        for line in self._it:
            lines.append(line)
            if len(lines) >= batch_size:
                t = now()
                yield SourceBatch(lines, np.full(len(lines), t, dtype=np.int64))
                lines = []
        t = now()
        yield SourceBatch(lines, np.full(len(lines), t, dtype=np.int64), final=True)


class SocketTextSource(Source):
    """Line-delimited TCP socket source (reference chapter1/.../Main.java:17).

    Reconnects are NOT attempted (Flink's simple socket source semantics):
    when the server closes, the stream ends and event-time jobs flush.

    ``raw=True`` switches the reader to byte-block mode: received chunks
    are split only at the last newline and queued as (bytes, n_lines)
    blocks — no per-line Python strings anywhere — feeding the
    executor's native raw ingest lane. Per-line arrival stamps coarsen
    to the block's receive time (the same instant up to one ``recv``).
    """

    replayable = False  # live network stream: gone once read

    def __init__(
        self,
        host: str,
        port: int,
        idle_tick_ms: float = 200.0,
        raw: bool = False,
    ):
        self.host = host
        self.port = port
        self.idle_tick_ms = idle_tick_ms
        self.raw = raw
        # raw mode queues length-framed byte blocks — the framing
        # producer ingest lanes shard; line mode's per-line queue is
        # itself the single-stream bottleneck, so it is not splittable
        self.splittable = raw
        # line mode: items are lines (~bytes each); raw mode: items are
        # up-to-1MB blocks, so the bound is a BYTE budget (~64 MB), not
        # a count sized for lines
        self._queue: "queue.Queue" = queue.Queue(
            maxsize=64 if raw else 1 << 16
        )
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    def queue_depth(self) -> Optional[int]:
        # items (lines or raw blocks) received but not yet consumed by
        # the executor — the socket source's backpressure signal
        return self._queue.qsize()

    def _reader(self) -> None:
        # lines are stamped with the wall clock AT READ TIME (Flink's
        # source-assigned processing time): if the job stalls (e.g. the
        # first jit compile), queued records keep their true arrival
        # times instead of inheriting the post-stall clock
        try:
            try:
                sock_cm = socket.create_connection((self.host, self.port))
            except OSError as e:
                # surface connect failures on the MAIN thread (Flink's
                # socket source fails the job with ConnectException too)
                self._error = RuntimeError(
                    f"socket source could not connect to "
                    f"{self.host}:{self.port}: {e} — start a line server "
                    f"first, e.g. `nc -lk {self.port}`"
                )
                return
            if self.raw:
                self._read_stream_raw(sock_cm)
            else:
                self._read_stream(sock_cm)
        except OSError as e:
            # mid-stream failures (e.g. connection reset) also fail the
            # job instead of masquerading as a clean end-of-stream
            self._error = RuntimeError(
                f"socket source lost the connection to "
                f"{self.host}:{self.port}: {e}"
            )
        finally:
            self._queue.put(None)  # sentinel: EOF

    def _read_stream(self, sock_cm) -> None:
        with sock_cm as sock:
            buf = b""
            while True:
                chunk = sock.recv(1 << 16)
                if not chunk:
                    break
                buf += chunk
                while b"\n" in buf:
                    line, buf = buf.split(b"\n", 1)
                    self._queue.put(
                        (line.decode("utf-8", "replace").rstrip("\r"),
                         monotonic_epoch_ms())
                    )
            if buf:
                self._queue.put(
                    (buf.decode("utf-8", "replace").rstrip("\r"),
                     monotonic_epoch_ms())
                )

    def _read_stream_raw(self, sock_cm) -> None:
        with sock_cm as sock:
            tail = b""
            while True:
                chunk = sock.recv(1 << 20)
                if not chunk:
                    break
                buf = tail + chunk
                cut = buf.rfind(b"\n")
                if cut < 0:
                    tail = buf
                    continue
                block, tail = buf[: cut + 1], buf[cut + 1 :]
                if b"\r" in block:  # CRLF parity with the line mode
                    block = block.replace(b"\r\n", b"\n")
                n = block.count(b"\n")
                self._queue.put((block, n, monotonic_epoch_ms()))
            if tail:
                self._queue.put(
                    (tail.rstrip(b"\r") + b"\n", 1, monotonic_epoch_ms())
                )

    def batches(self, batch_size: int, max_delay_ms: float) -> Iterator[SourceBatch]:
        self._thread = threading.Thread(target=self._reader, daemon=True)
        self._thread.start()
        done = False
        while not done:
            items: List = []
            total = 0
            deadline = _time.monotonic() + max_delay_ms / 1000.0
            while total < batch_size:
                timeout = deadline - _time.monotonic()
                if timeout <= 0:
                    break
                try:
                    item = self._queue.get(timeout=timeout)
                except queue.Empty:
                    break
                if item is None:
                    if self._error is not None:
                        raise self._error
                    done = True
                    break
                items.append(item)
                total += item[1] if self.raw else 1
            now = monotonic_epoch_ms()
            # idle ticks still advance the processing-time clock so
            # processing-time windows fire without fresh input
            if self.raw:
                yield SourceBatch(
                    [],
                    np.concatenate(
                        [np.full(n, stamp, dtype=np.int64) for _, n, stamp in items]
                    )
                    if items
                    else np.empty(0, dtype=np.int64),
                    advance_proc_to=now,
                    final=done,
                    raw=b"".join(block for block, _, _ in items),
                    n_raw=total,
                )
            else:
                yield SourceBatch(
                    [line for line, _ in items],
                    np.asarray([stamp for _, stamp in items], dtype=np.int64),
                    advance_proc_to=now,
                    final=done,
                )
            if not done and not items:
                _time.sleep(self.idle_tick_ms / 1000.0)
