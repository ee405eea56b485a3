"""ctypes bindings for the native fast parser.

Builds ``_fastparse.so`` from ``fastparse.cpp`` on first use, on the
host that loads it (g++ is in the image; pybind11 is not, so the binding
is plain ctypes). The library is never committed: it is built with
``-march=native``, and a copy from another CPU can die with SIGILL,
which ``dlopen`` does not catch. Falls back gracefully: callers check
``available()`` and keep the numpy/python path when compilation fails.
"""

from __future__ import annotations

import contextlib
import ctypes
import os
import subprocess
import threading
from typing import List, Optional

import numpy as np

from ..records import _Remap

KIND_STR = 0
KIND_F64 = 1
KIND_I64 = 2
KIND_ISO = 3

_HERE = os.path.dirname(os.path.abspath(__file__))
# build flavors: "default" is the tuned production .so; "asan" (selected
# with TPUSTREAM_NATIVE_FLAVOR=asan, plus LD_PRELOADing libasan into the
# interpreter) is the Makefile's sanitized target with
# -fsanitize=address,undefined for memory-safety runs of the same kernel.
# Each flavor's library name is also its Makefile target.
_FLAVORS = {"default": "_fastparse.so", "asan": "_fastparse_asan.so"}
_flavor = os.environ.get("TPUSTREAM_NATIVE_FLAVOR", "default")
if _flavor not in _FLAVORS:
    _flavor = "default"
_MAKE_TARGET = _FLAVORS[_flavor]
_SO = os.path.join(_HERE, _MAKE_TARGET)
_lock = threading.Lock()
_lib = None
_tried = False
_build_error: Optional[str] = None


def _tail(text: bytes, limit: int = 400) -> str:
    s = text.decode("utf-8", "replace").strip()
    return s[-limit:] if len(s) > limit else s


def _build() -> bool:
    """Build the .so, Makefile first, then a portable g++ fallback.

    The Makefile carries the tuned flags (-march=native); the fallback
    drops them so a host whose toolchain rejects the tuned line still
    gets A native parser rather than none. Each attempt writes a temp
    name next to ``_SO`` and renames it into place, so a concurrent
    loader (another test worker) never dlopens a half-written file.
    Never raises: on failure the last compiler stderr is kept in
    ``_build_error`` for the executor's flight breadcrumb and the numpy
    path takes over."""
    global _build_error
    src = os.path.join(_HERE, "fastparse.cpp")
    tmp = f"{_SO}.{os.getpid()}.tmp"
    if _flavor == "asan":
        fallback = [
            "g++", "-O1", "-g", "-fno-omit-frame-pointer",
            "-fsanitize=address,undefined", "-shared", "-fPIC",
            "-std=c++17", "-pthread", src, "-o", tmp,
        ]
    else:
        fallback = [
            "g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
            src, "-o", tmp,
        ]
    attempts = [
        ["make", "-B", "-C", _HERE, _MAKE_TARGET, f"OUT={tmp}"],
        fallback,
    ]
    errors = []
    try:
        for cmd in attempts:
            try:
                subprocess.run(cmd, check=True, capture_output=True)
                os.replace(tmp, _SO)
                _build_error = None
                return True
            except subprocess.CalledProcessError as e:
                errors.append(f"{cmd[0]}: {_tail(e.stderr or e.stdout or b'')}")
            except Exception as e:
                errors.append(f"{cmd[0]}: {e}")
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    _build_error = "; ".join(errors) or "unknown build failure"
    return False


def build_error() -> Optional[str]:
    """Why the native parser is unavailable (None when it is, or when
    no build has been attempted yet)."""
    return _build_error


def _load():
    global _lib, _tried, _build_error
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not os.path.exists(_SO) or os.path.getmtime(_SO) < os.path.getmtime(
            os.path.join(_HERE, "fastparse.cpp")
        ):
            if not _build():
                return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError as e:
            # a stale .so from another toolchain (missing GLIBCXX
            # symbols, wrong arch) dlopen-fails even though it is newer
            # than the source: rebuild once against THIS toolchain
            if not _build():
                _build_error = f"dlopen: {e}; rebuild: {_build_error}"
                return None
            try:
                lib = ctypes.CDLL(_SO)
            except OSError as e2:
                _build_error = f"dlopen after rebuild: {e2}"
                return None
        lib.tsp_table_new.restype = ctypes.c_void_p
        lib.tsp_table_free.argtypes = [ctypes.c_void_p]
        lib.tsp_table_size.argtypes = [ctypes.c_void_p]
        lib.tsp_table_size.restype = ctypes.c_int64
        lib.tsp_table_get.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_char_p,
            ctypes.c_int64,
        ]
        lib.tsp_table_get.restype = ctypes.c_int64
        lib.tsp_parse.argtypes = [
            ctypes.c_char_p,
            ctypes.c_int64,
            ctypes.c_char,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.tsp_parse.restype = ctypes.c_int64
        try:
            lib.tsp_parse_mt.argtypes = lib.tsp_parse.argtypes + [
                ctypes.c_int32
            ]
            lib.tsp_parse_mt.restype = ctypes.c_int64
        except AttributeError:
            # stale pre-MT .so: keep the graceful-fallback contract
            _build_error = "stale _fastparse.so missing tsp_parse_mt"
            return None
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def build_flavor() -> str:
    """The build flavor this process selected ("default" or "asan", via
    TPUSTREAM_NATIVE_FLAVOR) — named in the executor's
    ``native_parse_ready`` flight breadcrumb so a postmortem (or a
    sanitizer CI lane) shows which kernel actually ran."""
    return _flavor


class NativeTable:
    """A C-side intern table mirrored into a Python StringTable.

    Native ids are remapped to the Python table's ids after every parse,
    so literals interned Python-side (e.g. by device-chain string
    comparisons) and natively-parsed keys share one id namespace.
    """

    def __init__(self, py_table):
        lib = _load()
        self._lib = lib
        self.ptr = lib.tsp_table_new()
        self.py_table = py_table
        self._remap = _Remap()

    def __del__(self):  # pragma: no cover - interpreter teardown
        try:
            self._lib.tsp_table_free(self.ptr)
        except Exception:
            pass

    def __len__(self) -> int:
        return len(self._remap)

    def sync(self) -> np.ndarray:
        """Extend the remap for the native ids interned since the last
        call; returns the int32 remap (native id -> python id) as a view
        of the table's buffer, so a batch of seen keys costs no work
        proportional to the table's size."""
        lib = self._lib
        n = lib.tsp_table_size(self.ptr)
        have = len(self._remap)
        if n > have:
            buf = ctypes.create_string_buffer(4096)
            ids = []
            for i in range(have, n):
                ln = lib.tsp_table_get(self.ptr, i, buf, 4096)
                s = buf.raw[: min(ln, 4096)].decode("utf-8", "replace")
                ids.append(self.py_table.intern(s))
            self._remap.extend(ids)
        return self._remap.view()


_NO_SPAN = contextlib.nullcontext()


def _no_span(kind: str, step: int):
    return _NO_SPAN


class NativeParser:
    """Parses a byte buffer of lines into columns per a base-field spec."""

    def __init__(self, sep: str, specs, py_tables):
        """specs: list of (field_idx, kind, tz_hours); py_tables aligned
        (StringTable for KIND_STR outputs, else None)."""
        self._lib = _load()
        if self._lib is None:
            raise RuntimeError("native parser unavailable")
        self.sep = sep.encode()[0:1]
        self.specs = list(specs)
        n = len(self.specs)
        self._field = (ctypes.c_int32 * n)(*[s[0] for s in self.specs])
        self._kind = (ctypes.c_int32 * n)(*[s[1] for s in self.specs])
        self._tz = (ctypes.c_int32 * n)(*[s[2] for s in self.specs])
        self.tables: List[Optional[NativeTable]] = [
            NativeTable(t) if s[1] == KIND_STR else None
            for s, t in zip(self.specs, py_tables)
        ]
        self._tbl_ptrs = (ctypes.c_void_p * n)(
            *[t.ptr if t is not None else None for t in self.tables]
        )

    def parse(self, data: bytes, max_rows: int, threads: Optional[int] = None,
              tracer=None, step: int = -1, new_ids=None):
        """Parse into fresh numpy columns. ``threads`` > 1 uses the
        chunked multi-threaded kernel (identical output, including
        intern-id assignment order); default: TPUSTREAM_PARSE_THREADS or
        the core count, engaged only for buffers >= 1 MiB. With a
        ``tracer`` (obs/tracing.py) the kernel call records a
        ``parse.native`` span and the id remap a ``parse.remap`` span,
        both for ``step``: one span call each per batch, nothing per
        record. ``new_ids`` (an obs counter) is bumped once per batch by
        the native ids this batch appended to the remaps."""
        if threads is None:
            try:
                threads = int(
                    os.environ.get(
                        "TPUSTREAM_PARSE_THREADS", os.cpu_count() or 1
                    )
                )
            except ValueError:
                threads = os.cpu_count() or 1
        threads = max(1, min(int(threads), 64))
        n = len(self.specs)
        cols = []
        ptrs = (ctypes.c_void_p * n)()
        for i, (fi, kind, tz) in enumerate(self.specs):
            if kind == KIND_STR:
                c = np.empty(max_rows, dtype=np.int32)
            elif kind == KIND_F64:
                c = np.empty(max_rows, dtype=np.float64)
            else:
                c = np.empty(max_rows, dtype=np.int64)
            cols.append(c)
            ptrs[i] = c.ctypes.data_as(ctypes.c_void_p)
        bad = ctypes.c_int64(0)
        span = tracer.span if tracer is not None else _no_span
        with span("parse.native", step):
            rows = self._lib.tsp_parse_mt(
                data,
                len(data),
                self.sep,
                n,
                self._field,
                self._kind,
                self._tz,
                self._tbl_ptrs,
                ptrs,
                max_rows,
                ctypes.byref(bad),
                max(1, threads),
            )
        out = []
        added = 0
        with span("parse.remap", step):
            for c, t in zip(cols, self.tables):
                c = c[:rows]
                if t is not None:
                    have = len(t)
                    remap = t.sync()
                    added += len(remap) - have
                    c = remap[c] if len(remap) else c
                out.append(c)
        if new_ids is not None:
            new_ids.inc(added)
        return out, int(bad.value)
