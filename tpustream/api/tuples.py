"""Positional tuples with Flink-style ``f0/f1/f2`` field access.

The reference jobs manipulate ``Tuple2``/``Tuple3`` values positionally
(e.g. ``value.f2 > 90`` at reference chapter1/.../Main.java:27-33); these
classes reproduce that surface. They are plain field containers: during
device tracing the fields hold jax scalars, on the host they hold Python
values, and the ``print()`` sink formats them Java-style as ``(a,b,c)``.
"""

from __future__ import annotations

from typing import Any, Iterator


class TupleBase:
    """Common behavior for fixed-arity positional tuples."""

    ARITY: int = 0
    _FIELDS: tuple = ()

    def __init__(self, *values: Any):
        if len(values) != self.ARITY:
            raise TypeError(
                f"{type(self).__name__} expects {self.ARITY} values, got {len(values)}"
            )
        for name, v in zip(self._FIELDS, values):
            object.__setattr__(self, name, v)

    # --- positional access -------------------------------------------------
    def __getitem__(self, i: int) -> Any:
        return getattr(self, self._FIELDS[i])

    def __setitem__(self, i: int, v: Any) -> None:
        setattr(self, self._FIELDS[i], v)

    def __iter__(self) -> Iterator[Any]:
        return (getattr(self, f) for f in self._FIELDS)

    def __len__(self) -> int:
        return self.ARITY

    def values(self) -> tuple:
        return tuple(self)

    # --- comparison / display ---------------------------------------------
    def __eq__(self, other: Any) -> bool:
        if isinstance(other, TupleBase):
            return self.values() == other.values()
        if isinstance(other, tuple):
            return self.values() == other
        return NotImplemented

    def __hash__(self) -> int:
        return hash(self.values())

    def __repr__(self) -> str:
        inner = ",".join(_java_str(v) for v in self)
        return f"({inner})"


def _java_str(v: Any) -> str:
    """Format one field the way Java's ``Tuple.toString`` would.

    Java prints ``Double.toString`` (80.5, 86.26666666666667) and longs
    without a decimal point — Python's ``repr`` matches for round-trippable
    doubles, and bools/ints need no massaging.
    """
    import numpy as np

    if isinstance(v, (bool,)):
        return "true" if v else "false"
    if isinstance(v, (float, np.floating)):
        return repr(float(v))
    if isinstance(v, (int, np.integer)):
        return str(int(v))
    return str(v)


_new = object.__new__


class Tuple2(TupleBase):
    ARITY = 2
    _FIELDS = ("f0", "f1")

    @staticmethod
    def trusted(f0, f1) -> "Tuple2":
        """``Tuple2(f0, f1)`` without the arity check and the generic
        field loop: the host's emit path builds one per alert row."""
        t = _new(Tuple2)
        t.f0 = f0
        t.f1 = f1
        return t


class Tuple3(TupleBase):
    ARITY = 3
    _FIELDS = ("f0", "f1", "f2")

    @staticmethod
    def trusted(f0, f1, f2) -> "Tuple3":
        """``Tuple3(f0, f1, f2)``, built as :meth:`Tuple2.trusted`."""
        t = _new(Tuple3)
        t.f0 = f0
        t.f1 = f1
        t.f2 = f2
        return t


class Tuple4(TupleBase):
    ARITY = 4
    _FIELDS = ("f0", "f1", "f2", "f3")

    @staticmethod
    def trusted(f0, f1, f2, f3) -> "Tuple4":
        """``Tuple4(f0, f1, f2, f3)``, built as :meth:`Tuple2.trusted`."""
        t = _new(Tuple4)
        t.f0 = f0
        t.f1 = f1
        t.f2 = f2
        t.f3 = f3
        return t


TUPLE_CLASSES = {2: Tuple2, 3: Tuple3, 4: Tuple4}


def make_tuple(*values: Any) -> TupleBase:
    cls = TUPLE_CLASSES.get(len(values))
    if cls is None:
        raise TypeError(f"unsupported tuple arity {len(values)}")
    return cls(*values)
