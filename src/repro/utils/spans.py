"""Named host spans: in the profiler's trace, and in a recorder on demand.

Every span opens a ``jax.profiler.TraceAnnotation``, so a run under
``jax.profiler.trace(dir)`` shows the trainer's, loader's and tuner's
spans (``train.*``, ``loader.*``, ``tune.*``) in XProf beside the device
operations.  With no profiler running an annotation costs about a
microsecond.

``recording()`` installs a :class:`Recorder` for the length of a ``with``
block; while one is installed each span also appends one
:class:`Record`.  Records are stamped with ``time.time_ns()``: the
realtime clock, which is the clock the profiler stamps its host events
with, so records, the trace's host spans and its device operations share
one clock.  The record lies inside its annotation: it starts after the
annotation opens and ends before it closes.

Tracing is on when a profiler trace runs or a recorder is installed;
there is no other switch.

    with spans.recording() as rec:
        trainer.run()
    rec.seconds("train.tune")
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Any, Dict, Iterator, List, NamedTuple, Optional

import jax

_recorder: Optional["Recorder"] = None
_local = threading.local()


class Record(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    thread: int
    parent: Optional[str]
    attrs: Dict[str, Any]

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


class Recorder:
    """The records of every span closed while it is installed, from any
    thread, in the order they closed."""

    def __init__(self):
        self._lock = threading.Lock()
        self._records: List[Record] = []

    def add(self, record: Record) -> None:
        with self._lock:
            self._records.append(record)

    @property
    def records(self) -> List[Record]:
        with self._lock:
            return list(self._records)

    def named(self, name: str) -> List[Record]:
        return [r for r in self.records if r.name == name]

    def seconds(self, name: str) -> float:
        """Summed duration of every span called ``name``."""
        return sum(r.seconds for r in self.named(name))

    def counts(self) -> Dict[str, int]:
        """Spans closed per name: the program's event counters."""
        return dict(collections.Counter(r.name for r in self.records))


def _stack() -> List[str]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class _Recorded:
    """An annotation whose span is also recorded."""

    __slots__ = ("name", "attrs", "_annotation", "_recorder", "_parent",
                 "_start")

    def __init__(self, name: str, attrs: Dict[str, Any], annotation,
                 recorder: Recorder):
        self.name = name
        self.attrs = attrs
        self._annotation = annotation
        self._recorder = recorder

    # No Python-level call between an annotation's edge and the clock
    # read beside it: the interpreter may switch threads at a call, and
    # the record would drift from its annotation for as long as another
    # thread holds the interpreter.  A switch can still fall right after
    # a C call returns, so on a busy thread a record may lag its
    # annotation by that much.
    def __enter__(self) -> "_Recorded":
        self._annotation.__enter__()
        self._start = time.time_ns()
        stack = _stack()
        self._parent = stack[-1] if stack else None
        stack.append(self.name)
        return self

    def __exit__(self, *exc) -> None:
        _stack().pop()
        end = time.time_ns()
        self._annotation.__exit__(*exc)
        self._recorder.add(Record(self.name, self._start, end,
                                  threading.get_ident(), self._parent,
                                  self.attrs))


def span(name: str, **attrs):
    """A span called ``name``, as a context manager; ``attrs`` go into
    the trace event and the record."""
    annotation = jax.profiler.TraceAnnotation(name, **attrs)
    rec = _recorder
    return annotation if rec is None else _Recorded(name, attrs,
                                                    annotation, rec)


def step_span(name: str, step: int):
    """A training step's outer span: a ``StepTraceAnnotation``, so XProf's
    step view groups by step.  The record carries ``step``."""
    annotation = jax.profiler.StepTraceAnnotation(name, step_num=step)
    rec = _recorder
    return annotation if rec is None else _Recorded(name, {"step": step},
                                                    annotation, rec)


@contextlib.contextmanager
def recording() -> Iterator[Recorder]:
    """Install a fresh :class:`Recorder` for the block and yield it."""
    global _recorder
    previous, rec = _recorder, Recorder()
    _recorder = rec
    try:
        yield rec
    finally:
        _recorder = previous
