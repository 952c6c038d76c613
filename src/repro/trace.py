"""Spans and counters of the program, in memory and on the profiler's clock.

A span is one timed piece of work at a layer boundary::

    with trace.job("serve.job"):            # the root of one job
        with trace.span("serve.prefill") as s:
            ...
            trace.count("rows", 8)          # onto the innermost open span
        prefill_s = trace.seconds(s)

While the profiler runs, each span opens a
``jax.profiler.TraceAnnotation`` of its name, so that it lands on the
host plane of the same trace as the device's operations, on one clock.
In memory a job keeps one ``Record`` per span: its name, start and end
on the monotonic clock (``time.perf_counter_ns``), its place in the job
and its parent's, and the counters added under it. A span belongs to the
job open on its thread; a span opened where no job is open goes to the
profiler alone, and nothing of it is kept.

Recording is always on, so it stays cheap: no numpy and no lock on the
path of a span, and no object kept that the garbage collector tracks
(``Job``). Memory is bounded: a ``Recorder`` keeps the process's first
job and the last few. While a job is open, JAX's compile requests and
its persistent compile cache's hits and misses are counted onto the
innermost open span, so a compile inside a timed loop shows where it
happened.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

import jax
from jax.profiler import TraceAnnotation

# JAX's compile-cache events (``jax.monitoring``) -> counter names
COMPILE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "compile_requests",
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}

_now = time.perf_counter_ns


class Record(NamedTuple):
    """A finished span. ``pos`` numbers a job's spans in the order they
    started (the root is 0); ``parent`` is the parent's ``pos``, or None
    for the root."""

    name: str
    start_ns: int
    end_ns: int
    pos: int
    parent: Optional[int]
    counts: Optional[Dict[str, float]]


class Job:
    """The records of one job's spans.

    A job keeps its spans in flat lists of numbers and strings, indexed
    by ``pos``, which the garbage collector does not track: recording
    adds no work to the collections that run inside a timed loop."""

    __slots__ = ("id", "wall_offset_ns", "_names", "_parents", "_starts",
                 "_ends", "_count_pos", "_count_names", "_count_values")

    def __init__(self, job_id: int) -> None:
        self.id = job_id
        # time.time_ns() - time.perf_counter_ns() at the job's start:
        # added to a record's times, it puts them on the wall clock, the
        # clock a profiler trace's start time is given on
        self.wall_offset_ns = time.time_ns() - _now()
        self._names: List[str] = []
        self._parents: List[Optional[int]] = []
        self._starts: List[int] = []
        self._ends: List[int] = []
        self._count_pos: List[int] = []
        self._count_names: List[str] = []
        self._count_values: List[float] = []

    @property
    def spans(self) -> List[Record]:
        """Every record, in the order the spans started."""
        counts: Dict[int, Dict[str, float]] = {}
        for pos, name, n in zip(self._count_pos, self._count_names,
                                self._count_values):
            counts.setdefault(pos, {})[name] = n
        return [Record(*fields, pos, parent, counts.get(pos))
                for pos, (*fields, parent) in enumerate(zip(
                    self._names, self._starts, self._ends, self._parents))]

    @property
    def root(self) -> Record:
        return self.spans[0]

    def named(self, name: str) -> List[Record]:
        """The records called ``name``, in the order they started."""
        return [r for r in self.spans if r.name == name]


class Span:
    """An open span; leaving its ``with`` block records it in its job."""

    __slots__ = ("name", "start_ns", "end_ns", "pos", "parent", "job",
                 "counts", "_rec", "_ann")

    def __init__(self, rec: "Recorder", name: str,
                 job: Optional[Job] = None) -> None:
        self.name = name
        self.job = job
        self.parent: Optional[int] = None
        self.counts: Optional[Dict[str, float]] = None
        self._rec = rec
        self._ann = None

    def __enter__(self) -> "Span":
        stack = self._rec._local.stack
        job = self.job
        if stack:
            top = stack[-1]
            if job is None:
                job = self.job = top.job
            if job is top.job:
                self.parent = top.pos
        if job is not None:
            self.pos = len(job._names)
            job._names.append(self.name)
            job._parents.append(self.parent)
            job._starts.append(0)
            job._ends.append(0)
            stack.append(self)
        if TraceAnnotation.is_enabled():
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
        self.start_ns = _now()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = _now()
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        job = self.job
        if job is not None:
            self._rec._local.stack.pop()
            pos = self.pos
            job._starts[pos] = self.start_ns
            job._ends[pos] = self.end_ns
            if self.counts:
                for name, n in self.counts.items():
                    job._count_pos.append(pos)
                    job._count_names.append(name)
                    job._count_values.append(n)
            if pos == 0:
                self._rec._keep(job)

    def add(self, name: str, n: float = 1) -> None:
        """Add ``n`` to this span's counter ``name``."""
        if self.counts is None:
            self.counts = {name: n}
        else:
            self.counts[name] = self.counts.get(name, 0) + n


class _Stack(threading.local):
    def __init__(self) -> None:
        self.stack: List[Span] = []


class Recorder:
    """Keeps the first job recorded and the last ``keep`` ones."""

    def __init__(self, keep: int = 4) -> None:
        self._local = _Stack()
        self._ids = itertools.count(1)
        self.first: Optional[Job] = None
        self.recent: collections.deque = collections.deque(maxlen=keep)

    def job(self, name: str) -> Span:
        """The root span of a new job (enter it with ``with``)."""
        return Span(self, name, Job(next(self._ids)))

    def span(self, name: str) -> Span:
        return Span(self, name)

    def count(self, name: str, n: float = 1) -> None:
        """Add ``n`` to counter ``name`` of the innermost open span of
        this thread's job; dropped where no job is open."""
        stack = self._local.stack
        if stack:
            stack[-1].add(name, n)

    def jobs(self) -> List[Job]:
        """The jobs kept, oldest first."""
        rest = [j for j in self.recent if j is not self.first]
        return ([self.first] if self.first is not None else []) + rest

    def _keep(self, job: Job) -> None:
        if self.first is None:
            self.first = job
        self.recent.append(job)


RECORDER = Recorder()
_listening = False


def _on_compile_event(event: str, **_) -> None:
    name = COMPILE_EVENTS.get(event)
    if name is not None:
        RECORDER.count(name)


def job(name: str) -> Span:
    """The root span of a new job in the process's recorder."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_listener(_on_compile_event)
        _listening = True
    return RECORDER.job(name)


def span(name: str) -> Span:
    """A span in the process's recorder."""
    return Span(RECORDER, name)


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to counter ``name`` of the innermost open span."""
    RECORDER.count(name, n)


def jobs() -> List[Job]:
    """The jobs the process's recorder kept: its first and the last few."""
    return RECORDER.jobs()


def seconds(s) -> float:
    """A span's or a record's duration in seconds."""
    return (s.end_ns - s.start_ns) / 1e9


def summary(j: Job) -> Dict[str, Dict[str, float]]:
    """Per span name, in the order the names first started: ``count``,
    ``total_s``, ``mean_us`` and ``max_us``, and the sum of each counter
    added under spans of that name."""
    out: Dict[str, Dict[str, float]] = {}
    for name, start, end in zip(j._names, j._starts, j._ends):
        row = out.setdefault(name, {"count": 0, "total_s": 0.0,
                                    "mean_us": 0.0, "max_us": 0.0})
        row["count"] += 1
        row["total_s"] += (end - start) / 1e9
        row["max_us"] = max(row["max_us"], (end - start) / 1e3)
    for pos, name, n in zip(j._count_pos, j._count_names, j._count_values):
        row = out[j._names[pos]]
        row[name] = row.get(name, 0) + n
    for row in out.values():
        row["mean_us"] = row["total_s"] * 1e6 / row["count"]
    return out
