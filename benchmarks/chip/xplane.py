"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` file into plain lists of
``[name, start_ns, end_ns]``: the operations of each TPU device, the
programs (XLA modules) each device ran, and the Python functions of the
host thread that drove them. Everything else here works on those lists, so
the tests check it on a small recorded excerpt.
"""

from __future__ import annotations

import bisect
import collections
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]          # name, start ns, end ns

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(path: str, marker: str = "run_job") -> Dict:
    """{"devices": {plane: {"ops": [...], "modules": [...]}},
    "host": [...]} from a trace file. ``host`` holds the Python tracer's
    events of the thread that drove the job: the line on which a function
    named ``marker`` ran (the harness's ``run_job``); it is empty where
    the Python tracer was off."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            devices[plane.name] = {
                key: _events(lines[name].events) if name in lines else []
                for key, name in (("ops", OPS_LINE),
                                  ("modules", MODULES_LINE))}
        elif plane.name.startswith("/host:") and not host:
            for line in plane.lines:
                evs = _events(line.events)
                if any(n.startswith("$") and n.endswith(f" {marker}")
                       for n, _, _ in evs):
                    host = evs
                    break
    return {"devices": devices, "host": host}


def _events(events: Iterable) -> List[Event]:
    return [(e.name, int(e.start_ns), int(e.end_ns)) for e in events]


def union_ns(events: Sequence[Event], lo: Optional[int] = None,
             hi: Optional[int] = None) -> int:
    """Length of the union of the events' intervals, clipped to [lo, hi]."""
    spans = sorted((s if lo is None else max(s, lo),
                    e if hi is None else min(e, hi)) for _, s, e in events)
    total, cur_s, cur_e = 0, None, None
    for s, e in spans:
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(events: Sequence[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Intervals in [lo, hi] in which none of the events runs."""
    gaps, cursor = [], lo
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if e <= lo or s >= hi:
            continue
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < hi:
        gaps.append((cursor, hi))
    return gaps


def programs(modules: Sequence[Event], name: str) -> List[Event]:
    """Runs of the jitted program ``name`` (XLA names its module
    ``jit_<name>(<fingerprint>)``), in time order."""
    hits = [m for m in modules if m[0].split("(")[0] == f"jit_{name}"]
    return sorted(hits, key=lambda m: m[1])


def span_idle_share(ops: Sequence[Event], lo: int, hi: int) -> float:
    """1 - (union of op intervals within [lo, hi]) / (hi - lo)."""
    return 1.0 - union_ns(ops, lo, hi) / (hi - lo)


def mean_gap_ns(runs: Sequence[Event]) -> Optional[float]:
    """Mean time from the end of one run to the start of the next."""
    if len(runs) < 2:
        return None
    return sum(b[1] - a[2] for a, b in zip(runs, runs[1:])) / (len(runs) - 1)


def self_times(ops: Sequence[Event]) -> Dict[str, int]:
    """Nanoseconds each operation name ran outside the operations nested
    in it (a loop's body runs inside the loop's own event), keyed by the
    HLO instruction's name: the event name up to " = "."""
    tot: Dict[str, int] = collections.Counter()
    stack: List[List] = []               # [name, end, self ns] of open ops
    for name, s, e in sorted(ops, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            done = stack.pop()
            tot[done[0]] += done[2]
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name.split(" = ")[0], e, e - s])
    for name, _, ns in stack:
        tot[name] += ns
    return tot


def top_ops(ops: Sequence[Event], n: int = 10) -> List[List]:
    """The ``n`` operation names with the most device self time, in
    seconds."""
    tot = self_times(ops)
    return [[name, ns / 1e9] for name, ns in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def host_label(host: Sequence[Event], starts: Sequence[int], t: int) -> str:
    """The innermost Python function (one defined in a ``.py`` file) that
    the host thread was running at time ``t``. ``host`` is sorted by start
    and ``starts`` holds its start times. Calls nest, so the innermost
    open call is the latest-started one that has not ended yet.
    "unattributed" where the tracer shows none."""
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        name, s, e = host[i]
        if e > t and ".py:" in name:
            return name.lstrip("$")
    return "unattributed"


def labelled_gaps(ops: Sequence[Event], host: Sequence[Event], lo: int,
                  hi: int, n: int = 10) -> List[List]:
    """Device idle time in [lo, hi], summed by what the host was doing in
    the middle of each gap; the ``n`` largest, in seconds."""
    tot: Dict[str, int] = collections.Counter()
    host = sorted(host, key=lambda ev: ev[1])
    starts = [ev[1] for ev in host]
    for s, e in idle_gaps(ops, lo, hi):
        tot[host_label(host, starts, (s + e) // 2)] += e - s
    return [[label, ns / 1e9] for label, ns in tot.most_common(n)]
