"""Per-layer numbers from the program's own spans: the `jbp.<op>` events
that `repro.core.dxt` puts on the profiler's `/host:CPU` plane, one per
`TRACER.span(op)`, on the clock of the device lines.

The window rule is `reduce_trace`'s: a span that overlaps the window is
the window's. The reader gives a number on every trace. A program with no
`jbp.*` span (one from before these spans, or one whose spans were lost
on the way to the profiler) explains none of its idle time, so its share
reads 100%. That the program's spans reach the trace is held by the
program's own tests.
"""
from __future__ import annotations

import bisect

PREFIX = "jbp."


def union(intervals) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clipped(view, events) -> list:
    """[start, end) of the events that overlap the window, cut to it."""
    return [(max(s, view.t0), min(e, view.t1)) for _, s, e in events
            if e > view.t0 and s < view.t1]


def subtract(intervals, cuts) -> list:
    """Sorted disjoint [start, end) `intervals` less the union `cuts`."""
    out = []
    for s, e in intervals:
        for cs, ce in cuts:
            if ce <= s or cs >= e:
                continue
            if cs > s:
                out.append([s, cs])
            s = max(s, ce)
            if s >= e:
                break
        if s < e:
            out.append([s, e])
    return out


def leaves(spans) -> list:
    """The spans inside which no other span lies, on any thread. An
    enclosing span (a whole save, a restore, a drain) does not explain
    its own time: only the work it waits on does."""
    spans = sorted(spans, key=lambda x: (x[1], -x[2]))
    starts = [s for _, s, _ in spans]
    out = []
    for i, (n, s, e) in enumerate(spans):
        j = bisect.bisect_right(starts, e)
        if not any(e2 <= e and (s2, e2) != (s, e)
                   for _, s2, e2 in spans[i + 1:j]):
            out.append((n, s, e))
    return out


def idle_unattributed_share(view):
    """Share, in percent, of the window's device-idle time (the window
    less the union of `XLA Modules` events) in which no leaf `jbp.*` span
    is open on any host thread; over all devices."""
    ours = [x for x in view.spans if x[0].startswith(PREFIX)
            and x[2] > view.t0 and x[1] < view.t1]
    covered = union(clipped(view, leaves(ours)))
    idle = bare = 0.0
    for d in view.devices:
        gaps = subtract([[view.t0, view.t1]],
                        union(clipped(view, d["modules"])))
        idle += length(gaps)
        bare += length(subtract(gaps, covered))
    return 100.0 * bare / idle if idle > 0 else 0.0
