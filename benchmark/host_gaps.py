"""Name the device's idle gaps by what the host was doing in them.

While a profiler capture is open the program enters
``jax.profiler.TraceAnnotation`` for every loop-held stage (``loop.*``),
for the verify threads' stages (``verify.*``) and for garbage collections
(``gc.pause``), so they land in the trace's ``/host:CPU`` plane, one line
per thread, on the clock of the device planes. This module reads them:

  1. per thread, nested annotations are flattened so that each instant
     belongs to the innermost one (a stage's SELF time, as the program's
     accumulators charge it);
  2. for each gap between two ``XLA Modules`` events of a chip, each
     stage's overlap with the gap is summed over all host threads;
  3. the gap is named by the WORKING stage with the largest overlap where
     working stages cover at least half of it; else by the stage with the
     largest overlap of any kind where annotations cover half of it; else
     ``unannotated``.

``verify.collect`` is the one stage that is a wait by construction (the
dispatcher with no pile it may dispatch): it spans nearly every gap, so
it names a gap only where no work does. ``wait_only`` in the shares is
the idle time during which no thread was in a working stage: the loop's
thread was idle or in code no stage covers.

benchmark/run.py and trace_reduce.py were not PR 26's to edit, so nothing
in the driver's path calls this yet and its result line still reads
``between_modules``: the attribution is a builder's reading until a
benchmark PR takes ``idle_gaps`` in ``trace_reduce.reduce_file`` from
``attribute(path)`` (PERF.md sec. 7). Until then ``attribute`` takes any
capture's ``*.xplane.pb`` (a node's ``--device-profile`` capture stays on
disk; run.py removes its own, so PR 26's readings came from an
uncommitted script that wrapped ``reduce_file`` for the run).

A trace with no annotations (the parent commit, the recorded round-5
trace) reads ``unannotated`` in every gap and raises nothing.
"""

from __future__ import annotations

import bisect
import re

import trace_reduce

HOST_PLANE = "/host:CPU"
# the program's own annotations among whatever else the host tracer wrote
STAGE = re.compile(r"(loop|verify|gc)\.[a-z0-9_.]+")
WAITS = frozenset({"verify.collect"})
UNANNOTATED = "unannotated"


def flatten(events: list) -> list:
    """``[(start, end, stage)]`` of one thread, possibly nested, to
    disjoint ``[(start, end, stage)]`` in which each instant belongs to
    the innermost event. A child is cut to its parent's end."""
    out: list = []
    stack: list = []  # (end, stage), outermost first
    cursor = 0.0

    def close(upto: float) -> None:
        nonlocal cursor
        while stack and stack[-1][0] <= upto:
            end, stage = stack.pop()
            if end > cursor:
                out.append((cursor, end, stage))
                cursor = end

    for start, end, stage in sorted(events, key=lambda e: (e[0], -e[1])):
        close(start)
        if stack:
            if start > cursor:
                out.append((cursor, start, stack[-1][1]))
            end = min(end, stack[-1][0])
        cursor = max(cursor, start) if stack else start
        if end > cursor:
            stack.append((end, stage))
    close(float("inf"))
    return out


def host_segments(data) -> list:
    """One flattened segment list per host thread that has annotations."""
    threads = []
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            events = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in line.events if STAGE.fullmatch(e.name)]
            if events:
                threads.append(flatten(events))
    return threads


def device_gaps(data) -> list:
    """``[(start, end)]`` between consecutive module events of each chip,
    as trace_reduce.reduce_file finds them."""
    gaps = []
    for plane in data.planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PLANE):
            continue
        lines = {line.name: line for line in plane.lines}
        if trace_reduce.MODULES not in lines:
            continue
        modules = sorted((e.start_ns, e.start_ns + e.duration_ns)
                         for e in lines[trace_reduce.MODULES].events)
        if not modules:
            continue
        reach = modules[0][1]
        for start, end in modules[1:]:
            if start > reach:
                gaps.append((reach, start))
            reach = max(reach, end)
    return sorted(gaps)


def _overlaps(gaps: list, segments: list):
    """(gap index, segment, overlap) for each segment that meets a gap."""
    starts = [g[0] for g in gaps]
    for seg in segments:
        i = max(0, bisect.bisect_right(starts, seg[0]) - 1)
        while i < len(gaps) and gaps[i][0] < seg[1]:
            lo, hi = max(seg[0], gaps[i][0]), min(seg[1], gaps[i][1])
            if hi > lo:
                yield i, (lo, hi, seg[2]), hi - lo
            i += 1


def attribute_gaps(gaps: list, threads: list) -> dict:
    """Name each of ``gaps`` from the threads' flattened segments."""
    by_stage = [dict() for _ in gaps]   # stage -> ns, summed over threads
    work = [[] for _ in gaps]           # intervals of working stages
    covered = [[] for _ in gaps]        # intervals of any stage
    for segments in threads:
        for i, (lo, hi, stage), ns in _overlaps(gaps, segments):
            by_stage[i][stage] = by_stage[i].get(stage, 0.0) + ns
            covered[i].append((lo, hi))
            if stage not in WAITS:
                work[i].append((lo, hi))

    named = []
    totals: dict = {}
    idle_ns = work_ns = covered_ns = 0.0
    for (start, end), stages, w, c in zip(gaps, by_stage, work, covered):
        length = end - start
        w_ns, c_ns = trace_reduce._union_ns(w), trace_reduce._union_ns(c)
        idle_ns += length
        work_ns += w_ns
        covered_ns += c_ns
        for stage, ns in stages.items():
            totals[stage] = totals.get(stage, 0.0) + ns
        working = {s: ns for s, ns in stages.items() if s not in WAITS}
        if working and 2 * w_ns >= length:
            name = max(working, key=working.get)
        elif stages and 2 * c_ns >= length:
            name = max(stages, key=stages.get)
        else:
            name = UNANNOTATED
        named.append((length, name))
    named.sort(key=lambda g: -g[0])
    share = (lambda ns: 100.0 * ns / idle_ns) if idle_ns else (lambda ns: 0.0)
    return {
        "gaps": len(gaps),
        "idle_between_modules_s": idle_ns / 1e9,
        "idle_gaps": [[name, ns / 1e9]
                      for ns, name in named[:trace_reduce.TOP]],
        # a stage's overlap with all idle time, summed over threads, as a
        # share of it: two busy threads can make these pass 100 together
        "idle_share_by_stage": {
            stage: share(ns)
            for stage, ns in sorted(totals.items(), key=lambda kv: -kv[1])},
        "unannotated_share": share(idle_ns - covered_ns),
        "wait_only_share": share(covered_ns - work_ns),
    }


def attribute(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return attribute_gaps(device_gaps(data), host_segments(data))
