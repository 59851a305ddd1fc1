"""The program's own spans in a `jax.profiler` trace, and what the host
did while the device was idle.

Since PR 25 the program marks every layer boundary with
`global_tracer.layer(name)`, which enters a
`jax.profiler.TraceAnnotation("nomad.<name>")`: under a profiler session
each span is an event of a `/host:*` plane, on the line of the thread
that ran it, nested as the spans nest, and on the same clock as the
`XLA Ops` of `/device:TPU:<n>` (`tests/test_host_spans.py` checks that
on a recorded trace: every `jit_solve_kernel` module event ends inside
a `nomad.solve.dispatch` or `nomad.solve.fetch` span).

An idle gap is labelled with the LEAF span that covers most of it: per
thread, the innermost open `nomad.*` span at each instant (a parent
only counts where no child of it is open); the seconds of every leaf
inside the gap are summed by name over the threads, and the largest
sum names the gap.  A working span beats a wait span (one whose last
name part says `wait`: `worker.dequeue_wait`, `plan.result_wait`,
`worker.wait_index`), whatever their sums: a thread that waits says
nothing about what the others do.  While the collector runs
(`gc.pause`) no other thread does, so that stretch counts for it alone.
`unattributed` only where no program span overlaps the gap.  A trace of
a program that writes no `nomad.*` event (every trace before PR 25)
reads `unattributed` throughout.

`run.py per_layer` prints `idle_gaps` as `breakdown.idle_gaps`, and
`reduce_idle_attributed` is `layers.REDUCERS["idle_attributed"]`.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Tuple

import xplane

HOST_PLANE = re.compile(r"^/host:")
PREFIX = "nomad."
UNATTRIBUTED = "unattributed"
#: spans during which no other thread runs (CPython collects with the
#: interpreter lock held)
STOP_THE_WORLD = ("gc.pause",)

Span = Tuple[str, str, float, float]    # thread, name, start_s, end_s
Leaf = Tuple[str, float, float]         # name, start_s, end_s


def host_spans(profile) -> List[Span]:
    """Every `nomad.*` event of the host planes: (thread line, span
    name without the prefix, start_s, end_s)."""
    out = []
    for plane in profile.planes:
        if not HOST_PLANE.match(plane.name):
            continue
        for i, ln in enumerate(plane.lines):
            thread = f"{plane.name}/{i}:{ln.name}"
            for ev in ln.events:
                if ev.name.startswith(PREFIX):
                    s = ev.start_ns * 1e-9
                    out.append((thread, ev.name[len(PREFIX):], s,
                                s + ev.duration_ns * 1e-9))
    return out


def leaves(spans: List[Span]) -> List[Leaf]:
    """The stretches in which a span is its thread's innermost open
    one, sorted by start.  The spans of one thread nest (each is a
    `with` block), so a stack walk over them by start suffices."""
    by_thread: Dict[str, list] = {}
    for thread, name, s, e in spans:
        by_thread.setdefault(thread, []).append((s, -e, name))
    out: List[Leaf] = []
    for evs in by_thread.values():
        evs.sort()
        stack: List[list] = []          # [name, end, resume]

        def close_until(t: float) -> None:
            while stack and stack[-1][1] <= t:
                name, end, resume = stack.pop()
                if end > resume:
                    out.append((name, resume, end))
                if stack:
                    stack[-1][2] = max(stack[-1][2], end)

        for s, neg_e, name in evs:
            close_until(s)
            if stack and s > stack[-1][2]:
                out.append((stack[-1][0], stack[-1][2], s))
            stack.append([name, -neg_e, s])
        close_until(float("inf"))
    # a stop-the-world span stops the other threads too: whatever they
    # had open did not run then, so those stretches are not theirs
    stops = _union([(s, e) for n, s, e in out if n in STOP_THE_WORLD])
    if stops:
        out = [leaf for n, s, e in out for leaf in
               ([(n, s, e)] if n in STOP_THE_WORLD else
                [(n, lo, hi) for lo, hi in _minus((s, e), stops)])]
    out.sort(key=lambda leaf: leaf[1])
    return out


def _minus(iv: Tuple[float, float], holes: List[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    """`iv` without the sorted, disjoint `holes`."""
    lo, hi = iv
    out = []
    for s, e in holes[bisect.bisect_right([h[1] for h in holes], lo):]:
        if s >= hi:
            break
        if s > lo:
            out.append((lo, s))
        lo = max(lo, e)
    if lo < hi:
        out.append((lo, hi))
    return out


def is_wait(name: str) -> bool:
    return "wait" in name.rsplit(".", 1)[-1]


def label(gap: Tuple[float, float], leaf_list: List[Leaf],
          starts: Optional[List[float]] = None) -> str:
    """The leaf span that covers most of `gap` (module docstring)."""
    g0, g1 = gap
    if starts is None:
        starts = [s for _n, s, _e in leaf_list]
    total: Dict[str, float] = {}
    # leaves of several threads overlap, so every leaf that starts
    # before the gap ends is a candidate; those ending before it starts
    # drop out on the overlap test
    for name, s, e in leaf_list[:bisect.bisect_left(starts, g1)]:
        lo, hi = max(s, g0), min(e, g1)
        if hi > lo:
            total[name] = total.get(name, 0.0) + (hi - lo)
    if not total:
        return UNATTRIBUTED
    working = {n: t for n, t in total.items() if not is_wait(n)}
    pick = working or total
    return max(sorted(pick), key=lambda n: pick[n])


def device_gaps(profile) -> List[Tuple[float, float]]:
    """(start_s, end_s) of every gap between device ops on the first
    chip, in time order."""
    planes = xplane.device_planes(profile)
    if not planes:
        return []
    iv = sorted((s, e) for _n, s, e in
                xplane.events(planes[0], xplane.OPS_LINE))
    gaps, end = [], None
    for s, e in iv:
        if end is not None and s > end:
            gaps.append((end, s))
        end = e if end is None else max(end, e)
    return gaps


def idle_gaps(profile, k: int = 10) -> List[list]:
    """The `k` longest gaps between device ops on the first chip,
    longest first, as `[label, seconds]`."""
    gaps = sorted(device_gaps(profile), key=lambda g: g[0] - g[1])[:k]
    leaf_list = leaves(host_spans(profile))
    starts = [s for _n, s, _e in leaf_list]
    return [[label(g, leaf_list, starts), g[1] - g[0]] for g in gaps]


def _union(intervals: List[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def idle_attributed_share(profile, waits: bool = True
                          ) -> Optional[float]:
    """Percent of the device's idle time (the gaps between its ops, from
    the first op to the last) that some `nomad.*` host span covers.
    With `waits` a thread parked in a wait span covers too (ISSUE 25's
    reading: a server with an idle worker reads near 100 whatever the
    others do); without, only the stretches in which some thread's
    innermost span is a working one.  None where no device op ran or
    none left a gap."""
    gaps = device_gaps(profile)
    idle = sum(e - s for s, e in gaps)
    if not idle:
        return None
    spans = host_spans(profile)
    cover = _union([(s, e) for _t, _n, s, e in spans] if waits else
                   [(s, e) for n, s, e in leaves(spans) if not is_wait(n)])
    ends = [e for _s, e in cover]
    covered = 0.0
    for g0, g1 in gaps:
        i = bisect.bisect_right(ends, g0)
        while i < len(cover) and cover[i][0] < g1:
            covered += min(cover[i][1], g1) - max(cover[i][0], g0)
            i += 1
    return 100.0 * covered / idle


def reduce_idle_attributed(spec: dict, obs) -> Optional[float]:
    """`layers.REDUCERS["idle_attributed"]`; the metric's file says
    whether wait spans cover (`waits`, default true)."""
    if obs.profile is None:
        return None
    return idle_attributed_share(obs.profile, bool(spec.get("waits", True)))


def leaf_seconds(profile) -> List[list]:
    """Seconds each span name was a thread's innermost open span, over
    the whole trace: the host's self time by layer."""
    total: Dict[str, float] = {}
    for name, s, e in leaves(host_spans(profile)):
        total[name] = total.get(name, 0.0) + (e - s)
    return [[n, t] for n, t in sorted(total.items(),
                                      key=lambda kv: -kv[1])]


def modules_ending_inside(profile, module_pattern: str,
                          span_names: Tuple[str, ...]) -> Tuple[int, int]:
    """(module events of the first chip matching `module_pattern` that
    end inside a host span named in `span_names`, all such events)."""
    planes = xplane.device_planes(profile)
    if not planes:
        return 0, 0
    rx = re.compile(module_pattern)
    cover = _union([(s, e) for _t, n, s, e in host_spans(profile)
                    if n in span_names])
    starts = [s for s, _e in cover]
    inside = n_all = 0
    for name, _s, end in xplane.events(planes[0], xplane.MODULES_LINE):
        if not rx.search(name):
            continue
        n_all += 1
        i = bisect.bisect_right(starts, end) - 1
        if i >= 0 and end <= cover[i][1]:
            inside += 1
    return inside, n_all


if __name__ == "__main__":
    # by hand: python benchmark/host_spans.py <file.xplane.pb>
    import json
    import sys
    prof = xplane.load(sys.argv[1])
    print(json.dumps({
        "idle_attributed_share": idle_attributed_share(prof),
        "idle_gaps": idle_gaps(prof),
        "leaf_seconds": leaf_seconds(prof),
        "solve_kernels_ending_inside_dispatch_or_fetch":
            modules_ending_inside(prof, "^jit_solve_kernel",
                                  ("solve.dispatch", "solve.fetch"))},
        indent=1))
