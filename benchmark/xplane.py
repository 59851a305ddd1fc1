"""Reduction of a `jax.profiler` trace (`*.xplane.pb`) to device times.

What the trace of a TPU v5e looks like (looked at by hand, PR 24): one
plane per chip named `/device:TPU:<n>`; on it a line `XLA Modules` with
one event per execution of a compiled program, named
`<jit name>(<fingerprint>)`, and a line `XLA Ops` with one event per HLO
op executed, named by its HLO instruction (`%fusion.12 = ...` or
`fusion.12`); a `while` op's event spans its whole loop and its body's
ops are events of their own inside it, so "busy" is the UNION of op
intervals, never their sum.  Host threads are planes named `/host:*`.
A CPU-backend trace has no `/device:` plane: every reader here then
returns nothing and the harness prints no device metric.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

from stats import union_length

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]          # seconds


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def device_planes(profile) -> list:
    return [p for p in profile.planes if DEVICE_PLANE.match(p.name)]


def _line(plane, name: str):
    for ln in plane.lines:
        if ln.name == name:
            return ln
    return None


def events(plane, line_name: str) -> List[Tuple[str, float, float]]:
    """(name, start_s, end_s) of every event on the named line."""
    ln = _line(plane, line_name)
    if ln is None:
        return []
    out = []
    for ev in ln.events:
        s = ev.start_ns * 1e-9
        out.append((ev.name, s, s + ev.duration_ns * 1e-9))
    return out


def busy_seconds(profile) -> Optional[float]:
    """Seconds in which an operation ran on the device: the union of the
    op intervals of each chip, averaged over the chips."""
    planes = device_planes(profile)
    if not planes:
        return None
    per_chip = [union_length([(s, e) for _n, s, e in events(p, OPS_LINE)])
                for p in planes]
    return sum(per_chip) / len(per_chip)


def _inside(ops, spans: List[Interval]) -> List[Interval]:
    """Op intervals that start inside one of `spans` (sorted sweep)."""
    import bisect
    spans = sorted(spans)
    starts = [s for s, _e in spans]
    out = []
    for _n, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < spans[i][1]:
            out.append((s, e))
    return out


def program_seconds(profile, module_pattern: str) -> Optional[float]:
    """Device time of every op of the programs whose `XLA Modules` name
    matches: union of the op intervals inside those module events,
    averaged over the chips.  None where no such module ran."""
    rx = re.compile(module_pattern)
    planes = device_planes(profile)
    per_chip, found = [], False
    for p in planes:
        spans = [(s, e) for n, s, e in events(p, MODULES_LINE)
                 if rx.search(n)]
        if spans:
            found = True
        ops = events(p, OPS_LINE)
        per_chip.append(union_length(_inside(ops, spans)))
    if not found:
        return None
    return sum(per_chip) / len(per_chip)


def short_name(name: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    name = name.split(" = ", 1)[0].strip()
    return name.lstrip("%")[:80]


def top_ops(profile, k: int = 10) -> List[list]:
    """The device ops that took most time, by the trace's own names
    (leaf time: a `while` that only wraps its body's ops is listed with
    its whole span, as the trace gives it)."""
    total: Dict[str, float] = {}
    for p in device_planes(profile):
        for n, s, e in events(p, OPS_LINE):
            key = short_name(n)
            total[key] = total.get(key, 0.0) + (e - s)
    n_chips = max(1, len(device_planes(profile)))
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t / n_chips] for n, t in rows]


def describe(profile, max_names: int = 12) -> dict:
    """Planes, lines and the commonest event names: what a builder looks
    at by hand before writing a pattern."""
    out = {}
    for p in profile.planes:
        lines = {}
        for ln in p.lines:
            names: Dict[str, int] = {}
            n = 0
            for ev in ln.events:
                n += 1
                key = short_name(ev.name)
                names[key] = names.get(key, 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:max_names]
            lines[ln.name] = {"events": n, "names": top}
        out[p.name] = lines
    return out


if __name__ == "__main__":
    # by hand: python benchmark/xplane.py <file.xplane.pb>
    import json
    import sys
    prof = load(sys.argv[1])
    print(json.dumps({"planes": describe(prof),
                      "busy_s": busy_seconds(prof),
                      "top_ops": top_ops(prof)}, indent=1))
