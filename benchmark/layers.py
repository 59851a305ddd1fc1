"""Per-layer metrics: one small data file per metric
(`layer_metrics/<name>.json`) names what it reads and which of the
reducers below combines it.  A later PR adds a metric by adding a file
and a `BENCHMARK.json` entry.

What a reader may read (`Observed`):
  counters   the program's counters, diffed around the traced window
  samples    {key: (sum, count)} of the program's timing samples, diffed
  hists      {key: (sum, count)} of its explicit-bucket histograms, diffed
  harness    numbers the harness counted itself around the same window
             (evals_completed, placements_visible, compiles, window_s)
  profile    the device trace of that window (None on a CPU backend)
  config     the cell's configuration file
  peaks      the chip's published peaks (None for an unknown kind)

A term is {"counter": key} | {"counter_prefix": p} | {"sample_sum": key}
| {"sample_count": key} | {"hist_sum": key} | {"harness": key}; a list
of terms is their sum.  A reader that finds
nothing to read returns None and the harness leaves the metric out.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import bytes_models
import host_spans
import xplane

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass
class Observed:
    counters: Dict[str, float] = field(default_factory=dict)
    samples: Dict[str, tuple] = field(default_factory=dict)
    hists: Dict[str, tuple] = field(default_factory=dict)
    harness: Dict[str, float] = field(default_factory=dict)
    profile: object = None
    config: dict = field(default_factory=dict)
    peaks: Optional[dict] = None


def diff_dumps(before: dict, after: dict) -> dict:
    """`global_metrics.dump()` after minus before, as Observed fields."""
    def sub(kind, fields):
        out = {}
        for k, a in after.get(kind, {}).items():
            b = before.get(kind, {}).get(k, {})
            out[k] = tuple(a[f] - b.get(f, 0) for f in fields)
        return out
    counters = {k: v - before.get("counters", {}).get(k, 0.0)
                for k, v in after.get("counters", {}).items()}
    return {"counters": counters,
            "samples": sub("samples", ("sum", "count")),
            "hists": sub("histograms", ("sum", "count"))}


def load_metric(name: str) -> dict:
    with open(os.path.join(HERE, "layer_metrics", f"{name}.json"),
              encoding="utf-8") as f:
        return json.load(f)


def _term(obs: Observed, term: dict) -> Optional[float]:
    (kind, key), = term.items()
    if kind == "counter":
        return obs.counters.get(key)
    if kind == "counter_prefix":
        vals = [v for k, v in obs.counters.items() if k.startswith(key)]
        return sum(vals) if vals else None
    if kind in ("sample_sum", "sample_count"):
        v = obs.samples.get(key)
        return None if v is None else v[0 if kind == "sample_sum" else 1]
    if kind == "hist_sum":
        v = obs.hists.get(key)
        return None if v is None else v[0]
    if kind == "harness":
        return obs.harness.get(key)
    raise ValueError(f"unknown term kind {kind!r}")


def _total(obs: Observed, terms: List[dict]) -> Optional[float]:
    vals = [_term(obs, t) for t in terms]
    if any(v is None for v in vals):
        return None
    return float(sum(vals))


# ----------------------------------------------------------- reducers
def _ratio(spec: dict, obs: Observed) -> Optional[float]:
    num, den = _total(obs, spec["num"]), _total(obs, spec["den"])
    if num is None or not den:
        return None
    return spec.get("scale", 1.0) * num / den


def _value(spec: dict, obs: Observed) -> Optional[float]:
    return _total(obs, spec["num"])


def _device_idle(spec: dict, obs: Observed) -> Optional[float]:
    if obs.profile is None:
        return None
    busy = xplane.busy_seconds(obs.profile)
    window = obs.harness.get("window_s")
    if busy is None or not window:
        return None
    return 100.0 * (1.0 - busy / window)


def _device_time_over(spec: dict, obs: Observed) -> Optional[float]:
    if obs.profile is None:
        return None
    dev = xplane.program_seconds(obs.profile, spec["module_pattern"])
    den = _total(obs, spec["den"])
    if dev is None or not den:
        return None
    return spec.get("scale", 1.0) * dev / den


def _roofline(spec: dict, obs: Observed) -> Optional[float]:
    """The least time the chip could take for the bytes the work must
    move, over the device time the program took: a share in percent."""
    if obs.profile is None or obs.peaks is None:
        return None
    dev = xplane.program_seconds(obs.profile, spec["module_pattern"])
    if not dev:
        return None
    fn = getattr(bytes_models, spec["bytes_fn"])
    args = {k: _total(obs, terms) for k, terms in spec["args"].items()}
    if any(v is None for v in args.values()):
        return None
    least_bytes = fn(obs.config, **args)
    return 100.0 * (least_bytes / obs.peaks[spec["peak"]]) / dev


REDUCERS: Dict[str, Callable[[dict, Observed], Optional[float]]] = {
    "ratio": _ratio,
    "value": _value,
    "device_idle_share": _device_idle,
    "device_time_over": _device_time_over,
    "roofline": _roofline,
    "idle_attributed": host_spans.reduce_idle_attributed,
}


def read_metric(name: str, obs: Observed) -> Optional[float]:
    spec = load_metric(name)
    try:
        reducer = REDUCERS[spec["reducer"]]
    except KeyError:
        raise ValueError(f"layer metric {name}: unknown reducer "
                         f"{spec['reducer']!r} (have {sorted(REDUCERS)})")
    return reducer(spec, obs)


def load_peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json"), encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no published peaks recorded for device kind {device_kind!r}"
            f"; add it to benchmark/peaks.json with its source (known: "
            f"{sorted(k for k in table if not k.startswith('_'))})")
    return table[device_kind]
