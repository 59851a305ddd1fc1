#!/usr/bin/env python3
"""Run the controls of `correct` at a cell's own size.

    python benchmark/controls.py --workload <cell> --seeds 1,2,3 --jobs 300

For each seed: the plain reference put in the program's place (`sound`)
and each control the configuration's file lists (`correct.controls`, by
its name in `reference.CONTROLS` or in a rule's `CONTROLS`) place the
same `--jobs` jobs on the cell's cluster; the rows they leave go
through the same comparison a run's store goes through.  `sound` has to
come out correct and every control not correct.  Host work only: nothing
here touches JAX or the program.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import check  # noqa: E402
import cluster  # noqa: E402
import reference  # noqa: E402


def run_controls(cfg: dict, seed: int, n_jobs: int) -> dict:
    plain = cluster.make_plain_nodes(cfg, seed)
    ids = [(f"job-{seed}-{i}", None) for i in range(n_jobs)]
    known = reference.controls_of(cfg)
    out = {}
    for name, kw in [("sound", dict)] + [
            (c, known[c]) for c in cfg["correct"]["controls"]]:
        rows = check.reference_rows(cfg, plain, ids, **kw())
        numbers = check.compare(cfg, plain, rows, ids, None, 0)
        numbers["not_raft_applied"] = 0     # no raft log to hold it to
        out[name] = check.verdict(cfg, numbers)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--jobs", type=int, default=300)
    a = ap.parse_args(argv)
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json"),
              encoding="utf-8") as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == a.workload)
    cfg = cluster.load_config(cell["config"])
    check.validate(cfg)
    bad = 0
    for seed in (int(s) for s in a.seeds.split(",")):
        t0 = time.monotonic()
        res = run_controls(cfg, seed, a.jobs)
        for name, v in res.items():
            nums = {k: c["value"] for k, c in v["compared"].items()}
            print(json.dumps({"workload": a.workload, "seed": seed,
                              "control": name, "correct": v["correct"],
                              "compared": nums}))
        failing = [n for n, v in res.items()
                   if n != "sound" and not v["correct"]]
        if not res["sound"]["correct"] or len(failing) < len(res) - 1:
            bad += 1
        print(f"seed {seed}: sound correct={res['sound']['correct']}, "
              f"controls not correct: {failing} "
              f"({time.monotonic() - t0:.1f}s)", file=sys.stderr)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
