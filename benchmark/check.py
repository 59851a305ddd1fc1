"""How `correct` is decided.

Once the window has closed (and the device's memory peak has been read),
the state store's final state is read back into plain rows, one per live
alloc, and compared with the plain reference (`reference.py`) and the
configuration's guarantees.  Every number compared is printed beside its
limit.  The limits are the configuration's own: its file lists, under
`correct.limits`, each number its runs are held to, with the limit and
the two readings the limit was set from.  A number the file does not
list is not compared in that configuration's cells; one it lists and
the run cannot compute makes the run not correct.

  jobs_off_count          jobs whose live allocs, in any group, differ
                          from what the reference placed for the same
                          job sequence (the asked count, whenever the
                          reference finds room, as it does under the
                          ceiling); jobs the run never registered count too
  overcommitted_nodes     nodes whose usage, summed from scratch in
                          float64 over the rows, exceeds capacity on cpu,
                          memory or disk
  constraint_violations   allocs on a node outside the reference's
                          feasibility mask for their job
  unknown_refs            allocs on a node or of a job the run never made
  not_raft_applied        allocs whose create index lies beyond the raft
                          log's applied index, or at an index whose log
                          entry is not a plan result
  off_device_solves       solves answered from another platform than the
                          one JAX reports, watchdog failovers, degraded
                          (brownout) solves
  scores_unrecorded       allocs of the run's jobs that carry no score
  score_mismatch_p99      the solve's arithmetic: over every alloc of the
                          run's jobs the gap between the score the
                          program recorded for the chosen node and the
                          reference's float64 score of that node (bin-pack
                          + job anti-affinity + node affinity + spread,
                          normalised), recomputed from the rows; the
                          99th percentile over the allocs
  choice_gap_p90          the solve's choice (configurations without a
                          spread): over the plans the gap by which the
                          worst node a plan chose scores below the
                          reference's k-th best feasible node, k the
                          allocs the plan asked for; the 90th percentile
                          over the plans
  spread_miss_share       only where the job has a spread: mean over the
                          jobs of sum_v |allocs on value v - even share| /
                          allocs, 0 = even

A configuration's rules (`rules/<name>.py`, README.md "A rule") add
fields to the rows (`alloc_row`), numbers of their own (`numbers`, named
in the rule's `NUMBERS`) and what more a node needs to fit in the
choice's replay (`rows_fit`).

What state the program scored a node in is not in the rows: a plan is
scored from a snapshot taken somewhere between its job's registration
and its commit, and a fused round lets an eval see part of what its
neighbours place.  So both score numbers take the most favourable of
the states the rows allow: the mismatch over every subset of the allocs
on that node that landed after the job was registered and whose own job
was registered before this plan committed (those that landed earlier
are always counted), with or without one alloc of each of the job's own
groups that the solve proposed there and took back; the choice gap over
the store's state after each commit between the job's registration and
the plan's own.  A sound solve reads its rounding error whichever state
it saw.  What a solve proposed and took back elsewhere is not in the
rows at all, so a few allocs and plans can read far off in a sound run:
both numbers are percentiles, not the widest gap (which is printed
beside them, not compared); a loss of precision or a broken choice
moves most allocs and plans (PERF.md section 2).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

import cluster
import reference

PLAN_ENTRIES = ("plan_result", "plan_results_batch")
#: every number `compare` itself can give a limit something to hold
NUMBERS = ("unknown_refs", "jobs_off_count", "overcommitted_nodes",
           "constraint_violations", "not_raft_applied",
           "off_device_solves", "scores_unrecorded", "score_mismatch_p99",
           "score_mismatch_max", "choice_gap_p90", "choice_gap_max",
           "spread_miss_share")


def limits_of(cfg: dict) -> Dict[str, float]:
    return {k: v["limit"] for k, v in cfg["correct"]["limits"].items()}


def validate(cfg: dict) -> None:
    """Before anything is built: every number the configuration holds
    its runs to and every control it lists is one that this file, the
    reference or one of its rules knows."""
    numbers = set(NUMBERS)
    for rule in cluster.rules_of(cfg):
        numbers.update(getattr(rule, "NUMBERS", ()))
    unknown = sorted(set(cfg["correct"]["limits"]) - numbers)
    if unknown:
        raise ValueError(
            f"correct.limits of {cfg.get('name')} lists {unknown}, which "
            f"neither check.py nor a rule of {cfg.get('rules', [])} "
            "computes")
    unknown = sorted(set(cfg["correct"]["controls"])
                     - set(reference.controls_of(cfg)))
    if unknown:
        raise ValueError(
            f"correct.controls of {cfg.get('name')} lists {unknown}, which "
            f"neither reference.py nor a rule of {cfg.get('rules', [])} "
            "defines")


def _with_rule_fields(rows: dict, held: List[dict]) -> dict:
    """The rules' fields of each alloc as columns of `rows` (lists, None
    where a rule said nothing of that alloc)."""
    for key in sorted({k for h in held for k in h}):
        rows[key] = [h.get(key) for h in held]
    return rows


def rows_from_snapshot(cfg: dict, snapshot, plain: cluster.PlainNodes
                       ) -> dict:
    """One row per live alloc, from the store alone; and the index at
    which each job was registered."""
    slot = {nid: i for i, nid in enumerate(plain.ids)}
    job_ids, groups, node_ix, res, created, score = [], [], [], [], [], []
    alloc_row = cluster.hooks(cfg, "alloc_row")
    held: List[dict] = []
    for a in snapshot.allocs():
        if a.terminal_status():
            continue
        ar = a.allocated_resources
        job_ids.append(a.job_id)
        groups.append(a.task_group)
        node_ix.append(slot.get(a.node_id, -1))
        res.append((sum(t.cpu for t in ar.tasks.values()),
                    sum(t.memory_mb for t in ar.tasks.values()),
                    ar.shared.disk_mb))
        created.append(a.create_index)
        recorded = a.metrics.scores.get(a.node_id) \
            if a.metrics is not None else None
        score.append(np.nan if recorded is None else float(recorded))
        if alloc_row:
            held.append({k: v for fn in alloc_row
                         for k, v in fn(a).items()})
    return _with_rule_fields(
        {"job_id": job_ids, "group": groups,
         "node": np.asarray(node_ix, np.int64),
         "res": np.asarray(res, np.float64).reshape(-1, 3),
         "create_index": np.asarray(created, np.int64),
         "score": np.asarray(score, np.float64),
         "job_index": {j.id: int(j.create_index)
                       for j in snapshot.jobs()}}, held)


def raft_view(server) -> dict:
    """What the raft log says about plan entries, for `not_raft_applied`:
    the applied index and the type of each entry still in the log."""
    raft = server.raft
    types = {}
    log = raft.log
    for i in range(log.offset + 1, log.last_index() + 1):
        e = log.get(i)
        if e is not None:
            types[i] = e.etype
    return {"last_applied": int(raft.last_applied), "types": types,
            "compacted_to": int(log.offset)}


def compare(cfg: dict, plain: cluster.PlainNodes, rows: dict,
            sent: List[tuple], raft: Optional[dict],
            off_device_solves: int) -> Dict[str, float]:
    """The numbers `correct` compares, from plain rows.  `sent` is every
    job the run registered, in order: (job id, shape), shape None for
    the whole job template."""
    n = len(plain)
    sent_job_ids = [jid for jid, _s in sent]
    res_jobs = cluster.resident_jobs(cfg)
    expected: Dict[str, Dict[str, int]] = {
        f"resident-{j}": {"r": c} for j, c in enumerate(res_jobs)}
    ref = reference.place_sequence(cfg, plain, [s for _j, s in sent])
    for (jid, shape), placed in zip(sent, ref["placed"]):
        expected[jid] = {g["name"]: c for g, c in zip(
            cluster.job_groups(cfg, shape), placed)}

    out: Dict[str, float] = {}
    node = rows["node"]
    known_job = np.array([j in expected for j in rows["job_id"]], bool)
    out["unknown_refs"] = int((node < 0).sum() + (~known_job).sum())

    live: Dict[tuple, int] = {}
    for jid, g in zip(rows["job_id"], rows["group"]):
        live[(jid, g)] = live.get((jid, g), 0) + 1
    off = 0
    for jid, want in expected.items():
        if any(live.get((jid, g), 0) != c for g, c in want.items()):
            off += 1
    off += len({jid for (jid, g) in live
                if jid in expected and g not in expected[jid]})
    out["jobs_off_count"] = off

    ok = node >= 0
    usage = np.zeros((n, 3), np.float64)
    np.add.at(usage, node[ok], rows["res"][ok])
    out["overcommitted_nodes"] = int((usage > plain.cap).any(axis=1).sum())

    feasible = reference.constraint_mask(cfg, plain)
    sent_set = set(sent_job_ids)
    is_sent = np.array([j in sent_set for j in rows["job_id"]], bool)
    out["constraint_violations"] = int((~feasible[node[ok & is_sent]]).sum())

    if raft is not None:
        ci = rows["create_index"]
        bad = int((ci > raft["last_applied"]).sum() + (ci <= 0).sum())
        for i in np.unique(ci):
            t = raft["types"].get(int(i))
            if t is not None and t not in PLAN_ENTRIES:
                bad += int((ci == i).sum())
        out["not_raft_applied"] = bad
    out["off_device_solves"] = int(off_device_solves)

    mine = np.where(is_sent & ok)[0]
    if len(mine):
        out["scores_unrecorded"] = int(np.isnan(rows["score"][mine]).sum())
        gaps = score_mismatches(cfg, plain, rows, sent, mine)
        out["score_mismatch_p99"] = _quantile(gaps, 0.99)
        out["score_mismatch_max"] = float(gaps.max())
        if not cfg["job"]["spreads"]:
            gaps = choice_gaps(cfg, plain, rows, sent, mine, feasible)
            out["choice_gap_p90"] = _quantile(gaps, 0.90)
            out["choice_gap_max"] = float(gaps.max())

    spreads = cfg["job"]["spreads"]
    if spreads and sent_job_ids:
        attr = plain.attr(spreads[0][0])
        vals, inv = np.unique(attr, return_inverse=True)
        by_job: Dict[str, List[int]] = {}
        for jid, ni, s in zip(rows["job_id"], node, is_sent):
            if s and ni >= 0:
                by_job.setdefault(jid, []).append(int(ni))
        miss = []
        for jid, shape in sent:
            nis = by_job.get(jid)
            if shape is not None:      # the window's jobs, whole template
                continue
            if not nis:
                continue
            cnt = np.bincount(inv[nis], minlength=len(vals))
            miss.append(np.abs(cnt - len(nis) / len(vals)).sum()
                        / len(nis))
        if miss:
            out["spread_miss_share"] = float(np.mean(miss))
    for numbers in cluster.hooks(cfg, "numbers"):
        out.update(numbers(cfg, plain, rows, sent, ref))
    return out


def _quantile(values: np.ndarray, q: float) -> float:
    """Nearest rank from above: the smallest value with at least q of
    the values at or below it."""
    v = np.sort(values)
    return float(v[max(0, int(np.ceil(q * len(v))) - 1)])


def _group_of(cfg: dict, sent: List[tuple]) -> Dict[tuple, dict]:
    """(job id, group name) -> the group's plain dict."""
    by_shape: Dict[object, Dict[str, dict]] = {}
    out = {}
    for jid, shape in sent:
        if shape not in by_shape:
            by_shape[shape] = {g["name"]: g
                               for g in cluster.job_groups(cfg, shape)}
        for name, g in by_shape[shape].items():
            out[(jid, name)] = g
    return out


#: most (cpu, memory, collisions) states looked at for one alloc; a
#: node busier than that is looked at in commit order only
MAX_STATES = 20000


def _states_seen(k: int, others: List[int], groups: List[dict], jid, grp,
                 ci, res, reg, registered: int, commit: int) -> np.ndarray:
    """[s, 3] (cpu, memory, collisions) sums of the other allocs of the
    run's jobs on alloc k's node that its solve may have counted: all
    that landed before its job was registered, any subset of those that
    landed later and whose own job was registered before k's plan
    committed, and one alloc of each of its own job's `groups` that the
    solve proposed there and took back (a wave's conflict, a cut plan),
    so that it is not in the rows."""
    must = np.zeros(3)
    optional: Dict[tuple, int] = {
        (g["cpu"], g["mem"], float(g["name"] == grp[k])): 1
        for g in groups}
    ordered = []
    for o in others:
        if o == k:
            continue
        add = (res[o][0], res[o][1],
               float(jid[o] == jid[k] and grp[o] == grp[k]))
        if ci[o] <= registered:
            must += add
        elif reg.get(jid[o], 0) <= commit:
            optional[add] = optional.get(add, 0) + 1
            ordered.append((ci[o], add))
    if np.prod([c + 1.0 for c in optional.values()]) > MAX_STATES:
        steps = np.array([a for _c, a in sorted(ordered)])
        return must[None, :] + np.concatenate(
            [np.zeros((1, 3)), np.cumsum(steps, axis=0)])
    takes = np.stack(np.meshgrid(
        *[np.arange(c + 1.0) for c in optional.values()],
        indexing="ij"), axis=-1).reshape(-1, len(optional))
    return np.unique(must[None, :] + takes @ np.array(list(optional)),
                     axis=0)


def score_mismatches(cfg: dict, plain: cluster.PlainNodes, rows: dict,
                     sent: List[tuple], mine: np.ndarray) -> np.ndarray:
    """For each alloc of `mine`, the gap between its recorded score and
    the reference's float64 score of the same node, under the most
    favourable of the states the rows allow (module docstring).  An
    alloc without a score reads infinity."""
    jid, grp, node = rows["job_id"], rows["group"], rows["node"]
    ci, res, rec = rows["create_index"], rows["res"], rows["score"]
    reg = rows["job_index"]
    base = reference.resident_usage(cfg, len(plain))
    affinity = reference.affinity_column(plain, cfg["job"])
    group_of = _group_of(cfg, sent)
    groups_of = {j: cluster.job_groups(cfg, shape) for j, shape in sent}
    spreads = cfg["job"]["spreads"]
    wsum = sum(w for _a, w in spreads)
    boost_cache: Dict[int, np.ndarray] = {}

    def boosts(count: int) -> np.ndarray:
        if count not in boost_cache:
            total = np.zeros(1)
            for _attr, w in spreads:
                total = np.unique(np.add.outer(
                    total, reference.spread_boost_values(count)
                    * (w / wsum)).ravel())
            boost_cache[count] = total
        return boost_cache[count]

    on_node: Dict[int, List[int]] = {}
    for k in mine:
        on_node.setdefault(int(node[k]), []).append(int(k))
    gaps = np.full(len(mine), np.inf)
    for i, k in enumerate(mine):
        if np.isnan(rec[k]):
            continue
        ni, commit = int(node[k]), ci[k]
        registered = reg.get(jid[k], 0)
        count = group_of[(jid[k], grp[k])]["count"]
        st = _states_seen(k, on_node[ni], groups_of[jid[k]], jid, grp, ci,
                          res, reg, registered, commit)
        after = base[ni][None, :2] + st[:, :2] + res[k][None, :2]
        binpack = reference.binpack_score(after, plain.cap[ni][None, :2])
        score = reference.normalized_score(
            binpack[:, None], st[:, 2][:, None], count, affinity[ni],
            boosts(count)[None, :])
        gaps[i] = np.abs(score - rec[k]).min()
    return gaps


def choice_gaps(cfg: dict, plain: cluster.PlainNodes, rows: dict,
                sent: List[tuple], mine: np.ndarray,
                feasible: np.ndarray) -> np.ndarray:
    """The gap, for each plan (the allocs of one job and group with one
    create index), between the reference's k-th best feasible score
    and the score of the worst node the plan chose, both for the next
    alloc of the group on the node, k the allocs the group still asked
    for; each plan under the most favourable of the store's states after
    each commit from its job's registration up to its own (module
    docstring).  Bin-pack, job anti-affinity and node affinity; no
    spread.  A node fits where cpu, memory and disk do and every rule's
    `rows_fit` says so of the allocs live in that state."""
    jid, grp, node = rows["job_id"], rows["group"], rows["node"]
    ci, res = rows["create_index"], rows["res"]
    reg = rows["job_index"]
    n = len(plain)
    affinity = reference.affinity_column(plain, cfg["job"])
    group_of = _group_of(cfg, sent)
    by_commit: Dict[int, List[int]] = {}
    plans: Dict[tuple, List[int]] = {}
    for k in mine:
        by_commit.setdefault(int(ci[k]), []).append(int(k))
        plans.setdefault((int(ci[k]), jid[k], grp[k]), []).append(int(k))
    keys = sorted(plans)
    # the oldest registration any plan from here on can look back to
    oldest = np.minimum.accumulate(
        [reg.get(j, 0) for _c, j, _g in keys][::-1])[::-1]

    rows_fit = cluster.hooks(cfg, "rows_fit")
    # rows a rule counts as live in a state: every alloc that is not of
    # the run's jobs, and the first m of theirs in commit order
    others = np.setdiff1d(np.flatnonzero(node >= 0), mine)
    committed: List[int] = []
    used = reference.resident_usage(cfg, n)
    # (index, usage after it, len(committed) after it), oldest first
    states = [(0, used.copy(), 0)]
    placed: Dict[tuple, List[int]] = {}            # (job, group) -> rows
    gaps, at = [], 0
    for commit in sorted(by_commit):
        while at < len(keys) and keys[at][0] == commit:
            _c, j, g = keys[at]
            ks = plans[keys[at]]
            group = group_of[(j, g)]
            before = placed.setdefault((j, g), [])
            asked = group["count"] - len(before)
            ask = np.array([group["cpu"], group["mem"], group["disk"]])
            chosen = np.unique(node[ks])
            collisions = np.bincount(node[before], minlength=n) \
                if before else np.zeros(n)
            # not older than the job's registration, nor than the
            # group's own last plan (a retry is scored after it)
            since = max([reg.get(j, 0)] + [int(ci[k]) for k in before[-1:]])
            first = max((i for i, st in enumerate(states)
                         if st[0] <= since), default=0)
            best = float("inf")
            for _x, usage, m in states[first:]:
                after = usage + ask
                fits = feasible & (after <= plain.cap).all(axis=1)
                if rows_fit:
                    live = np.concatenate(
                        [others, np.asarray(committed[:m], np.int64)])
                    for rule_fit in rows_fit:
                        fits &= rule_fit(cfg, plain, rows, live, group)
                if asked > fits.sum():
                    best = 0.0       # fewer nodes fit than were asked for
                    break
                score = np.where(fits, reference.normalized_score(
                    reference.binpack_score(after, plain.cap), collisions,
                    group["count"], affinity, 0.0), -np.inf)
                kth = np.partition(score, -asked)[-asked]
                best = min(best, max(0.0, float(kth - score[chosen].min())))
                if best == 0.0:
                    break
            gaps.append(best)
            before.extend(ks)
            at += 1
        ks = by_commit[commit]
        np.add.at(used, node[ks], res[ks])
        committed.extend(ks)
        states.append((commit, used.copy(), len(committed)))
        if at < len(keys):
            keep = max((i for i, st in enumerate(states)
                        if st[0] <= oldest[at]), default=0)
            del states[:keep]
    return np.array(gaps)


def reference_rows(cfg: dict, plain: cluster.PlainNodes,
                   sent: List[tuple], round_jobs: int = 32,
                   **placer_kw) -> dict:
    """The reference, put in the program's place: the rows a store would
    hold had `reference.Placer` (or one of its controls) done the
    scheduling.  Resident allocs come from the layout.  Each job is
    registered and then committed before the next; under the `isolate`
    control the jobs of one round are registered together and committed
    in order after that, as concurrent callers' are."""
    together = round_jobs if placer_kw.get("isolate") else 1
    r = cfg["resident"]
    job_ids, grp, node, res, created, score = [], [], [], [], [], []
    held: List[dict] = []
    node_of = cluster.resident_node_index(cfg)
    k = 0
    for j, count in enumerate(cluster.resident_jobs(cfg)):
        for _ in range(count):
            job_ids.append(f"resident-{j}")
            grp.append("r")
            node.append(int(node_of[k]))
            res.append((r["cpu_mhz"], r["memory_mb"], r["disk_mb"]))
            created.append(1)
            score.append(np.nan)
            held.append({})
            k += 1
    placed = reference.place_sequence(cfg, plain, [s for _j, s in sent],
                                      round_jobs=round_jobs, **placer_kw)
    job_index = {}
    for j, (jid, _shape) in enumerate(sent):
        first = 2 + 2 * together * (j // together)
        job_index[jid] = first + j % together
    for j, gi, ni, sc, holds in placed["rows"]:
        g = cluster.job_groups(cfg, sent[j][1])[gi]
        job_ids.append(sent[j][0])
        grp.append(g["name"])
        node.append(ni)
        res.append((g["cpu"], g["mem"], g["disk"]))
        created.append(job_index[sent[j][0]] + together)
        score.append(sc)
        held.append(holds)
    return _with_rule_fields(
        {"job_id": job_ids, "group": grp,
         "node": np.asarray(node, np.int64),
         "res": np.asarray(res, np.float64).reshape(-1, 3),
         "create_index": np.asarray(created, np.int64),
         "score": np.asarray(score, np.float64),
         "job_index": job_index}, held)


def verdict(cfg: dict, numbers: Dict[str, float]) -> dict:
    """Each number the configuration holds its runs to beside its limit,
    and whether all hold.  A number the configuration lists and the run
    did not produce reads as missing, and the run is not correct."""
    compared = {}
    ok = True
    for name, limit in limits_of(cfg).items():
        value = numbers.get(name)
        if value is None or not value <= limit:
            ok = False
        compared[name] = {"value": "missing" if value is None else value,
                          "limit": limit}
    return {"correct": bool(ok), "compared": compared}
