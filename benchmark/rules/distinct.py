"""Replicas kept apart: `distinct_hosts` and `distinct_property`
constraints at job or group scope, and the account of where a job's
allocs already stand.

Data, from the configuration's file:
  job.distinct      a list of limits, each at `scope` "job" (over all of
                    a job's groups) or "group" (each group on its own):
                    {"scope": "group", "operand": "distinct_hosts"}
                    {"scope": "job", "operand": "distinct_property",
                     "attribute": "${attr.zone}", "limit": 5}
                    `attribute` names a node column of
                    `cluster.attributes`; `limit` defaults to 1

Semantics, the reference scheduler's (hashicorp/nomad `scheduler/`):
  feasible.go DistinctHostsIterator   a node is infeasible when it
                    already holds an alloc of the job (job scope) or of
                    the job and group (group scope): the job's live
                    allocs and those proposed so far in the plan
  feasible.go DistinctPropertyIterator over propertyset.go   a node is
                    infeasible when the allocs of the job (job scope) or
                    of the job and group (group scope) on nodes with its
                    value of the attribute, live and proposed, already
                    number `limit` (`allowedCount`, 1 when the constraint
                    gives none); a node that lacks the attribute is
                    infeasible ("missing property")
  rank.go           neither adds a score term: scores stay bin-pack + job
                    anti-affinity + node affinity + spread

Departures, all of them narrowings the data above cannot leave: no alloc
of a run stops, so the plan clears no value (propertyset.go's
`clearedValues`) and a job's counts only grow; every job the run
registers is new, so its counts start at none (`begin_job`) and nothing
is shared between jobs, which is why the `isolated_round` control needs
no `begin_round` here; an attribute is a column of `cluster.attributes`,
which every node holds or none does, so "lacks the attribute" is the
whole cluster or no node; the limit is an integer in the data where
upstream parses the constraint's right-hand string.

A placement moves the fit of every node that shares the chosen node's
value, which the placer sees because it ANDs every rule's whole `fits`
in after every placement (`reference.Placer`).  It defines no
`rows_fit`: a configuration that names it holds no `choice_gap_p90`
(c3's spread already keeps that number out).

Numbers (a configuration that names this rule lists both under
`correct.limits` with the limit 0, and `distinct_ignored` under
`correct.controls`), over the live allocs of the run's jobs:
  distinct_hosts_shared     allocs beyond the first of a (job, node), or
                            of a (job, group, node) at group scope, for
                            each `distinct_hosts` entry
  distinct_property_over    allocs beyond `limit` of a (job, value), or
                            of a (job, group, value) at group scope, for
                            each `distinct_property` entry

Planes (`planes`): one a `distinct_property` attribute, the node's index
of its value, which a solve must read to count a value's allocs.
`distinct_hosts` counts by node and needs none.
"""
from __future__ import annotations

from typing import List

import numpy as np

NUMBERS = ("distinct_hosts_shared", "distinct_property_over")
#: the placer keeps the counts and never consults them
CONTROLS = {"distinct_ignored": {"distinct_fits": False}}

HOSTS, PROPERTY = "distinct_hosts", "distinct_property"


def entries(cfg: dict) -> List[dict]:
    """`job.distinct`, each entry checked and its limit filled in."""
    out = []
    for e in cfg["job"]["distinct"]:
        if e["scope"] not in ("job", "group") \
                or e["operand"] not in (HOSTS, PROPERTY):
            raise ValueError(f"job.distinct entry {e}: scope job or "
                             f"group, operand {HOSTS} or {PROPERTY}")
        e = dict(e)
        if e["operand"] == PROPERTY:
            e["limit"] = int(e.get("limit", 1))
        out.append(e)
    return out


def _value_index(plain, attr: str):
    """Each node's index into the attribute's values, and their number
    (one value for all where the cluster has no such column: those nodes
    are infeasible, `feasible`)."""
    col = plain.cols.get(attr)
    if col is None:
        return np.zeros(len(plain), np.int64), 1
    vals, inv = np.unique(col, return_inverse=True)
    return inv, len(vals)


# ------------------------------------------------------------ plain data
def feasible(cfg: dict, plain) -> np.ndarray:
    ok = np.ones(len(plain), bool)
    for e in entries(cfg):
        if e["operand"] == PROPERTY:
            ok &= e["attribute"] in plain.cols
    return ok


def planes(cfg: dict) -> int:
    """The value index of each attribute a `distinct_property` names."""
    return len({e["attribute"] for e in entries(cfg)
                if e["operand"] == PROPERTY})


# ----------------------------------------------------------- the placer
def start(placer) -> None:
    es = entries(placer.cfg)
    for e in es:
        if e["operand"] == PROPERTY:
            e["inv"], e["n_values"] = _value_index(placer.plain,
                                                   e["attribute"])
        else:
            e["n_values"] = len(placer.plain)
    placer.state["distinct"] = {"entries": es}


def begin_job(placer) -> None:
    st = placer.state["distinct"]
    st["counts"] = [{} for _e in st["entries"]]


def _counts(st: dict, i: int, group: dict) -> np.ndarray:
    """The job's allocs per node (`distinct_hosts`) or per value
    (`distinct_property`) under entry i, for `group`'s scope."""
    e = st["entries"][i]
    key = None if e["scope"] == "job" else group["name"]
    if key not in st["counts"][i]:
        st["counts"][i][key] = np.zeros(e["n_values"], np.int64)
    return st["counts"][i][key]


def fits(placer, group: dict) -> np.ndarray:
    ok = np.ones(len(placer.plain), bool)
    if not placer.rule_kw.get("distinct_fits", True):
        return ok
    st = placer.state["distinct"]
    for i, e in enumerate(st["entries"]):
        c = _counts(st, i, group)
        if e["operand"] == HOSTS:
            ok &= c == 0
        else:
            ok &= c[e["inv"]] < e["limit"]
    return ok


def commit(placer, ni: int, group: dict) -> dict:
    st = placer.state["distinct"]
    for i, e in enumerate(st["entries"]):
        _counts(st, i, group)[ni if e["operand"] == HOSTS
                              else e["inv"][ni]] += 1
    return {}


# -------------------------------------------------------------- the rows
def numbers(cfg: dict, plain, rows: dict, sent: list, ref: dict) -> dict:
    mine = {jid for jid, _shape in sent}
    out = {"distinct_hosts_shared": 0, "distinct_property_over": 0}
    for e in entries(cfg):
        hosts = e["operand"] == HOSTS
        col = None if hosts else plain.cols.get(e["attribute"])
        counts: dict = {}
        for jid, grp, ni in zip(rows["job_id"], rows["group"], rows["node"]):
            if jid not in mine or ni < 0:
                continue
            where = int(ni) if hosts else \
                ("" if col is None else str(col[ni]))
            key = (jid, None if e["scope"] == "job" else grp, where)
            counts[key] = counts.get(key, 0) + 1
        if hosts:
            out["distinct_hosts_shared"] += sum(
                c - 1 for c in counts.values() if c > 1)
        else:
            out["distinct_property_over"] += sum(
                c - e["limit"] for c in counts.values() if c > e["limit"])
    return out


# --------------------------------------------------- the program's objects
def _constraint(e: dict):
    from nomad_tpu.structs import Constraint
    if e["operand"] == HOSTS:
        return Constraint(operand=HOSTS)
    return Constraint(ltarget=e["attribute"], rtarget=str(e["limit"]),
                      operand=PROPERTY)


def build_group(tg, group: dict, cfg: dict) -> None:
    tg.constraints.extend(_constraint(e) for e in entries(cfg)
                          if e["scope"] == "group")


def build_job(job, cfg: dict) -> None:
    job.constraints.extend(_constraint(e) for e in entries(cfg)
                           if e["scope"] == "job")
