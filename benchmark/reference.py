"""The plain reference: the scheduler's semantics in numpy, from the
configuration's plain data alone.  It imports nothing of the program and
takes nothing the program has made.

Semantics are the reference scheduler's (hashicorp/nomad `scheduler/`):
  feasible.go   datacenter membership; constraints `=`, `!=` and the
                lexical `<`, `<=`, `>`, `>=`; a node fits when used + ask
                <= capacity on cpu, memory and disk
  rank.go       BinPackIterator + structs.ScoreFit: 20 - (10^free_cpu +
                10^free_mem), clamped to [0, 18], over 18;
                JobAntiAffinityIterator: -(collisions + 1) / count when
                the node already holds allocs of the same job and group;
                NodeAffinityIterator: matched weights / sum of |weights|;
                ScoreNormalizationIterator: mean of the scores present
  spread.go     even spread over an attribute's values, per task group

`Placer` places a sequence of jobs one alloc at a time, greedily, every
placement seeing every earlier one: the straightforward implementation.
Its `controls` are the lower-precision and guarantee-breaking variants
that `correct` has to tell apart from a sound run (see check.py).

What a node holds beside cpu, memory and disk (device instances, ports)
is a rule's: a file `rules/<name>.py` that the configuration names under
`rules` (README.md, "A rule").  The mask and the placer ask each loaded
rule; a configuration that names none reads as it always did.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

import cluster


def constraint_mask(cfg: dict, plain: cluster.PlainNodes) -> np.ndarray:
    """Nodes on which an alloc of the configuration's job may run."""
    job = cfg["job"]
    ok = np.isin(plain.attr("${node.datacenter}"), list(job["datacenters"]))
    for lt, op, rt in job["constraints"]:
        col = plain.attr(lt)
        if op in ("=", "==", "is"):
            ok &= col == rt
        elif op in ("!=", "not"):
            ok &= col != rt
        elif op == ">=":
            ok &= col >= rt
        elif op == ">":
            ok &= col > rt
        elif op == "<=":
            ok &= col <= rt
        elif op == "<":
            ok &= col < rt
        else:
            raise ValueError(f"reference has no rule for operand {op!r}")
    for feasible in cluster.hooks(cfg, "feasible"):
        ok &= feasible(cfg, plain)
    return ok


def resident_usage(cfg: dict, n_nodes: int) -> np.ndarray:
    """[n, 3] float64 usage of the resident allocs, from the layout."""
    r = cfg["resident"]
    counts = np.bincount(cluster.resident_node_index(cfg),
                         minlength=n_nodes).astype(np.float64)
    vec = np.array([r["cpu_mhz"], r["memory_mb"], r["disk_mb"]],
                   np.float64)
    return counts[:, None] * vec[None, :]


def _even_spread_boost(counts: np.ndarray) -> np.ndarray:
    """spread.go evenSpreadScoreBoost for each attribute value, given
    the group's current count per value."""
    out = np.zeros(len(counts))
    if counts.sum() == 0:
        return out
    lo, hi = counts.min(), counts.max()
    for v, cur in enumerate(counts):
        if cur != lo:
            out[v] = -1.0 if lo == 0 else (lo - cur) / lo
        elif lo == hi:
            out[v] = -1.0
        else:
            out[v] = 1.0 if lo == 0 else (hi - lo) / lo
    return out


def affinity_column(plain: cluster.PlainNodes, job: dict) -> np.ndarray:
    """NodeAffinityIterator: matched weights over the sum of |weights|,
    per node."""
    aff = np.zeros(len(plain))
    wsum = sum(abs(w) for *_x, w in job["affinities"])
    for lt, op, rt, w in job["affinities"]:
        if op not in ("=", "==", "is"):
            raise ValueError("reference scores `=` affinities only")
        aff += np.where(plain.attr(lt) == rt, float(w), 0.0)
    return aff / wsum if wsum else aff


def spread_boost_values(count: int) -> np.ndarray:
    """Every value `_even_spread_boost` can take for a group of `count`
    allocs (0: no alloc of the group placed yet, the term is absent)."""
    out = {0.0}
    for lo in range(count + 1):
        for hi in range(max(lo, 1), count + 1):
            counts = np.array([lo, hi], np.float64)
            out.update(_even_spread_boost(counts).tolist())
            for cur in range(lo + 1, hi):
                out.add(float(_even_spread_boost(
                    np.array([lo, cur, hi], np.float64))[1]))
    return np.array(sorted(out))


def normalized_score(binpack, collisions, count: int, affinity, boost):
    """ScoreNormalizationIterator over the terms present: bin-pack
    always, job anti-affinity where the node already holds `collisions`
    allocs of the same job and group, node affinity and spread where
    they are not 0.  Arrays broadcast."""
    hit = np.asarray(collisions) > 0
    anti = np.where(hit, -(np.asarray(collisions) + 1.0) / count, 0.0)
    terms = 1.0 + hit + (np.asarray(affinity) != 0) \
        + (np.asarray(boost) != 0)
    return (binpack + anti + affinity + boost) / terms


def binpack_score(after: np.ndarray, cap: np.ndarray) -> np.ndarray:
    """structs.ScoreFit over 18, for usage `after` the placement."""
    a64, c64 = after.astype(np.float64), cap.astype(np.float64)
    total = (10.0 ** (1.0 - a64[..., 0] / c64[..., 0])
             + 10.0 ** (1.0 - a64[..., 1] / c64[..., 1]))
    return np.clip(20.0 - total, 0.0, 18.0) / 18.0


class Placer:
    """Greedy sequential placement of the configuration's jobs.

    dtype        precision of the capacity arithmetic (float64; the
                 bfloat16 control rounds every sum as a default-precision
                 dot on the chip would)
    isolate      jobs of one round do not see each other's placements
                 (control: the optimistic concurrency of a fused round
                 with its revalidation and the plan applier's re-check
                 left out)
    use_spread   False drops the spread term (control: spread ignored)
    sample       each placement looks at this many fitting nodes drawn
                 at random instead of all of them (control: the upstream
                 scheduler's own LimitIterator, which scores about
                 log2(nodes) candidates; the configuration states that
                 every node is scored)
    rule_kw      keywords of a rule's own controls (its `CONTROLS`), for
                 its hooks to read

    A rule keeps its state on the placer (`state[<its name>]`) through
    `start(placer)`, `begin_round(placer)` (the `isolate` control's
    copy) and `begin_job(placer)` (what this job sees); `fits(placer,
    group)` says on which nodes one more alloc of the group finds what
    the rule accounts for, and `commit(placer, ni, group)` debits it and
    returns what the alloc then holds, for the reference's rows.
    """

    def __init__(self, cfg: dict, plain: cluster.PlainNodes,
                 dtype=np.float64, isolate: bool = False,
                 use_spread: bool = True, sample: int = 0, **rule_kw):
        self.cfg, self.plain = cfg, plain
        self.dtype = dtype
        self.isolate, self.use_spread = isolate, use_spread
        self.sample = sample
        unknown = set(rule_kw) - {
            k for rule in cluster.rules_of(cfg)
            for kw in getattr(rule, "CONTROLS", {}).values() for k in kw}
        if unknown:
            raise TypeError(f"Placer has no keyword {sorted(unknown)} and "
                            "no control of the configuration's rules has")
        self.rule_kw = rule_kw
        self.state: Dict[str, object] = {}
        self.rng = np.random.default_rng(len(plain))
        n = len(plain)
        self.cap = plain.cap.astype(dtype)
        self.used = resident_usage(cfg, n).astype(dtype)
        job = cfg["job"]
        self.feasible = constraint_mask(cfg, plain)
        self.affinity = affinity_column(plain, job)
        self.spreads = []
        swsum = sum(w for _a, w in job["spreads"])
        for attr, w in job["spreads"]:
            vals, inv = np.unique(plain.attr(attr), return_inverse=True)
            self.spreads.append((inv, len(vals), w / swsum))
        self._round_base: Optional[np.ndarray] = None
        self._fits = cluster.hooks(cfg, "fits")
        self._commit = cluster.hooks(cfg, "commit")
        self._call("start")

    def _call(self, hook: str) -> None:
        for fn in cluster.hooks(self.cfg, hook):
            fn(self)

    # round handling for the `isolate` control
    def begin_round(self) -> None:
        self._round_base = self.used.copy() if self.isolate else None
        self._call("begin_round")

    def place_job(self, shape=None) -> List[List[tuple]]:
        """Place one job (`shape` as `cluster.job_groups` takes it:
        None is the whole template); returns, per group, (node index,
        the score it was chosen by, what the rules say the alloc holds)
        of every alloc placed (shorter than the group's count where the
        reference finds no room).  Every step scores every node; only
        the chosen node's terms are recomputed between steps, which
        changes no value."""
        seen = self.used if self._round_base is None \
            else self._round_base.copy()
        self._call("begin_job")
        out = []
        for g in cluster.job_groups(self.cfg, shape):
            ask = np.array([g["cpu"], g["mem"], g["disk"]], self.dtype)
            after = (seen + ask).astype(self.dtype)
            fits = self.feasible & (after <= self.cap).all(axis=1)
            for rule_fits in self._fits:
                fits &= rule_fits(self, g)
            binpack = binpack_score(after, self.cap)
            on_node = np.zeros(len(self.plain))       # same job + group
            per_value = [np.zeros(nv) for _i, nv, _w in self.spreads]
            chosen: List[tuple] = []
            for _ in range(g["count"]):
                if not fits.any():
                    break
                boost = 0.0
                if self.use_spread:
                    for (inv, _nv, w), cnt in zip(self.spreads, per_value):
                        boost = boost + _even_spread_boost(cnt)[inv] * w
                final = normalized_score(binpack, on_node, g["count"],
                                         self.affinity, boost)
                looked = fits
                if self.sample and fits.sum() > self.sample:
                    looked = np.zeros(len(fits), bool)
                    looked[self.rng.choice(np.flatnonzero(fits),
                                           self.sample, replace=False)] = True
                ni = int(np.argmax(np.where(looked, final, -np.inf)))
                holds: dict = {}
                for commit in self._commit:
                    holds.update(commit(self, ni, g))
                chosen.append((ni, float(final[ni]), holds))
                seen[ni] = after[ni]
                if seen is not self.used:
                    self.used[ni] = (self.used[ni] + ask).astype(self.dtype)
                after[ni] = (seen[ni] + ask).astype(self.dtype)
                fits[ni] = self.feasible[ni] and bool(
                    (after[ni] <= self.cap[ni]).all()) and all(
                    rule_fits(self, g)[ni] for rule_fits in self._fits)
                binpack[ni] = binpack_score(after[ni], self.cap[ni])
                on_node[ni] += 1
                for (inv, _nv, _w), cnt in zip(self.spreads, per_value):
                    cnt[inv[ni]] += 1
            out.append(chosen)
        return out


def place_sequence(cfg: dict, plain: cluster.PlainNodes, shapes: list,
                   round_jobs: int = 32, **placer_kw) -> Dict[str, object]:
    """Place one job per entry of `shapes` (None: the whole template), in
    rounds of `round_jobs`; returns rows (job number, group number, node
    index, score, what the rules say the alloc holds) and the per-job,
    per-group counts placed."""
    p = Placer(cfg, plain, **placer_kw)
    rows, placed = [], []
    for j, shape in enumerate(shapes):
        if j % round_jobs == 0:
            p.begin_round()
        per_group = p.place_job(shape)
        placed.append([len(c) for c in per_group])
        for gi, chosen in enumerate(per_group):
            rows.extend((j, gi, ni, sc, holds) for ni, sc, holds in chosen)
    return {"rows": rows, "placed": placed}


def bfloat16():
    import ml_dtypes
    return ml_dtypes.bfloat16


#: the controls: name -> Placer keywords.  Each breaks one thing a later
#: PR could be tempted to break; `correct` must come out false on each.
CONTROLS = {
    "bfloat16": lambda: {"dtype": bfloat16()},
    "isolated_round": lambda: {"isolate": True},
    "spread_ignored": lambda: {"use_spread": False},
    "sampled_14_nodes": lambda: {"sample": 14},
}


def controls_of(cfg: dict) -> Dict[str, object]:
    """Every control the configuration may list: those above and its
    rules' own (`CONTROLS = {name: Placer keywords}`), each as a
    function that gives the keywords."""
    out = dict(CONTROLS)
    for rule in cluster.rules_of(cfg):
        for name, kw in getattr(rule, "CONTROLS", {}).items():
            out[name] = lambda kw=kw: dict(kw)
    return out
