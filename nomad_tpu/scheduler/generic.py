"""GenericScheduler: service + batch scheduling through the TPU solver.

Per-eval flow mirrors the reference (scheduler/generic_sched.go:122 Process,
:213 process retry loop, :324 computeJobAllocs, :427 computePlacements) with
one structural change — the reference's per-placement iterator-chain solve
becomes a SINGLE batched Solver.solve() over all of the eval's placements,
the core of the TPU recast (SURVEY §7.1).
"""
from __future__ import annotations

import copy
import functools
import time as _time
from typing import Dict, List, Optional, Tuple

from ..solver.solve import LazyAllocsView, Solver
from ..solver.tensorize import PlacementAsk
from ..structs import (ALLOC_CLIENT_PENDING, ALLOC_DESIRED_RUN,
                       CONSTRAINT_DISTINCT_PROPERTY, EVAL_STATUS_BLOCKED,
                       EVAL_STATUS_COMPLETE, EVAL_STATUS_FAILED,
                       EVAL_TRIGGER_ALLOC_STOP, EVAL_TRIGGER_DEPLOYMENT_WATCHER,
                       EVAL_TRIGGER_DEPLOYMENT_PROMOTION,
                       EVAL_TRIGGER_FAILED_FOLLOW_UP,
                       EVAL_TRIGGER_JOB_DEREGISTER, EVAL_TRIGGER_JOB_REGISTER,
                       EVAL_TRIGGER_MAX_PLANS, EVAL_TRIGGER_NODE_DRAIN,
                       EVAL_TRIGGER_NODE_UPDATE, EVAL_TRIGGER_PERIODIC_JOB,
                       EVAL_TRIGGER_PREEMPTION, EVAL_TRIGGER_QUEUED_ALLOCS,
                       EVAL_TRIGGER_RETRY_FAILED_ALLOC,
                       EVAL_TRIGGER_ROLLING_UPDATE, EVAL_TRIGGER_SCALING,
                       AllocDeploymentStatus, Allocation, Evaluation, Job,
                       Plan, RescheduleEvent, RescheduleTracker, TaskGroup,
                       resolve_node_target)
from ..utils.ids import generate_uuid
from ..utils.tracing import NULL_SPAN, global_tracer as _tr
from . import feasible as hostfeas
from .reconcile import (AllocDestructiveResult, AllocPlaceResult, Reconciler)
from .util import (adjust_queued_allocations, generic_alloc_update_fn,
                   tainted_nodes, update_non_terminal_allocs_to_lost)

MAX_SERVICE_ATTEMPTS = 5
MAX_BATCH_ATTEMPTS = 2

BLOCKED_EVAL_MAX_PLAN_DESC = "created due to placement conflicts"
BLOCKED_EVAL_FAILED_PLACEMENTS_DESC = "created to place remaining allocations"

_VALID_TRIGGERS = {
    EVAL_TRIGGER_JOB_REGISTER, EVAL_TRIGGER_JOB_DEREGISTER,
    EVAL_TRIGGER_NODE_DRAIN, EVAL_TRIGGER_NODE_UPDATE,
    EVAL_TRIGGER_ALLOC_STOP, EVAL_TRIGGER_ROLLING_UPDATE,
    EVAL_TRIGGER_QUEUED_ALLOCS, EVAL_TRIGGER_PERIODIC_JOB,
    EVAL_TRIGGER_MAX_PLANS, EVAL_TRIGGER_DEPLOYMENT_WATCHER,
    EVAL_TRIGGER_DEPLOYMENT_PROMOTION,
    EVAL_TRIGGER_RETRY_FAILED_ALLOC, EVAL_TRIGGER_FAILED_FOLLOW_UP,
    EVAL_TRIGGER_PREEMPTION, EVAL_TRIGGER_SCALING,
}


def _layer(name: str):
    """Mark a scheduler phase as one layer span of its eval's trace."""
    def mark(fn):
        @functools.wraps(fn)
        def phase(self, *args, **kw):
            with _tr.layer(name, self.eval.id):
                return fn(self, *args, **kw)
        return phase
    return mark


class _Missing:
    """One pending placement: a reconciler place/destructive result bound
    to its task group."""

    def __init__(self, name: str, tg: TaskGroup,
                 previous: Optional[Allocation] = None,
                 reschedule: bool = False, canary: bool = False,
                 stop_previous: bool = False, stop_desc: str = ""):
        self.name = name
        self.tg = tg
        self.previous = previous
        self.reschedule = reschedule
        self.canary = canary
        self.stop_previous = stop_previous
        self.stop_desc = stop_desc


class GenericScheduler:
    """Schedules service and batch jobs (reference: generic_sched.go:77)."""

    def __init__(self, state, planner, batch: bool = False,
                 solver: Optional[Solver] = None):
        self.state = state
        self.planner = planner
        self.batch = batch
        self.solver = solver or Solver()

        self.eval: Optional[Evaluation] = None
        self.job: Optional[Job] = None
        self.plan: Optional[Plan] = None
        self.plan_result = None
        self.deployment = None
        self.blocked: Optional[Evaluation] = None
        self.failed_tg_allocs: Dict[str, object] = {}
        self.queued_allocs: Dict[str, int] = {}
        self.followup_evals: List[Evaluation] = []
        self._class_eligibility: Dict[str, bool] = {}
        self._escaped = False

    # ------------------------------------------------------------------ API
    def process(self, evaluation: Evaluation) -> Optional[str]:
        self.eval = evaluation
        if evaluation.triggered_by not in _VALID_TRIGGERS:
            desc = f"scheduler cannot handle '{evaluation.triggered_by}'"
            self._set_status(EVAL_STATUS_FAILED, desc)
            return None

        limit = MAX_BATCH_ATTEMPTS if self.batch else MAX_SERVICE_ATTEMPTS
        progress = {"made": False}

        def once() -> Tuple[bool, Optional[str]]:
            progress["made"] = False
            done, err = self._process(progress)
            return done, err

        attempts = 0
        err: Optional[str] = None
        while attempts < limit:
            done, err = once()
            if err is not None or done:
                break
            attempts = 0 if progress["made"] else attempts + 1
        else:
            # retries exhausted: roll remaining work into a blocked eval
            if self.eval.status != EVAL_STATUS_BLOCKED:
                self._create_blocked_eval(planning_failure=True)
            err = "maximum attempts reached"
            self._set_status(EVAL_STATUS_FAILED, err)
            return None

        if err is not None:
            self._set_status(EVAL_STATUS_FAILED, str(err))
            return err
        self._set_status(EVAL_STATUS_COMPLETE, "")
        return None

    # ------------------------------------------------------------ internals
    def _process(self, progress) -> Tuple[bool, Optional[str]]:
        with _tr.layer("sched.snapshot", self.eval.id):
            snapshot = (self.state.snapshot()
                        if hasattr(self.state, "snapshot") else self.state)
        missing, err = self._begin(self.eval, snapshot)
        if err is not None:
            return False, err
        if missing:
            err = self._compute_placements(snapshot, missing)
            if err is not None:
                return False, err
        return self._finalize(progress)

    def _begin(self, ev: Evaluation, snapshot
               ) -> Tuple[List["_Missing"], Optional[str]]:
        """Everything before the device solve: reconcile and assemble the
        plan skeleton. Returns the pending placements."""
        self.eval = ev
        with _tr.layer("sched.reconcile", ev.id):
            self.snapshot = snapshot
            self.job = snapshot.job_by_id(ev.namespace, ev.job_id)
            self.failed_tg_allocs = {}
            self.queued_allocs = {}
            self.followup_evals = []
            self._sticky_probes = []
            self._unfinished = 0
            self.plan = ev.make_plan(self.job)

            if not self.batch:
                self.deployment = snapshot.latest_deployment_by_job(
                    ev.namespace, ev.job_id)
                if self.deployment is not None \
                        and not self.deployment.active():
                    self.deployment = None
            else:
                self.deployment = None
            return self._compute_job_allocs(snapshot)

    def _finalize(self, progress) -> Tuple[bool, Optional[str]]:
        """Everything after the solve: blocked/follow-up evals and plan
        submission. Returns (done, err); not-done means retry."""
        ev = self.eval
        # blocked eval for any failed placements
        if (ev.status != EVAL_STATUS_BLOCKED and self.failed_tg_allocs
                and self.blocked is None):
            self._create_blocked_eval(planning_failure=False)

        # follow-up evals for delayed reschedules
        for fev in self.followup_evals:
            fev.previous_eval = ev.id
            self.planner.create_eval(fev)

        # placements the solve's wave budget left undecided are not
        # failures: submit what was decided, then go round again (a
        # fused batch of a hundred evals aiming at the same best nodes
        # routinely leaves some).  A blocked eval would wait for a
        # capacity change that, with capacity plentiful, need not come.
        done = self._unfinished == 0
        if self.plan.is_no_op() and not ev.annotate_plan:
            return done, None

        result, new_state = self.planner.submit_plan(self.plan)
        if result is None:
            return False, "plan submission failed"
        self.plan_result = result
        adjust_queued_allocations(result, self.queued_allocs)
        # progress = the applied result actually changed state (reference:
        # progressMade) — a bare snapshot refresh doesn't reset the budget
        progress["made"] = bool(result.node_update or result.node_allocation
                                or result.deployment
                                or result.deployment_updates)

        if new_state is not None:
            self.state = new_state
            return False, None
        full, _expected, _actual = result.full_commit(self.plan)
        if not full:
            return False, None
        return done, None

    def _compute_job_allocs(self, snapshot
                            ) -> Tuple[List["_Missing"], Optional[str]]:
        ev = self.eval
        allocs = snapshot.allocs_by_job(ev.namespace, ev.job_id)
        tainted = tainted_nodes(snapshot, allocs)
        update_non_terminal_allocs_to_lost(self.plan, tainted, allocs)

        reconciler = Reconciler(
            generic_alloc_update_fn(snapshot, self.plan), self.batch,
            ev.job_id, self.job, self.deployment, allocs, tainted, ev.id)
        results = reconciler.compute()

        if ev.annotate_plan:
            self.plan.annotations = {
                "desired_tg_updates": results.desired_tg_updates}

        self.plan.deployment = results.deployment
        self.plan.deployment_updates = results.deployment_updates
        if results.deployment is not None:
            self.deployment = results.deployment

        for group_evals in results.desired_followup_evals.values():
            self.followup_evals.extend(group_evals)

        for stop in results.stop:
            self.plan.append_stopped_alloc(stop.alloc, stop.status_description,
                                           stop.client_status)

        dep_id = self.deployment.id if self.deployment else ""
        for update in results.inplace_update:
            if update.deployment_id != dep_id:
                update.deployment_id = dep_id
                update.deployment_status = None
            self.plan.append_alloc(update)

        for update in results.attribute_updates.values():
            self.plan.append_alloc(update)

        if not results.place and not results.destructive_update:
            if self.job is not None:
                for tg in self.job.task_groups:
                    self.queued_allocs[tg.name] = 0
            return [], None

        for p in results.place:
            self.queued_allocs[p.task_group.name] = \
                self.queued_allocs.get(p.task_group.name, 0) + 1
        for d in results.destructive_update:
            self.queued_allocs[d.place_task_group.name] = \
                self.queued_allocs.get(d.place_task_group.name, 0) + 1
        _tr.event(ev.id, "schedule.reconcile",
                  n_place=len(results.place),
                  n_destructive=len(results.destructive_update),
                  n_stop=len(results.stop),
                  n_inplace=len(results.inplace_update))

        missing: List[_Missing] = []
        # destructive replacements go first so their capacity frees up for
        # the batch (reference passes destructive before place)
        for d in results.destructive_update:
            missing.append(_Missing(
                name=d.place_name, tg=d.place_task_group,
                previous=d.stop_alloc, stop_previous=True,
                stop_desc=d.stop_status_description))
        for p in results.place:
            missing.append(_Missing(
                name=p.name, tg=p.task_group, previous=p.previous_alloc,
                reschedule=p.reschedule, canary=p.canary))
        return missing, None

    # ----------------------------------------------------- placement solve
    def _compute_placements(self, snapshot, missing: List[_Missing]
                            ) -> Optional[str]:
        prep = self._prepare_placements(snapshot, missing)
        if prep is None:
            return None
        nodes, by_dc, allocs_by_node, asks, ask_missing = prep
        # proposed-state corrections for the solver's resident world:
        # this plan's eager stops and the sticky probes are the ONLY
        # places the proposed usage differs from the store-tracked one
        stops = [a for lst in self.plan.node_update.values()
                 for a in lst]
        from .preemption import preemption_enabled
        preempt_ok = preemption_enabled(
            snapshot.scheduler_config(),
            "batch" if self.batch else "service")
        span = _tr.stage(self.eval.id, "solve",
                         job_id=self.eval.job_id, fused=False)
        out = self.solver.solve(
            nodes, asks, allocs_by_node, by_dc, snapshot=snapshot,
            proposed_delta=(stops, list(self._sticky_probes)),
            preempt=preempt_ok)
        self._consume_solve(snapshot, out, nodes, allocs_by_node, missing,
                            ask_missing, span=span)
        return None

    @_layer("sched.prepare")
    def _prepare_placements(self, snapshot, missing: List[_Missing],
                            nodes=None, by_dc=None, allocs_by_node=None,
                            node_by_id=None):
        """Pre-solve work: eager destructive stops, sticky placements and
        per-tg ask assembly. Returns (nodes, by_dc, allocs_by_node, asks,
        ask_missing), or None when nothing remains for the solver.
        The ready nodes, their DC counts and id map come from the
        snapshot's shared view (read-only); the fleet path passes the
        round's nodes/by_dc/node_by_id and allocs_by_node so evals in
        one batch see the same world."""
        if self.job is None:
            return None
        if nodes is None:
            nodes, by_dc, node_by_id = snapshot.ready_node_view(
                self.job.datacenters)
        if not nodes:
            for m in missing:
                self._record_failure(m, None)
            self._stop_destructive_for_failed(missing, set())
            return None

        # stop the old allocs of destructive updates up front — the plan
        # applier frees that capacity for the replacements
        for m in missing:
            if m.stop_previous and m.previous is not None:
                self.plan.append_stopped_alloc(m.previous, m.stop_desc, "")

        # proposed live allocs by node: state minus plan stops.  With a
        # resident solver world the eager O(cluster) walk collapses to a
        # lazy per-node view — the solve reads usage from the
        # delta-maintained tensors, and the host fixups only ever touch
        # the chosen candidates' nodes
        if allocs_by_node is None:
            stopped_ids = {a.id for allocs in self.plan.node_update.values()
                           for a in allocs}
            if self.solver.resident_active(snapshot):
                allocs_by_node = LazyAllocsView(snapshot, stopped_ids)
            else:
                allocs_by_node = {}
                for n in nodes:
                    live = [a for a in snapshot.allocs_by_node(n.id)
                            if not a.terminal_status()
                            and a.id not in stopped_ids]
                    if live:
                        allocs_by_node[n.id] = live

        # sticky-disk placements prefer their previous node (reference:
        # generic_sched.go:628 findPreferredNode)
        batch_missing: List[_Missing] = []
        sticky_done: List[Tuple[_Missing, object, object]] = []
        for m in missing:
            pref = self._preferred_node(m, node_by_id)
            if pref is not None:
                placed = self._try_node(snapshot, pref, m, allocs_by_node)
                if placed is not None:
                    sticky_done.append((m, pref, placed))
                    continue
            batch_missing.append(m)
        for m, node, resources in sticky_done:
            self._emit_alloc(m, node, resources, score=0.0, metrics=None)

        if not batch_missing:
            return None

        # ---- group the remaining placements into per-tg asks ----
        by_tg: Dict[str, List[_Missing]] = {}
        for m in batch_missing:
            by_tg.setdefault(m.tg.name, []).append(m)

        # this job's proposed live allocs by node — the only slice the
        # anti-affinity / distinct / spread seeds ever read
        job_allocs = self._job_allocs_by_node(snapshot, allocs_by_node,
                                              node_by_id)
        proposed_by_job_tg: Dict[str, Dict[str, int]] = {}
        for nid, live in job_allocs.items():
            for a in live:
                proposed_by_job_tg.setdefault(
                    a.task_group, {}).setdefault(nid, 0)
                proposed_by_job_tg[a.task_group][nid] += 1

        asks: List[PlacementAsk] = []
        ask_missing: List[List[_Missing]] = []
        for tg_name, ms in by_tg.items():
            tg = ms[0].tg
            csi_err, csi_blocked = self._csi_state(snapshot, tg, nodes)
            if csi_err is not None:
                # a required CSI volume is missing or unclaimable: the
                # group cannot place anywhere (reference:
                # CSIVolumeChecker, feasible.go:194)
                from ..structs import AllocMetric
                metric = AllocMetric()
                metric.constraint_filtered = {csi_err: len(nodes)}
                metric.coalesced_failures = max(len(ms) - 1, 0)
                self.failed_tg_allocs[tg.name] = metric
                # a destructive update whose replacement cannot place
                # must keep its old alloc running: retract the eager
                # stops this group queued
                for m in ms:
                    if m.stop_previous and m.previous is not None:
                        lst = self.plan.node_update.get(
                            m.previous.node_id, [])
                        self.plan.node_update[m.previous.node_id] = [
                            a for a in lst if a.id != m.previous.id]
                        if not self.plan.node_update[m.previous.node_id]:
                            del self.plan.node_update[m.previous.node_id]
                continue
            penalty = frozenset(
                m.previous.node_id for m in ms
                if m.reschedule and m.previous is not None)
            existing = dict(proposed_by_job_tg.get(tg_name, {}))
            blocked, prop_limits = self._distinct_state(
                snapshot, tg, job_allocs, node_by_id)
            spread_seed = self._spread_seed(tg, job_allocs, node_by_id)
            asks.append(PlacementAsk(
                job=self.job, tg=tg, count=len(ms),
                penalty_nodes=penalty, existing_by_node=existing,
                distinct_hosts_blocked=blocked | csi_blocked,
                spread_seed=spread_seed,
                property_limits=prop_limits))
            ask_missing.append(ms)
        if not asks:
            return None
        return nodes, by_dc, allocs_by_node, asks, ask_missing

    def _csi_state(self, snapshot, tg, nodes):
        """CSI volume feasibility (reference: CSIVolumeChecker,
        feasible.go:194): every requested csi volume must exist, be
        schedulable, and have write capacity for writable requests;
        nodes not running the volume's plugin (healthy) are excluded
        from placement. Returns (fatal_reason | None, blocked_node_ids)."""
        vols = [(name, v) for name, v in tg.volumes.items()
                if v.type == "csi"]
        if not vols:
            return None, frozenset()
        blocked = set()
        for name, req in vols:
            vol = snapshot.csi_volume_by_id(self.job.namespace,
                                            req.source)
            if vol is None:
                return f"missing CSI volume {req.source}", frozenset()
            if not vol.schedulable:
                return f"CSI volume {req.source} unschedulable", \
                    frozenset()
            if not req.read_only and not vol.write_free():
                return (f"CSI volume {req.source} has exhausted its "
                        "write claims"), frozenset()
            for n in nodes:
                info = n.csi_node_plugins.get(vol.plugin_id)
                if info is None or not info.healthy:
                    blocked.add(n.id)
        return None, frozenset(blocked)

    @_layer("sched.plan_build")
    def _consume_solve(self, snapshot, out, nodes, allocs_by_node,
                       missing: List[_Missing],
                       ask_missing: List[List[_Missing]],
                       span=None) -> None:
        """Post-solve work: emit allocs, preempt or record failures, and
        retract eager stops for failed destructive replacements. `out`
        placements must use ask indexes local to `ask_missing`.
        `span`: the eval's open solve trace span — ended here with the
        device counters (out.trace)."""
        # map solver placements (contiguous per ask) back to missing
        from .preemption import preemption_enabled
        preempt_ok = preemption_enabled(
            snapshot.scheduler_config(), "batch" if self.batch else "service")
        # per-ask consume cursors instead of pop(0) list churn
        queues = [list(ms) for ms in ask_missing]
        cursor = [0] * len(queues)
        failed: set = set()
        for placement in out.placements:
            g = placement.ask_index
            m = queues[g][cursor[g]]
            cursor[g] += 1
            if placement.node is None:
                if placement.retryable:
                    # undecided, not failed: no preemption, no blocked
                    # eval — but a destructive update keeps its old
                    # alloc until the replacement lands
                    self._unfinished += 1
                    failed.add(id(m))
                    continue
                if not (preempt_ok and self._try_preemption(
                        nodes, m, allocs_by_node)):
                    self._record_failure(m, placement)
                    failed.add(id(m))
                continue
            if placement.evicted:
                # the in-kernel preemption pass already selected this
                # placement's victim set (solver/kernel.py eviction
                # waves) — commit the (place, evict) pair without the
                # host-side walk
                self._commit_kernel_eviction(placement, m,
                                             allocs_by_node)
                continue
            self._emit_alloc(m, placement.node, placement.resources,
                             placement.score, placement.metrics)

        if self.failed_tg_allocs:
            # remember per-class eligibility for the blocked eval
            for elig in out.class_eligibility:
                self._class_eligibility.update(elig)
        self._stop_destructive_for_failed(missing, failed)
        if span is not None and span is not NULL_SPAN:
            span.set(**(getattr(out, "trace", None) or {}))
            span.end()

    def _stop_destructive_for_failed(self, missing: List[_Missing],
                                     failed: set) -> None:
        """A destructive update whose replacement failed to place must keep
        its old alloc running: retract the eager stop."""
        for m in missing:
            if not (m.stop_previous and m.previous is not None):
                continue
            if id(m) in failed:
                lst = self.plan.node_update.get(m.previous.node_id, [])
                self.plan.node_update[m.previous.node_id] = [
                    a for a in lst if a.id != m.previous.id]
                if not self.plan.node_update[m.previous.node_id]:
                    del self.plan.node_update[m.previous.node_id]

    def _commit_kernel_eviction(self, placement, m: _Missing,
                                allocs_by_node) -> None:
        """Commit a (place, evict) pair the device eviction pass
        selected: victims leave via plan.node_preemptions, the alloc
        lands with preempted_allocations set, and the shared
        allocs_by_node view advances so later placements (and the
        host-side fallback walk) see both sides."""
        from ..utils.metrics import global_metrics as _m
        _m.incr_counter("scheduler.preempt.kernel")
        node = placement.node
        vset = set(placement.evicted)
        proposed = list(allocs_by_node.get(node.id, ())) \
            if allocs_by_node is not None else []
        victims = [a for a in proposed if a.id in vset]
        alloc = self._emit_alloc(m, node, placement.resources,
                                 placement.score, placement.metrics)
        alloc.preempted_allocations = sorted(vset)
        if allocs_by_node is not None:
            allocs_by_node[node.id] = [a for a in proposed
                                       if a.id not in vset] + [alloc]
        for v in victims:
            self.plan.append_preempted_alloc(v, alloc.id)

    def _try_preemption(self, nodes, m: _Missing, allocs_by_node) -> bool:
        """Second pass for an exhausted placement: across ALL feasible
        nodes, find victim sets (task-group resources, then network and
        device dimensions — preemption.find_preemption) and place on the
        BEST node — highest bin-pack score after eviction, matching the
        reference where preemption options feed the regular rank/max
        pipeline (preemption.go wired via rank.go BinPackIterator) —
        not the first node that works.  Counted as the host-side
        FALLBACK — ISSUE 7 steady state should select evictions
        in-kernel instead (scheduler.preempt.kernel)."""
        from ..structs.funcs import score_fit, allocs_fit
        from ..utils.metrics import global_metrics as _m
        from .preemption import find_preemption
        _m.incr_counter("scheduler.preempt.host_fallback")

        best = None                # (score, node, victims, resources)
        for node in nodes:
            ok, _why = hostfeas.group_feasible(node, self.job, m.tg)
            if not ok:
                continue
            proposed = allocs_by_node.get(node.id, [])
            victims = find_preemption(node, proposed, self.job, m.tg)
            if not victims:
                continue
            victim_ids = {v.id for v in victims}
            remaining = [a for a in proposed if a.id not in victim_ids]
            trial = dict(allocs_by_node)
            trial[node.id] = remaining
            resources = self.solver._host_commit(
                node, 0, PlacementAsk(job=self.job, tg=m.tg, count=1),
                {}, {}, trial)
            if resources is None:
                continue
            probe = Allocation(id="probe", task_group=m.tg.name,
                               allocated_resources=resources)
            fit, _dim, used = allocs_fit(node, remaining + [probe])
            if not fit:
                continue
            score = score_fit(node, used)
            if best is None or score > best[0]:
                best = (score, node, victims, resources)
        if best is None:
            return False
        _score, node, victims, resources = best
        victim_ids = {v.id for v in victims}
        remaining = [a for a in allocs_by_node.get(node.id, [])
                     if a.id not in victim_ids]
        alloc = self._emit_alloc(m, node, resources, _score, None)
        alloc.preempted_allocations = sorted(victim_ids)
        # later placements must see both the evictions and the new
        # alloc's usage
        allocs_by_node[node.id] = remaining + [alloc]
        for v in victims:
            self.plan.append_preempted_alloc(v, alloc.id)
        return True

    def _preferred_node(self, m: _Missing, node_by_id):
        if m.previous is None or not m.tg.ephemeral_disk.sticky:
            return None
        return node_by_id.get(m.previous.node_id)

    def _try_node(self, snapshot, node, m: _Missing, allocs_by_node):
        """Host-side single-node feasibility + commit for sticky placements."""
        ok, _reason = hostfeas.group_feasible(node, self.job, m.tg)
        if not ok:
            return None
        resources = self.solver._host_commit(
            node, 0, PlacementAsk(job=self.job, tg=m.tg, count=1),
            {}, {}, allocs_by_node)
        if resources is None:
            return None
        from ..structs.funcs import allocs_fit
        live = list(allocs_by_node.get(node.id, []))
        probe = Allocation(id=generate_uuid(), job=self.job,
                           job_id=self.job.id, node_id=node.id,
                           allocated_resources=resources,
                           task_group=m.tg.name)
        fit, _dim, _used = allocs_fit(node, live + [probe])
        if not fit:
            return None
        allocs_by_node.setdefault(node.id, []).append(probe)
        # tracked separately: the solver's resident world overlays probe
        # usage onto its delta-maintained tensors instead of re-walking
        # allocs_by_node
        self._sticky_probes.append(probe)
        return resources

    def _job_allocs_by_node(self, snapshot, allocs_by_node, node_by_id
                            ) -> Dict[str, List[Allocation]]:
        """This job's proposed live allocs grouped by node — equal to
        filtering allocs_by_node down to job_id, but O(job) via the job
        index (plus the tracked sticky probes) when the view is lazy,
        so the seed walks never materialize the cluster."""
        out: Dict[str, List[Allocation]] = {}
        if isinstance(allocs_by_node, LazyAllocsView):
            for a in snapshot.allocs_by_job(self.job.namespace,
                                            self.job.id):
                if (a.terminal_status() or a.id in allocs_by_node.excluded
                        or a.node_id not in node_by_id):
                    continue
                out.setdefault(a.node_id, []).append(a)
            for p in self._sticky_probes:
                out.setdefault(p.node_id, []).append(p)
            return out
        for nid, live in allocs_by_node.items():
            lst = [a for a in live if a.job_id == self.job.id]
            if lst:
                out[nid] = lst
        return out

    def _distinct_state(self, snapshot, tg: TaskGroup, job_allocs,
                        node_by_id):
        """Existing-state inputs for distinct_hosts / distinct_property.
        `job_allocs` is this job's proposed live allocs by node
        (_job_allocs_by_node)."""
        blocked = set()
        merged = hostfeas.merged_constraints(self.job, tg)
        has_job_distinct = any(
            c.operand == "distinct_hosts" for c in self.job.constraints)
        has_distinct = has_job_distinct or any(
            c.operand == "distinct_hosts" for c in merged)
        if has_distinct:
            for nid, live in job_allocs.items():
                for a in live:
                    if has_job_distinct or a.task_group == tg.name:
                        blocked.add(nid)
                        break
        # distinct_property limits, keyed by (scope, target) so job-level
        # charges are shared across the job's asks in one batch while
        # tg-level ones count only that group's allocs
        prop_limits: Dict[Tuple[str, str], Tuple[int, Dict[str, int]]] = {}

        def add_prop(c, job_scope: bool) -> None:
            limit = 1
            if c.rtarget:
                try:
                    limit = int(c.rtarget)
                except ValueError:
                    limit = 1
            counts: Dict[str, int] = {}
            for nid, live in job_allocs.items():
                n_cnt = sum(
                    1 for a in live
                    if job_scope or a.task_group == tg.name)
                if not n_cnt:
                    continue
                node = node_by_id.get(nid)
                if node is None:
                    continue
                val, ok = resolve_node_target(node, c.ltarget)
                if ok:
                    counts[str(val)] = counts.get(str(val), 0) + n_cnt
            # include the job id: the fused fleet solve mixes asks from
            # multiple jobs in one Solver.solve() with a shared prop_used
            # map, so scope keys must not collide across jobs
            ns = self.job.namespace
            key = (f"job:{ns}:{self.job.id}" if job_scope
                   else f"tg:{ns}:{self.job.id}:{tg.name}", c.ltarget)
            prop_limits[key] = (limit, counts)

        for c in self.job.constraints:
            if c.operand == CONSTRAINT_DISTINCT_PROPERTY:
                add_prop(c, True)
        tg_cons = list(tg.constraints)
        for t in tg.tasks:
            tg_cons.extend(t.constraints)
        for c in tg_cons:
            if c.operand == CONSTRAINT_DISTINCT_PROPERTY:
                add_prop(c, False)
        return frozenset(blocked), prop_limits

    def _spread_seed(self, tg: TaskGroup, job_allocs, node_by_id):
        seed: Dict[str, Dict[str, int]] = {}
        spreads = list(self.job.spreads) + list(tg.spreads)
        if not spreads:
            return seed
        for sp in spreads:
            counts: Dict[str, int] = {}
            for nid, live in job_allocs.items():
                n_tg = sum(1 for a in live
                           if a.task_group == tg.name)
                if not n_tg:
                    continue
                node = node_by_id.get(nid)
                if node is None:
                    continue
                val, ok = resolve_node_target(node, sp.attribute)
                if ok:
                    counts[str(val)] = counts.get(str(val), 0) + n_tg
            seed[sp.attribute] = counts
        return seed

    # ------------------------------------------------------------- results
    def _emit_alloc(self, m: _Missing, node, resources, score: float,
                    metrics) -> Allocation:
        from ..structs import AllocMetric
        now = _time.time()
        alloc = Allocation(
            id=generate_uuid(), namespace=self.eval.namespace,
            eval_id=self.eval.id, name=m.name, job_id=self.job.id,
            job=self.job, task_group=m.tg.name, node_id=node.id,
            node_name=node.name,
            allocated_resources=resources,
            metrics=metrics or AllocMetric(),
            desired_status=ALLOC_DESIRED_RUN,
            client_status=ALLOC_CLIENT_PENDING,
            deployment_id=self.deployment.id if self.deployment else "",
            create_time=now, modify_time=now)
        if metrics is not None:
            metrics.scores = {node.id: score}
        if m.previous is not None:
            alloc.previous_allocation = m.previous.id
            if m.reschedule:
                _update_reschedule_tracker(alloc, m.previous, now)
        if m.canary and self.deployment is not None:
            alloc.deployment_status = AllocDeploymentStatus(canary=True)
        self.plan.append_alloc(alloc)
        return alloc

    def _record_failure(self, m: _Missing, placement) -> None:
        from ..structs import AllocMetric
        existing = self.failed_tg_allocs.get(m.tg.name)
        if existing is not None:
            existing.coalesced_failures += 1
            return
        metric = placement.metrics if placement is not None else AllocMetric()
        self.failed_tg_allocs[m.tg.name] = metric

    def _create_blocked_eval(self, planning_failure: bool) -> None:
        escaped = self._escaped or not self._class_eligibility
        blocked = self.eval.create_blocked_eval(
            self._class_eligibility, escaped, "")
        # the scheduling snapshot's index, so BlockedEvals can detect
        # capacity changes that raced this eval (missed-unblock check)
        blocked.snapshot_index = getattr(self.snapshot, "index", 0)
        if planning_failure:
            blocked.triggered_by = EVAL_TRIGGER_MAX_PLANS
            blocked.status_description = BLOCKED_EVAL_MAX_PLAN_DESC
        else:
            blocked.status_description = BLOCKED_EVAL_FAILED_PLACEMENTS_DESC
        self.planner.create_eval(blocked)
        self.blocked = blocked

    def _set_status(self, status: str, description: str) -> None:
        ev = copy.copy(self.eval)
        ev.status = status
        ev.status_description = description
        if self.blocked is not None:
            ev.blocked_eval = self.blocked.id
        ev.failed_tg_allocs = dict(self.failed_tg_allocs)
        ev.queued_allocations = dict(self.queued_allocs)
        if self.deployment is not None and status == EVAL_STATUS_COMPLETE:
            ev.deployment_id = self.deployment.id
        self.planner.update_eval(ev)


def _update_reschedule_tracker(alloc: Allocation, prev: Allocation,
                               now: float) -> None:
    """Carry the reschedule history onto the replacement (reference:
    generic_sched.go:591 updateRescheduleTracker — keeps events within the
    policy interval, appends this reschedule)."""
    policy = None
    if prev.job is not None:
        tg = prev.job.lookup_task_group(prev.task_group)
        if tg is not None:
            policy = tg.reschedule_policy
    events: List[RescheduleEvent] = []
    if prev.reschedule_tracker:
        if policy is not None and not policy.unlimited and policy.interval_s:
            window = now - policy.interval_s
            events = [e for e in prev.reschedule_tracker.events
                      if e.reschedule_time > window]
        else:
            events = list(prev.reschedule_tracker.events)
    delay = prev.next_delay(policy) if policy is not None else 0.0
    events.append(RescheduleEvent(
        reschedule_time=now, prev_alloc_id=prev.id,
        prev_node_id=prev.node_id, delay_s=delay))
    alloc.reschedule_tracker = RescheduleTracker(events=events)
