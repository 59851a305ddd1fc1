"""GenericScheduler end-to-end-through-harness tests, mirroring key
scheduler/generic_sched_test.go cases."""
import time

from nomad_tpu import mock, structs
from nomad_tpu.scheduler.harness import Harness
from nomad_tpu.structs import (ALLOC_CLIENT_FAILED, ALLOC_CLIENT_RUNNING,
                               EVAL_STATUS_BLOCKED, EVAL_STATUS_COMPLETE,
                               TaskState, UpdateStrategy, alloc_name)


def setup_cluster(h: Harness, n_nodes=10):
    nodes = [mock.node() for _ in range(n_nodes)]
    for n in nodes:
        h.store.upsert_node(h.next_index(), n)
    return nodes


def register_job(h: Harness, job):
    h.store.upsert_job(h.next_index(), job)
    ev = mock.eval_(job_id=job.id, type=job.type,
                    triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER)
    h.store.upsert_evals(h.next_index(), [ev])
    return ev


def test_job_register_places_all():
    h = Harness()
    setup_cluster(h)
    job = mock.job()           # count=10
    ev = register_job(h, job)
    h.process("service", ev)

    assert len(h.plans) == 1
    out = h.store.allocs_by_job("default", job.id)
    assert len(out) == 10
    names = sorted(a.name for a in out)
    assert names == sorted(alloc_name(job.id, "web", i) for i in range(10))
    # eval acked complete with zero queued
    assert h.evals[-1].status == EVAL_STATUS_COMPLETE
    assert h.evals[-1].queued_allocations.get("web", 0) == 0
    # placements carry explainability metrics
    a = out[0]
    assert a.metrics.nodes_evaluated == 10
    assert a.metrics.score_meta


def test_job_register_no_nodes_creates_blocked_eval():
    h = Harness()
    job = mock.job()
    ev = register_job(h, job)
    h.process("service", ev)
    assert not h.store.allocs_by_job("default", job.id)
    assert len(h.create_evals) == 1
    blocked = h.create_evals[0]
    assert blocked.status == EVAL_STATUS_BLOCKED
    assert h.evals[-1].status == EVAL_STATUS_COMPLETE
    assert "web" in h.evals[-1].failed_tg_allocs
    assert h.evals[-1].queued_allocations["web"] == 10


def test_partial_capacity_places_some_blocks_rest():
    h = Harness()
    # 2 nodes, each fits 2 groups (500 cpu / 256mb each; node 3900/7936)
    nodes = [mock.node() for _ in range(2)]
    for n in nodes:
        n.node_resources.cpu = 1200
        n.node_resources.memory_mb = 1024
        n.reserved_resources.cpu = 100
        n.reserved_resources.memory_mb = 0
        h.store.upsert_node(h.next_index(), n)
    job = mock.job()
    for tg in job.task_groups:
        for t in tg.tasks:
            t.resources.networks = []
        tg.count = 6
    ev = register_job(h, job)
    h.process("service", ev)
    out = [a for a in h.store.allocs_by_job("default", job.id)]
    assert len(out) == 4        # 2 per node
    assert len(h.create_evals) == 1
    assert h.evals[-1].queued_allocations["web"] == 2


def test_scale_down_stops_extra():
    h = Harness()
    setup_cluster(h, 5)
    job = mock.job()
    job.task_groups[0].count = 5
    ev = register_job(h, job)
    h.process("service", ev)
    assert len([a for a in h.store.allocs_by_job("default", job.id)
                if not a.terminal_status()]) == 5

    job2 = mock.job(id=job.id)
    job2.task_groups[0].count = 3
    job2.version = 1
    ev2 = register_job(h, job2)
    h.process("service", ev2)
    live = [a for a in h.store.allocs_by_job("default", job.id)
            if not a.server_terminal_status()]
    assert len(live) == 3


def test_job_deregister_stops_all():
    h = Harness()
    setup_cluster(h, 3)
    job = mock.job()
    job.task_groups[0].count = 3
    ev = register_job(h, job)
    h.process("service", ev)

    job2 = mock.job(id=job.id)
    job2.stop = True
    job2.version = 1
    h.store.upsert_job(h.next_index(), job2)
    ev2 = mock.eval_(job_id=job.id,
                     triggered_by=structs.EVAL_TRIGGER_JOB_DEREGISTER)
    h.process("service", ev2)
    live = [a for a in h.store.allocs_by_job("default", job.id)
            if not a.server_terminal_status()]
    assert not live


def test_node_down_reschedules():
    h = Harness()
    nodes = setup_cluster(h, 4)
    job = mock.job()
    job.task_groups[0].count = 4
    job.task_groups[0].reschedule_policy = structs.ReschedulePolicy(
        unlimited=True, delay_s=0, delay_function="constant")
    ev = register_job(h, job)
    h.process("service", ev)
    allocs = h.store.allocs_by_job("default", job.id)
    victim_node = allocs[0].node_id
    for a in allocs:
        a.client_status = ALLOC_CLIENT_RUNNING
    h.store.upsert_allocs(h.next_index(), allocs)

    h.store.update_node_status(h.next_index(), victim_node,
                               structs.NODE_STATUS_DOWN)
    ev2 = mock.eval_(job_id=job.id,
                     triggered_by=structs.EVAL_TRIGGER_NODE_UPDATE)
    h.process("service", ev2)
    live = [a for a in h.store.allocs_by_job("default", job.id)
            if not a.terminal_status()]
    on_victim = [a for a in live if a.node_id == victim_node]
    assert not on_victim
    lost = [a for a in h.store.allocs_by_job("default", job.id)
            if a.client_status == structs.ALLOC_CLIENT_LOST]
    assert lost


def test_destructive_update_rolls_with_max_parallel():
    h = Harness()
    setup_cluster(h, 6)
    job = mock.job()
    job.task_groups[0].count = 6
    job.task_groups[0].update = UpdateStrategy(max_parallel=2)
    ev = register_job(h, job)
    h.process("service", ev)
    for a in h.store.allocs_by_job("default", job.id):
        a.client_status = ALLOC_CLIENT_RUNNING
        h.store.upsert_allocs(h.next_index(), [a])

    job2 = mock.job(id=job.id)
    job2.task_groups[0].count = 6
    job2.task_groups[0].update = UpdateStrategy(max_parallel=2)
    job2.task_groups[0].tasks[0].config = {"command": "/bin/sleep"}
    job2.version = 1
    ev2 = register_job(h, job2)
    h.process("service", ev2)
    plan = h.plans[-1]
    n_new = sum(len(v) for v in plan.node_allocation.values())
    n_stop = sum(len(v) for v in plan.node_update.values())
    assert n_new == 2
    assert n_stop == 2
    assert plan.deployment is not None


def test_failed_alloc_rescheduled_with_tracker():
    h = Harness()
    setup_cluster(h, 3)
    job = mock.job()
    job.task_groups[0].count = 2
    job.task_groups[0].reschedule_policy = structs.ReschedulePolicy(
        attempts=3, interval_s=3600, delay_s=0, unlimited=False,
        delay_function="constant")
    ev = register_job(h, job)
    h.process("service", ev)
    allocs = h.store.allocs_by_job("default", job.id)
    now = time.time()
    victim = allocs[0]
    victim.client_status = ALLOC_CLIENT_FAILED
    victim.task_states = {"web": TaskState(state="dead", failed=True,
                                           finished_at=now)}
    h.store.upsert_allocs(h.next_index(), allocs)

    ev2 = mock.eval_(job_id=job.id,
                     triggered_by=structs.EVAL_TRIGGER_RETRY_FAILED_ALLOC)
    h.process("service", ev2)
    replacements = [a for a in h.store.allocs_by_job("default", job.id)
                    if a.previous_allocation == victim.id]
    assert len(replacements) == 1
    rep = replacements[0]
    assert rep.name == victim.name
    assert rep.reschedule_tracker is not None
    assert rep.reschedule_tracker.events[0].prev_alloc_id == victim.id
    # penalty should steer the replacement off the failed node when
    # alternatives exist
    assert rep.node_id != victim.node_id
    # old alloc marked stopped
    stored_victim = h.store.alloc_by_id(victim.id)
    assert stored_victim.server_terminal_status()


def test_sticky_disk_prefers_previous_node():
    h = Harness()
    nodes = setup_cluster(h, 5)
    job = mock.job()
    job.task_groups[0].count = 1
    job.task_groups[0].ephemeral_disk.sticky = True
    ev = register_job(h, job)
    h.process("service", ev)
    orig = h.store.allocs_by_job("default", job.id)[0]
    orig.client_status = ALLOC_CLIENT_RUNNING
    h.store.upsert_allocs(h.next_index(), [orig])

    # destructive update: replacement should return to the same node
    job2 = mock.job(id=job.id)
    job2.task_groups[0].count = 1
    job2.task_groups[0].ephemeral_disk.sticky = True
    job2.task_groups[0].tasks[0].config = {"command": "/bin/other"}
    job2.version = 1
    ev2 = register_job(h, job2)
    h.process("service", ev2)
    live = [a for a in h.store.allocs_by_job("default", job.id)
            if not a.server_terminal_status()]
    assert len(live) == 1
    assert live[0].node_id == orig.node_id


def test_plan_rejection_exhausts_retries():
    h = Harness()
    setup_cluster(h, 2)
    h.reject_plan = True
    job = mock.job()
    job.task_groups[0].count = 1
    ev = register_job(h, job)
    h.process("service", ev)
    assert h.evals[-1].status == structs.EVAL_STATUS_FAILED
    # rolled into a blocked eval for later retry
    assert any(e.triggered_by == structs.EVAL_TRIGGER_MAX_PLANS
               for e in h.create_evals)


def test_batch_job_runs_once():
    h = Harness()
    setup_cluster(h, 2)
    job = mock.batch_job()
    job.task_groups[0].count = 2
    ev = register_job(h, job)
    ev.type = "batch"
    h.process("batch", ev)
    allocs = h.store.allocs_by_job("default", job.id)
    assert len(allocs) == 2
    # complete successfully -> re-eval places nothing new
    now = time.time()
    for a in allocs:
        a.client_status = structs.ALLOC_CLIENT_COMPLETE
        a.task_states = {"web": TaskState(state="dead", failed=False,
                                          finished_at=now)}
    h.store.upsert_allocs(h.next_index(), allocs)
    ev2 = mock.eval_(job_id=job.id, type="batch",
                     triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER)
    h.process("batch", ev2)
    assert len(h.store.allocs_by_job("default", job.id)) == 2


def test_spread_across_datacenters():
    h = Harness()
    for i in range(4):
        n = mock.node(datacenter="dc1" if i < 2 else "dc2")
        h.store.upsert_node(h.next_index(), n)
    job = mock.job()
    job.datacenters = ["dc1", "dc2"]
    job.task_groups[0].count = 4
    job.spreads = [structs.Spread(attribute="${node.datacenter}", weight=100)]
    ev = register_job(h, job)
    h.process("service", ev)
    allocs = h.store.allocs_by_job("default", job.id)
    assert len(allocs) == 4
    nodes_by_id = {n.id: n for n in h.store.nodes()}
    dcs = [nodes_by_id[a.node_id].datacenter for a in allocs]
    assert dcs.count("dc1") == 2 and dcs.count("dc2") == 2


def test_wave_budget_leftovers_retry_instead_of_blocking():
    """Placements the solve's wave budget left undecided are not
    capacity failures: the eval submits what was decided and goes round
    again.  Recording them as failures parked the eval behind a blocked
    eval that only a capacity change wakes — at deployment size, with
    capacity plentiful, a third of a fused batch hung there for good
    (ISSUE 21)."""
    from nomad_tpu.solver.solve import Placement, Solver

    class BudgetStarved(Solver):
        calls = 0

        def solve(self, *a, **kw):
            out = super().solve(*a, **kw)
            type(self).calls += 1
            if self.calls == 1:         # first round: 3 left undecided
                for i in (-1, -2, -3):
                    p = out.placements[i]
                    out.placements[i] = Placement(
                        ask_index=p.ask_index, node=None, score=0.0,
                        metrics=p.metrics, retryable=True,
                        failed_reason="solve wave budget exhausted "
                                      "(retryable)")
            return out

    h = Harness()
    h.solver = BudgetStarved(host="always")
    setup_cluster(h)
    job = mock.job()           # count=10
    ev = register_job(h, job)
    h.process("service", ev)

    assert BudgetStarved.calls == 2 and len(h.plans) == 2
    assert len(h.store.allocs_by_job("default", job.id)) == 10
    assert h.create_evals == []              # nothing blocked
    assert h.evals[-1].status == EVAL_STATUS_COMPLETE
    assert not h.evals[-1].failed_tg_allocs
