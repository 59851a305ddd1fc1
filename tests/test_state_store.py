"""State store behavior tests (reference: nomad/state/state_store_test.go
behaviors relevant to scheduling)."""
import threading
import time

from nomad_tpu import mock, structs
from nomad_tpu.state.store import SchedulerConfiguration, StateStore


def test_node_crud_and_ready_filter():
    s = StateStore()
    n1, n2 = mock.node(), mock.node(datacenter="dc2")
    s.upsert_node(10, n1)
    s.upsert_node(11, n2)
    assert s.node_by_id(n1.id).create_index == 10
    ready, by_dc = s.ready_nodes_in_dcs(["dc1"])
    assert [n.id for n in ready] == [n1.id]
    assert by_dc == {"dc1": 1}
    s.update_node_status(12, n1.id, structs.NODE_STATUS_DOWN)
    ready, _ = s.ready_nodes_in_dcs(["dc1"])
    assert ready == []
    assert s.latest_index() == 12


def test_upsert_preserves_create_index():
    s = StateStore()
    n = mock.node()
    s.upsert_node(5, n)
    import copy
    n2 = copy.copy(n)
    s.upsert_node(9, n2)
    assert s.node_by_id(n.id).create_index == 5
    assert s.node_by_id(n.id).modify_index == 9


def test_job_versioning():
    s = StateStore()
    j = mock.job()
    s.upsert_job(10, j)
    assert s.job_by_id(j.namespace, j.id).version == 0
    import copy
    j2 = copy.deepcopy(j)
    j2.task_groups[0].count = 20
    s.upsert_job(20, j2)
    got = s.job_by_id(j.namespace, j.id)
    assert got.version == 1 and got.task_groups[0].count == 20
    versions = s.job_versions(j.namespace, j.id)
    assert [v.version for v in versions] == [1, 0]
    assert s.job_by_id_and_version(j.namespace, j.id, 0).task_groups[0].count == 10


def test_job_version_not_bumped_without_spec_change():
    s = StateStore()
    j = mock.job()
    s.upsert_job(10, j)
    import copy
    j2 = copy.deepcopy(j)  # identical spec
    s.upsert_job(20, j2)
    assert s.job_by_id(j.namespace, j.id).version == 0


def test_alloc_indexes():
    s = StateStore()
    j = mock.job()
    s.upsert_job(1, j)
    a1 = mock.alloc(job=j)
    a2 = mock.alloc(job=j)
    a2.node_id = a1.node_id
    s.upsert_allocs(2, [a1, a2])
    assert {a.id for a in s.allocs_by_node(a1.node_id)} == {a1.id, a2.id}
    assert {a.id for a in s.allocs_by_job(j.namespace, j.id)} == {a1.id, a2.id}
    assert len(s.allocs_by_node_terminal(a1.node_id, False)) == 2
    # job goes running with a live alloc
    ev = mock.eval_(job_id=j.id, status=structs.EVAL_STATUS_COMPLETE)
    s.upsert_evals(3, [ev])
    assert s.job_by_id(j.namespace, j.id).status == structs.JOB_STATUS_RUNNING


def test_client_update_merge():
    s = StateStore()
    a = mock.alloc()
    s.upsert_allocs(2, [a])
    import copy
    upd = copy.copy(a)
    upd.client_status = structs.ALLOC_CLIENT_RUNNING
    upd.task_states = {"web": structs.TaskState(state="running")}
    s.update_allocs_from_client(3, [upd])
    got = s.alloc_by_id(a.id)
    assert got.client_status == structs.ALLOC_CLIENT_RUNNING
    assert got.task_states["web"].state == "running"
    assert got.modify_index == 3


def test_snapshot_isolation():
    s = StateStore()
    n = mock.node()
    s.upsert_node(1, n)
    snap = s.snapshot()
    assert snap.index == 1
    n2 = mock.node()
    s.upsert_node(2, n2)
    s.update_node_status(3, n.id, structs.NODE_STATUS_DOWN)
    # snapshot still sees the old world
    assert snap.node_by_id(n2.id) is None
    assert snap.node_by_id(n.id).status == structs.NODE_STATUS_READY
    assert s.node_by_id(n.id).status == structs.NODE_STATUS_DOWN


def test_plan_result_apply():
    s = StateStore()
    j = mock.job()
    s.upsert_job(1, j)
    old = mock.alloc(job=j)
    s.upsert_allocs(2, [old])
    new = mock.alloc(job=j)
    stop = structs.Plan().append_stopped_alloc  # not used; build manually
    import copy
    stopped = copy.copy(old)
    stopped.desired_status = structs.ALLOC_DESIRED_STOP
    stopped.job = None
    result = structs.PlanResult(
        node_update={old.node_id: [stopped]},
        node_allocation={new.node_id: [new]})
    s.upsert_plan_results(5, result, job=j)
    assert s.alloc_by_id(old.id).desired_status == structs.ALLOC_DESIRED_STOP
    assert s.alloc_by_id(old.id).job is j  # denormalized job restored
    assert s.alloc_by_id(new.id).create_index == 5


def test_blocking_query_wakes_on_write():
    s = StateStore()
    s.upsert_node(1, mock.node())
    results = []

    def waiter():
        results.append(s.wait_for_change(1, timeout=5.0))

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    s.upsert_node(2, mock.node())
    t.join(timeout=2)
    assert results == [2]


def test_scheduler_config():
    s = StateStore()
    assert s.scheduler_config().solver_backend == "tpu"
    s.set_scheduler_config(4, SchedulerConfiguration(solver_backend="host"))
    assert s.scheduler_config().solver_backend == "host"


def test_deployment_lifecycle():
    s = StateStore()
    j = mock.job()
    d = structs.Deployment(job_id=j.id)
    s.upsert_deployment(3, d)
    assert s.latest_deployment_by_job("default", j.id).id == d.id
    du = structs.DeploymentStatusUpdate(
        deployment_id=d.id, status=structs.DEPLOYMENT_STATUS_SUCCESSFUL,
        status_description="done")
    result = structs.PlanResult(deployment_updates=[du])
    s.upsert_plan_results(4, result)
    assert (s.deployment_by_id(d.id).status
            == structs.DEPLOYMENT_STATUS_SUCCESSFUL)


def test_live_readers_survive_concurrent_writes():
    """A reader polling the LIVE store while plans apply (an HTTP
    handler, chip_smoke.py) must never die with "dictionary changed
    size during iteration": every reader that walks a table copies it
    under the store lock (ISSUE 21).  More threads than cores, a
    shortened switch interval, time-bounded."""
    import sys

    s = StateStore()
    job = mock.job()
    s.upsert_job(1, job)
    n = mock.node()
    s.upsert_node(2, n)
    stop = threading.Event()
    errors = []

    def writer(base):
        ix = base
        while not stop.is_set():
            ix += 2
            ev = mock.eval_(job_id=job.id)
            s.upsert_evals(ix, [ev])
            a = mock.alloc(job=job, node_id=n.id, eval_id=ev.id)
            s.upsert_allocs(ix + 1, [a])
            if ix % 7 == 0:
                s.delete_eval(ix + 1, [ev.id], [a.id])

    def reader():
        try:
            while not stop.is_set():
                s.evals_by_job(job.namespace, job.id)
                list(s.evals())
                list(s.allocs())
                s.allocs_by_node(n.id)
                s.allocs_by_job(job.namespace, job.id)
                s.allocs_by_eval("nope")
                list(s.jobs())
                list(s.nodes())
                s.ready_nodes_in_dcs(["dc1"])
        except Exception as e:       # relayed to the asserting thread
            errors.append(e)
            stop.set()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = ([threading.Thread(target=writer, args=(10 + 1_000_000 * i,))
                    for i in range(4)]
                   + [threading.Thread(target=reader) for _ in range(12)])
        for t in threads:
            t.start()
        time.sleep(1.5)
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert errors == []
    assert len(s.evals_by_job(job.namespace, job.id)) > 0
