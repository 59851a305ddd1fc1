"""A plan's raft entry carries the plan's job ONCE (ISSUE 33): the
proposer writes a placement whose `job` is the plan's job without it,
the FSM decodes the entry's one job and the store hands that object to
every placement that came without.  Pinned here: the store such an
entry leaves is value for value what the old form (a job on every
placement) leaves, old entries still apply and replay, followers and
snapshot installs agree with the leader, and the proposer walks one
Job a plan (reference: structs.go Plan.AppendAlloc strips the job,
state_store.go UpsertPlanResults puts it back)."""
import copy
import json

import pytest

from nomad_tpu import mock, structs
from nomad_tpu.client.sim import wait_until
from nomad_tpu.raft import InProcTransport, RaftConfig, RaftNode, StateFSM
from nomad_tpu.server.server import Server, _plan_entry
from nomad_tpu.state.store import StateStore
from nomad_tpu.structs import (Affinity, AllocatedDeviceResource,
                               AllocatedResources, AllocatedSharedResources,
                               AllocatedTaskResources, Allocation,
                               Constraint, Plan, PlanResult, RequestedDevice,
                               Spread, SpreadTarget)
from nomad_tpu.utils.codec import to_wire
from nomad_tpu.utils.metrics import global_metrics


# ------------------------------------------------------------ fixtures
def _c3_job(n_groups=4):
    """c3's shape: 4 groups under two constraints, an affinity and a
    spread — the job whose tree is dear to walk."""
    job = mock.job()
    job.constraints = [
        Constraint("${attr.kernel.name}", "linux", "="),
        Constraint("${attr.cpu.arch}", "amd64", "=")]
    job.affinities = [Affinity("${node.class}", "large", "=", 35.0)]
    job.spreads = [Spread("${node.datacenter}", 50.0,
                          [SpreadTarget(f"dc{i}", 25) for i in range(4)])]
    base = job.task_groups[0]
    job.task_groups = []
    for g in range(n_groups):
        tg = copy.deepcopy(base)
        tg.name = f"g{g}"
        tg.count = 16
        tg.tasks[0].resources.cpu = 400 + 150 * g
        tg.tasks[0].resources.networks = []
        job.task_groups.append(tg)
    return job


def _c4_job():
    """c4's shape: one group whose task asks for a device instance."""
    job = mock.job()
    tg = job.task_groups[0]
    tg.name = "g0"
    tg.count = 16
    tg.tasks[0].resources.networks = []
    tg.tasks[0].resources.devices = [RequestedDevice("google/tpu/v4", 1)]
    return job


_JOBS = {"c3": _c3_job, "c4": _c4_job}


def _placements(job, nodes, per_group):
    """`per_group` new placements of every group of `job`, each holding
    the SAME job object, as `scheduler/generic.py` builds them."""
    out = []
    for tg in job.task_groups:
        task = tg.tasks[0]
        for i in range(per_group):
            node = nodes[len(out) % len(nodes)]
            devices = [AllocatedDeviceResource(
                "google", "tpu", "v4", [f"{node.id[:8]}-tpu-{i}"])
                for _ in task.resources.devices]
            out.append(Allocation(
                id=f"{job.id[-12:]}-{tg.name}-{i:03d}",
                namespace=job.namespace, eval_id=f"eval-{job.id[-12:]}",
                name=f"{job.id}.{tg.name}[{i}]", node_id=node.id,
                node_name=node.name, job_id=job.id, job=job,
                task_group=tg.name,
                allocated_resources=AllocatedResources(
                    tasks={task.name: AllocatedTaskResources(
                        cpu=task.resources.cpu,
                        memory_mb=task.resources.memory_mb,
                        devices=devices)},
                    shared=AllocatedSharedResources(disk_mb=300)),
                desired_status=structs.ALLOC_DESIRED_RUN,
                client_status=structs.ALLOC_CLIENT_PENDING,
                create_time=1.5, modify_time=1.5))
    return out


def _plan_of(job, allocs):
    plan = Plan(eval_id=f"eval-{job.id[-12:]}", job=job)
    for a in allocs:
        plan.append_alloc(a)
    result = PlanResult(node_allocation={
        k: list(v) for k, v in plan.node_allocation.items()})
    return plan, result


def _old_entry(plan, result):
    """The entry as every proposer wrote it before ISSUE 33: the job
    under each placement and once more beside the result."""
    return {"result": to_wire(result),
            "job": to_wire(plan.job) if plan.job is not None else None}


def _base_entries(nodes, jobs):
    return ([("node_upsert", {"node": to_wire(n)}) for n in nodes]
            + [("job_upsert", {"job": to_wire(j)}) for j in jobs])


def _replay(entries):
    """A fresh store with `entries` applied through `StateFSM.apply`,
    each through JSON as the durable log and the wire carry it."""
    fsm = StateFSM(StateStore())
    for index, (etype, payload) in enumerate(entries, start=1):
        fsm.apply(index, etype, json.loads(json.dumps(payload)))
    return fsm


def _tables(fsm):
    """Every replicated table and index as plain data: allocs with
    their jobs, jobs, summaries, table indexes."""
    return json.loads(fsm.snapshot())


def _jobs_walked(fn):
    before = global_metrics.dump()["counters"].get("plan.jobs_encoded", 0)
    out = fn()
    after = global_metrics.dump()["counters"].get("plan.jobs_encoded", 0)
    return out, after - before


# ------------------------------------------------- codec and the FSM
@pytest.mark.parametrize("shape", sorted(_JOBS))
def test_new_entry_leaves_the_store_the_old_form_leaves(shape):
    nodes = [mock.node() for _ in range(8)]
    job = _JOBS[shape]()
    plan, result = _plan_of(job, _placements(job, nodes, 16))
    base = _base_entries(nodes, [job])

    new = _plan_entry(plan, result)
    placed = [a for row in new["result"]["node_allocation"].values()
              for a in row]
    assert len(placed) == 16 * len(job.task_groups)
    assert all(a["job"] is None for a in placed)
    assert new["job"] == to_wire(job)

    got = _replay(base + [("plan_result", new)])
    want = _replay(base + [("plan_result", _old_entry(plan, result))])
    assert _tables(got) == _tables(want)

    stored = got.store.allocs_by_job(job.namespace, job.id)
    assert len(stored) == len(placed)
    assert all(a.job is not None for a in stored)
    assert len({id(a.job) for a in stored}) == 1, \
        "the placements of one plan share the entry's one decoded Job"
    assert to_wire(stored[0].job) == to_wire(job)
    assert all(a.create_index == len(base) + 1 for a in stored)


def test_batch_entry_gives_each_placement_its_own_items_job():
    nodes = [mock.node() for _ in range(4)]
    jobs = [_c3_job(), _c4_job(), _c3_job(n_groups=2)]
    items = [_plan_of(j, _placements(j, nodes, 3)) for j in jobs]
    base = _base_entries(nodes, jobs)

    new = {"items": [_plan_entry(p, r) for p, r in items]}
    old = {"items": [_old_entry(p, r) for p, r in items]}
    got = _replay(base + [("plan_results_batch", new)])
    want = _replay(base + [("plan_results_batch", old)])
    assert _tables(got) == _tables(want)

    for job in jobs:
        stored = got.store.allocs_by_job(job.namespace, job.id)
        assert len(stored) == 3 * len(job.task_groups)
        assert len({id(a.job) for a in stored}) == 1
        assert all(a.job.id == job.id for a in stored), \
            "never a neighbouring item's job"
        assert to_wire(stored[0].job) == to_wire(job)


def test_plan_mixing_placements_stops_and_a_preemption():
    nodes = [mock.node() for _ in range(4)]
    old_job, victim_job, job = _c3_job(n_groups=1), _c4_job(), _c3_job()
    first = [_plan_of(j, _placements(j, nodes, 4))
             for j in (old_job, victim_job)]
    base = _base_entries(nodes, [old_job, victim_job, job]) + [
        ("plan_result", _old_entry(p, r)) for p, r in first]

    running = _replay(base).store
    stops = running.allocs_by_job(old_job.namespace, old_job.id)[:2]
    victim = running.allocs_by_job(victim_job.namespace, victim_job.id)[0]
    plan, _ = _plan_of(job, _placements(job, nodes, 2))
    for a in stops:
        plan.append_stopped_alloc(a, "alloc not needed")
    plan.append_preempted_alloc(
        victim, next(iter(plan.node_allocation.values()))[0].id)
    result = PlanResult(
        node_update={k: list(v) for k, v in plan.node_update.items()},
        node_allocation={k: list(v)
                         for k, v in plan.node_allocation.items()},
        node_preemptions={k: list(v)
                          for k, v in plan.node_preemptions.items()})

    got = _replay(base + [("plan_result", _plan_entry(plan, result))])
    want = _replay(base + [("plan_result", _old_entry(plan, result))])
    assert _tables(got) == _tables(want)

    st = got.store
    assert all(st.alloc_by_id(a.id).desired_status
               == structs.ALLOC_DESIRED_STOP for a in stops)
    assert all(st.alloc_by_id(a.id).job.id == old_job.id for a in stops), \
        "a stopped alloc keeps the job the store already had for it"
    evicted = st.alloc_by_id(victim.id)
    assert evicted.desired_status == structs.ALLOC_DESIRED_EVICT
    assert evicted.job.id == victim_job.id
    placed = st.allocs_by_job(job.namespace, job.id)
    assert len(placed) == 8 and len({id(a.job) for a in placed}) == 1
    assert not [a.id for a in st.allocs() if a.job is None]


# ------------------------------------------------------ compatibility
def test_old_form_entry_still_applies():
    """A log from before ISSUE 33 holds a job under every placement:
    each alloc keeps the one it came with."""
    nodes = [mock.node() for _ in range(2)]
    job = _c4_job()
    plan, result = _plan_of(job, _placements(job, nodes, 4))
    entry = _old_entry(plan, result)
    assert all(a["job"] == to_wire(job)
               for row in entry["result"]["node_allocation"].values()
               for a in row)
    fsm = _replay(_base_entries(nodes, [job]) + [("plan_result", entry)])
    stored = fsm.store.allocs_by_job(job.namespace, job.id)
    assert len(stored) == 4
    assert all(to_wire(a.job) == to_wire(job) for a in stored)


def test_alloc_with_another_job_object_keeps_it_on_the_wire_and_in_store():
    nodes = [mock.node() for _ in range(2)]
    job = _c4_job()
    allocs = _placements(job, nodes, 4)
    other = copy.deepcopy(job)
    other.meta = {"owner": "someone-else"}
    allocs[1].job = other
    plan, result = _plan_of(job, allocs)

    entry, walked = _jobs_walked(lambda: _plan_entry(plan, result))
    assert walked == 2, "the plan's job and the one alloc's own"
    wire = {a["id"]: a for row in
            entry["result"]["node_allocation"].values() for a in row}
    assert wire[allocs[1].id]["job"] == to_wire(other)
    assert all(a["job"] is None
               for aid, a in wire.items() if aid != allocs[1].id)

    st = _replay(_base_entries(nodes, [job])
                 + [("plan_result", entry)]).store
    assert st.alloc_by_id(allocs[1].id).job.meta == {"owner": "someone-else"}
    assert st.alloc_by_id(allocs[0].id).job.meta == job.meta


@pytest.mark.parametrize("shape", sorted(_JOBS))
def test_entry_is_byte_for_byte_what_the_reflective_codec_wrote(
        shape, monkeypatch):
    """ISSUE 35 compiled the codec; the entry on the wire is the one
    commit c8d58ca wrote for the same plan (the golden string is built
    by that commit's `to_wire`, kept in `test_codec.py` as the plain
    reference), and a log that holds old-form and new entries written
    by either codec replays, through either decoder, to equal stores."""
    from nomad_tpu.raft import fsm as fsm_module
    from nomad_tpu.server import server as server_module
    from test_codec import ref_from_wire, ref_to_wire

    def dumps(payload):
        return json.dumps(payload, separators=(",", ":"))

    nodes = [mock.node() for _ in range(8)]
    first, second = _c3_job(n_groups=1), _JOBS[shape]()
    plans = [_plan_of(j, _placements(j, nodes, 16)) for j in (first, second)]

    def log():
        # to_wire is read off the module at each call, so the patch
        # below writes the same log through the reference
        return (_base_entries(nodes, [first, second])
                + [("plan_result", _old_entry(*plans[0])),
                   ("plan_result", _plan_entry(*plans[1])),
                   ("plan_results_batch",
                    {"items": [_plan_entry(*plans[0])]})])

    compiled = log()
    with monkeypatch.context() as m:
        m.setattr(server_module, "to_wire", ref_to_wire)
        m.setitem(globals(), "to_wire", ref_to_wire)
        golden = log()
    assert [dumps(p) for _, p in compiled] == [dumps(p) for _, p in golden]
    assert len(dumps(compiled[-2][1])) > 10_000

    by_compiled = _tables(_replay(compiled))
    monkeypatch.setattr(fsm_module, "from_wire", ref_from_wire)
    by_reference = _tables(_replay(golden))
    assert by_compiled == by_reference
    assert len(by_compiled["tables"]["allocs"]) \
        == 16 + 16 * len(second.task_groups)


def _single_voter(data_dir):
    fsm = StateFSM(StateStore())
    node = RaftNode(RaftConfig(node_id="s1", peers=[], data_dir=data_dir,
                               fsync=False), fsm, InProcTransport())
    node.start()
    assert wait_until(node.is_leader, timeout=10)
    return node, fsm


def test_durable_logs_of_both_forms_replay_to_equal_stores(tmp_path):
    nodes = [mock.node() for _ in range(4)]
    jobs = [_c3_job(), _c4_job()]
    plans = [_plan_of(j, _placements(j, nodes, 4)) for j in jobs]
    restored = {}
    for form, encode in (("old", _old_entry), ("new", _plan_entry)):
        d = str(tmp_path / form)
        node, _ = _single_voter(d)
        for etype, payload in _base_entries(nodes, jobs):
            node.propose(etype, payload)
        node.propose("plan_result", encode(*plans[0]))
        node.propose("plan_results_batch",
                     {"items": [encode(*plans[1])]})
        node.stop()
        # a restart: nothing but the directory survives
        node2, fsm2 = _single_voter(d)
        try:
            assert len(list(fsm2.store.allocs())) == 16 + 4
            restored[form] = _tables(fsm2)
        finally:
            node2.stop()
    assert restored["new"] == restored["old"]
    assert all(a[1]["job"] is not None
               for a in restored["new"]["tables"]["allocs"])


def test_encoder_leaves_the_plan_and_result_in_memory_as_they_were():
    """The scheduler, the applier's overlay and the worker's refresh
    still hold these allocs and read `a.job` off them."""
    s = Server(num_workers=1)
    s.start()
    try:
        nodes = [mock.node() for _ in range(4)]
        for n in nodes:
            s.register_node(n)
        job = _c3_job()
        plan, result = _plan_of(job, _placements(job, nodes, 16))
        before = to_wire(result)
        index, finish = s._apply_plan_async(plan, result)
        assert finish(10.0) == index
        assert plan.job is job
        for m in (plan.node_allocation, result.node_allocation):
            assert all(a.job is job for row in m.values() for a in row)
        assert to_wire(result) == before
        stored = s.store.allocs_by_job(job.namespace, job.id)
        assert len(stored) == 64
        assert all(a.job is not job and a.job is not None for a in stored)
        assert all(a.create_index == index for a in stored)
    finally:
        s.stop()


# -------------------------------------------------------- replication
def _alloc_rows(store):
    return sorted((to_wire(a) for a in store.allocs()),
                  key=lambda a: a["id"])


def test_followers_and_a_snapshot_install_hold_the_leaders_allocs():
    transport = InProcTransport()
    peers = ["s0", "s1", "s2"]
    fsms = [StateFSM(StateStore()) for _ in peers]
    rafts = [RaftNode(RaftConfig(node_id=p, peers=peers,
                                 election_timeout_s=(0.10, 0.25),
                                 heartbeat_interval_s=0.03,
                                 snapshot_threshold=32), f, transport)
             for p, f in zip(peers, fsms)]
    for r in rafts[:2]:
        r.start()
    try:
        assert wait_until(lambda: any(r.is_leader() for r in rafts[:2]),
                          timeout=10)
        lead = next(i for i in (0, 1) if rafts[i].is_leader())
        nodes = [mock.node() for _ in range(4)]
        jobs = [_c3_job(), _c4_job(), _c4_job()]
        for etype, payload in _base_entries(nodes, jobs):
            rafts[lead].propose(etype, payload)
        rafts[lead].propose("plan_result", _plan_entry(
            *_plan_of(jobs[0], _placements(jobs[0], nodes, 4))))
        rafts[lead].propose("plan_results_batch", {"items": [
            _plan_entry(*_plan_of(j, _placements(j, nodes, 4)))
            for j in jobs[1:]]})
        want = _alloc_rows(fsms[lead].store)
        assert len(want) == 16 + 4 + 4
        assert all(a["job"] is not None for a in want)
        # the follower that was up applied the same entries
        assert wait_until(
            lambda: len(list(fsms[1 - lead].store.allocs())) == len(want),
            timeout=10)
        assert _alloc_rows(fsms[1 - lead].store) == want
        # the dark one gets a snapshot taken after the plans
        for _ in range(40):
            rafts[lead].propose("node_upsert",
                                {"node": to_wire(mock.node())})
        assert rafts[lead].log.offset > len(nodes) + len(jobs) + 2, \
            "the log must have compacted past the plan entries"
        rafts[2].start()
        assert wait_until(
            lambda: len(list(fsms[2].store.nodes())) == len(nodes) + 40,
            timeout=10)
        assert _alloc_rows(fsms[2].store) == want
    finally:
        for r in rafts:
            try:
                r.stop()
            except Exception:
                pass


# -------------------------------------------------------- the counter
def test_jobs_encoded_counts_one_job_a_plan_and_one_an_item():
    nodes = [mock.node() for _ in range(8)]
    job = _c3_job()
    plan, result = _plan_of(job, _placements(job, nodes, 16))
    assert sum(len(v) for v in result.node_allocation.values()) == 64
    _, walked = _jobs_walked(lambda: _plan_entry(plan, result))
    assert walked == 1

    s = Server(num_workers=1)
    s.start()
    try:
        for n in nodes:
            s.register_node(n)
        items = [_plan_of(j, _placements(j, nodes, 2))
                 for j in (_c3_job(), _c4_job(), _c4_job())]
        (index, finish), walked = _jobs_walked(
            lambda: s._apply_plan_batch_async(items))
        assert walked == 3
        assert finish(10.0) == index
        (index, finish), walked = _jobs_walked(
            lambda: s._apply_plan_async(plan, result))
        assert walked == 1 and finish(10.0) == index
        _, walked = _jobs_walked(lambda: s._apply_plan(
            *_plan_of(job, _placements(_c4_job(), nodes, 2))))
        assert walked == 1 + 2, "placements of another job keep theirs"
    finally:
        s.stop()


def test_plan_without_a_job_walks_none():
    bare = mock.alloc()
    bare.job = None
    result = PlanResult(node_allocation={bare.node_id: [bare]})
    entry, walked = _jobs_walked(
        lambda: _plan_entry(Plan(eval_id="e"), result))
    assert walked == 0 and entry["job"] is None
    assert entry["result"] == to_wire(result)
