"""State store behavior tests (reference: nomad/state/state_store_test.go
behaviors relevant to scheduling)."""
import threading
import time

import pytest

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


# ------------------------------------------------ snapshot isolation of
# the alloc indexes.  A snapshot shares the index's id sets with the
# store (ISSUE 31); these hold the store to the contract that made the
# old per-key copy unnecessary: no write after `snapshot()` shows
# through it.
_INDEXES = ("_allocs_by_node", "_allocs_by_job")


class _World:
    """A small store every isolation case starts from: `job` with three
    allocs on `node` and one on `other`; `new_job` and `new_node` are
    known to the store and hold no alloc yet."""

    def __init__(self):
        self.store = StateStore()
        self._ix = 0
        self.node, self.other, self.new_node = (mock.node() for _ in range(3))
        for n in (self.node, self.other, self.new_node):
            self.store.upsert_node(self.ix(), n)
        self.job, self.new_job = mock.job(), mock.job()
        for j in (self.job, self.new_job):
            self.store.upsert_job(self.ix(), j)
        self.store.upsert_allocs(self.ix(), [
            mock.alloc(job=self.job, node_id=n.id)
            for n in (self.node, self.node, self.node, self.other)])

    def ix(self) -> int:
        self._ix += 1
        return self._ix

    def live(self):
        """A live alloc of `job` on `node` (the lowest id: any will do,
        the same on every run)."""
        return min(self.store.allocs_by_node_terminal(self.node.id, False),
                   key=lambda a: a.id)

    def view(self, reader) -> dict:
        """Everything the three index readers say, through `reader` (the
        live store or a snapshot of it), and the id sets themselves:
        the readers drop an id whose alloc the reader's alloc table
        lacks, which would hide an id added to a shared set."""
        out = {(name, k): set(ids) for name in _INDEXES
               for k, ids in reader._t[name].items()}
        for n in (self.node, self.other, self.new_node):
            out["node", n.id] = {a.id for a in reader.allocs_by_node(n.id)}
            for terminal in (False, True):
                out["node", n.id, terminal] = {
                    a.id for a in
                    reader.allocs_by_node_terminal(n.id, terminal)}
        for j in (self.job, self.new_job):
            out["job", j.id] = {
                a.id for a in reader.allocs_by_job(j.namespace, j.id)}
        return out


def _write_upsert_known(w, step):
    w.store.upsert_allocs(w.ix(), [mock.alloc(job=w.job, node_id=w.node.id)])


def _write_upsert_new(w, step):
    w.store.upsert_allocs(
        w.ix(), [mock.alloc(job=w.new_job, node_id=w.new_node.id)])


def _write_plan_results(w, step):
    import copy
    stopped = copy.copy(w.live())
    stopped.desired_status = structs.ALLOC_DESIRED_STOP
    stopped.job = None
    placed = mock.alloc(job=w.job, node_id=w.node.id)
    w.store.upsert_plan_results(w.ix(), structs.PlanResult(
        node_update={w.node.id: [stopped]},
        node_allocation={w.node.id: [placed]}), job=w.job)


def _write_client_update(w, step):
    import copy
    upd = copy.copy(w.live())
    upd.client_status = structs.ALLOC_CLIENT_FAILED
    w.store.update_allocs_from_client(w.ix(), [upd])


def _write_reap(w, step):
    a = w.live()
    w.store.delete_eval(w.ix(), [a.eval_id], [a.id])


def _write_restore(w, step):
    """Step 0 installs, through the FSM, the state of a replica that is
    one alloc ahead; step 1 writes to the restored tables."""
    from nomad_tpu.raft.fsm import StateFSM
    if step == 0:
        ahead = StateStore()
        StateFSM(ahead).restore(StateFSM(w.store).snapshot())
        ahead.upsert_allocs(w.ix(), [mock.alloc(job=w.job,
                                                node_id=w.node.id)])
        StateFSM(w.store).restore(StateFSM(ahead).snapshot())
    else:
        _write_upsert_known(w, step)


_INDEX_WRITERS = {
    "upsert_allocs_known_keys": _write_upsert_known,
    "upsert_allocs_new_keys": _write_upsert_new,
    "upsert_plan_results": _write_plan_results,
    "update_allocs_from_client": _write_client_update,
    "reap": _write_reap,
    "fsm_restore": _write_restore,
}


@pytest.mark.parametrize("writer", sorted(_INDEX_WRITERS))
def test_snapshot_isolation_of_the_alloc_indexes(writer):
    write = _INDEX_WRITERS[writer]
    w = _World()
    snap0 = w.store.snapshot()
    v0 = w.view(snap0)
    assert v0 == w.view(w.store)
    write(w, 0)
    v1 = w.view(w.store)
    assert v1 != v0, "the store reads the new state"
    assert w.view(snap0) == v0, "a write reached an older snapshot"
    # a snapshot between two writes to the same keys sees the first
    # and not the second
    snap1 = w.store.snapshot()
    write(w, 1)
    v2 = w.view(w.store)
    assert v2 != v1
    assert w.view(snap1) == v1
    assert w.view(snap0) == v0


@pytest.mark.parametrize("seed", [7, 2147483777, 31])
def test_snapshots_equal_a_model_that_copies_both_indexes(seed):
    """A random interleaving of upserts, re-upserts, removals and
    snapshots against a model that deep-copies both indexes at every
    snapshot, which is what `snapshot()` did before it shared them:
    at the end every snapshot still equals its model."""
    import copy
    import random
    rng = random.Random(seed)
    s = StateStore()
    nodes = [f"node-{i}" for i in range(6)]
    jobs = [mock.job() for _ in range(4)]
    for i, j in enumerate(jobs):
        s.upsert_job(i + 1, j)
    by_node = {n: set() for n in nodes}
    by_job = {j.id: set() for j in jobs}
    live, taken, index = {}, [], len(jobs)
    for _ in range(400):
        index += 1
        op = rng.random()
        if op < 0.45 or not live:
            batch = [mock.alloc(job=rng.choice(jobs),
                                node_id=rng.choice(nodes))
                     for _ in range(rng.randint(1, 5))]
            s.upsert_allocs(index, batch)
            for a in batch:
                live[a.id] = a
                by_node[a.node_id].add(a.id)
                by_job[a.job_id].add(a.id)
        elif op < 0.55:
            a = copy.copy(live[rng.choice(sorted(live))])
            a.desired_status = structs.ALLOC_DESIRED_STOP
            s.upsert_allocs(index, [a])     # the indexes stay as they are
        elif op < 0.80:
            doomed = rng.sample(sorted(live), min(len(live),
                                                  rng.randint(1, 3)))
            s.delete_eval(index, [], doomed)
            for aid in doomed:
                a = live.pop(aid)
                by_node[a.node_id].discard(aid)
                by_job[a.job_id].discard(aid)
        else:
            taken.append((s.snapshot(), copy.deepcopy(by_node),
                          copy.deepcopy(by_job)))
    assert len(taken) > 40
    taken.append((s, by_node, by_job))          # and the store itself
    for reader, model_nodes, model_jobs in taken:
        for n in nodes:
            assert {a.id for a in reader.allocs_by_node(n)} \
                == reader._t["_allocs_by_node"].get(n, set()) \
                == model_nodes[n]
        for j in jobs:
            assert {a.id for a in reader.allocs_by_job(j.namespace, j.id)} \
                == reader._t["_allocs_by_job"].get((j.namespace, j.id),
                                                   set()) \
                == model_jobs[j.id]


# ---------------------------------------------- what a snapshot and a
# write cost, as counts (never times)
def _counters():
    from nomad_tpu.utils.metrics import global_metrics
    c = global_metrics.dump()["counters"]
    return (c.get("state.index.keys_copied", 0),
            c.get("state.index.ids_copied", 0))


@pytest.fixture(scope="module")
def big_store():
    """2,000 nodes, 10,000 allocs of 100 jobs, five a node."""
    s = StateStore()
    nodes = [f"node-{i:04d}" for i in range(2000)]
    jobs = [mock.job() for _ in range(100)]
    for i, j in enumerate(jobs):
        s.upsert_job(i + 1, j)
    s.upsert_allocs(200, [mock.alloc(job=jobs[k % 100],
                                     node_id=nodes[k % 2000])
                          for k in range(10_000)])
    return s, nodes, jobs


def test_a_snapshot_builds_no_container_per_index_key(big_store):
    import gc
    s, _nodes, _jobs = big_store

    def collections():
        return [g["collections"] for g in gc.get_stats()]

    # a thread another test left behind can only add collections, so
    # the snapshots' own count is the least over a few attempts
    provoked = []
    for _ in range(3):
        gc.collect()
        before = collections()
        snaps = [s.snapshot() for _ in range(20)]
        provoked.append([a - b for a, b in zip(collections(), before)])
        if provoked[-1] == [0, 0, 0]:
            break
    assert provoked[-1] == [0, 0, 0], provoked
    for snap in snaps:
        for name in _INDEXES:
            assert len(snap._t[name]) == len(s._t[name]) > 0
            assert all(ids is s._t[name][k]
                       for k, ids in snap._t[name].items())


def test_a_plan_copies_the_keys_it_touches_and_no_other(big_store):
    s, nodes, jobs = big_store
    job = mock.job()
    s.upsert_job(s.latest_index() + 1, job)
    snap = s.snapshot()
    keys0, ids0 = _counters()
    landed = nodes[100:130]
    result = structs.PlanResult(node_allocation={
        n: [mock.alloc(job=job, node_id=n) for _ in range(2 + (i < 4))]
        for i, n in enumerate(landed)})
    assert sum(len(v) for v in result.node_allocation.values()) == 64
    s.upsert_plan_results(s.latest_index() + 1, result, job=job)
    changed = {(name, k) for name in _INDEXES
               for k, ids in snap._t[name].items()
               if s._t[name][k] is not ids}
    assert changed == {("_allocs_by_node", n) for n in landed}
    assert (job.namespace, job.id) not in snap._t["_allocs_by_job"]
    keys1, ids1 = _counters()
    assert keys1 - keys0 == len(landed)
    assert ids1 - ids0 == sum(
        len(snap._t["_allocs_by_node"][n]) for n in landed) == 5 * 30
    # until the next snapshot the store owns those keys: no more copies
    s.upsert_allocs(s.latest_index() + 1,
                    [mock.alloc(job=job, node_id=n) for n in landed])
    assert _counters() == (keys1, ids1)


def test_a_large_job_in_one_call_copies_each_key_once():
    """5,000 allocs under one job key and one node key, twice: the
    second call finds 5,000 ids under each key shared with a snapshot
    and copies them once, not once an alloc."""
    s = StateStore()
    job = mock.job()
    s.upsert_job(1, job)
    for index in (2, 3):
        s.snapshot()
        keys0, ids0 = _counters()
        s.upsert_allocs(index, [mock.alloc(job=job, node_id="node-0")
                                for _ in range(5000)])
        keys1, ids1 = _counters()
    assert keys1 - keys0 == 2
    assert ids1 - ids0 == 2 * 5000
    assert len(s.allocs_by_job(job.namespace, job.id)) == 10_000


def test_snapshot_readers_survive_concurrent_index_writes():
    """Snapshot readers walk id sets the store shares with them, with
    no lock, while plans land on and allocs are reaped from the same
    keys: a set written in place would raise "Set changed size during
    iteration" or change what a snapshot reads.  More threads than
    cores, a shortened switch interval, time-bounded."""
    import os
    import sys

    s = StateStore()
    job = mock.job()
    s.upsert_job(1, job)
    s.upsert_allocs(2, [mock.alloc(job=job, node_id="node-0")
                        for _ in range(50)])
    stop = threading.Event()
    errors = []

    def writer():
        ix, mine = 2, []
        while not stop.is_set():
            ix += 1
            a = mock.alloc(job=job, node_id="node-0")
            s.upsert_allocs(ix, [a])
            mine.append(a.id)
            if len(mine) > 20:
                s.delete_eval(ix, [], [mine.pop(0)])

    def reader():
        try:
            while not stop.is_set():
                snap = s.snapshot()
                first = {a.id for a in snap.allocs_by_node("node-0")}
                for _ in range(20):
                    again = {a.id for a in snap.allocs_by_job(
                        job.namespace, job.id)}
                    assert again == first \
                        == snap._t["_allocs_by_node"]["node-0"]
        except Exception as e:      # surfaced by the assert below
            errors.append(repr(e))
            stop.set()

    threads = [threading.Thread(target=writer, daemon=True)] + [
        threading.Thread(target=reader, daemon=True)
        for _ in range((os.cpu_count() or 4) + 2)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        time.sleep(1.0)
    finally:
        stop.set()
        sys.setswitchinterval(old)
    for t in threads:
        t.join(timeout=30.0)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
