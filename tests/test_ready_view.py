"""The store's ready-node view: the ready nodes of a datacenter set, their
count per datacenter and their id map, walked once per state of the
nodes table and shared by every snapshot of that state.  Each answer is
held against the walk the scheduler made on every eval before the view,
kept here as the oracle."""
import copy
import dataclasses
import threading
from types import MappingProxyType

import pytest

from nomad_tpu import mock, structs
from nomad_tpu.raft.fsm import StateFSM
from nomad_tpu.scheduler.harness import Harness
from nomad_tpu.state.store import StateSnapshot, StateStore
from nomad_tpu.utils.metrics import global_metrics

DC_SETS = (["dc1"], ["dc2"], ["dc1", "dc2"], ["dc2", "dc1"], ["*"],
           ["dc9"], [], ["dc3", "dc9"])


def oracle_walk(snapshot, datacenters):
    """readyNodesInDCs as every eval walked it before the view."""
    dcs = set(datacenters)
    out, by_dc = [], {}
    for n in snapshot.nodes():
        if not n.ready():
            continue
        if n.datacenter not in dcs and "*" not in dcs:
            continue
        out.append(n)
        by_dc[n.datacenter] = by_dc.get(n.datacenter, 0) + 1
    return out, by_dc, {n.id: n for n in out}


def built():
    return global_metrics.dump()["counters"].get(
        "state.ready_view.built", 0.0)


def assert_matches_walk(snapshot, datacenters):
    nodes, by_dc, by_id = snapshot.ready_node_view(datacenters)
    want_nodes, want_dc, want_id = oracle_walk(snapshot, datacenters)
    assert isinstance(nodes, tuple)
    assert isinstance(by_dc, MappingProxyType)
    assert isinstance(by_id, MappingProxyType)
    assert len(nodes) == len(want_nodes)
    assert all(a is b for a, b in zip(nodes, want_nodes))
    assert dict(by_dc) == want_dc
    assert list(by_id) == list(want_id)
    assert all(by_id[k] is v for k, v in want_id.items())
    ready, counts = snapshot.ready_nodes_in_dcs(datacenters)
    assert ready is nodes and counts is by_dc
    return nodes, by_dc, by_id


def cluster(store, index=100):
    """Nine nodes over three DCs, one of each kind that is not ready."""
    nodes = [mock.node(datacenter=f"dc{1 + i % 3}") for i in range(9)]
    nodes[4].status = structs.NODE_STATUS_DOWN
    nodes[5].scheduling_eligibility = structs.NODE_SCHED_INELIGIBLE
    nodes[7].drain = True
    for i, n in enumerate(nodes):
        store.upsert_node(index + i, n)
    return nodes


@pytest.mark.parametrize("datacenters", DC_SETS,
                         ids=lambda d: "+".join(d) or "none")
def test_view_is_the_walk_on_the_store_and_its_snapshots(datacenters):
    store = StateStore()
    cluster(store)
    snap = store.snapshot()
    nodes, by_dc, by_id = assert_matches_walk(snap, datacenters)
    # the live store answers from the same view, and its
    # ready_nodes_in_dcs still hands back copies of its own
    assert store.ready_node_view(datacenters) == (nodes, by_dc, by_id)
    ready, counts = store.ready_nodes_in_dcs(datacenters)
    assert type(ready) is list and type(counts) is dict
    assert ready == list(nodes) and counts == dict(by_dc)
    # a second snapshot of the same table shares the walk
    assert store.snapshot().ready_node_view(datacenters)[0] is nodes


def _register(store, nodes, ix):
    store.upsert_node(ix, mock.node(datacenter="dc2"))


def _down(store, nodes, ix):
    store.update_node_status(ix, nodes[0].id, structs.NODE_STATUS_DOWN)


def _down_then_ready(store, nodes, ix):
    store.update_node_status(ix, nodes[1].id, structs.NODE_STATUS_DOWN)
    store.update_node_status(ix + 1, nodes[1].id, structs.NODE_STATUS_READY)


def _back_to_ready(store, nodes, ix):
    store.update_node_status(ix, nodes[4].id, structs.NODE_STATUS_READY)


def _drain_on(store, nodes, ix):
    store.update_node_drain(ix, nodes[2].id, structs.DrainStrategy())


def _drain_off(store, nodes, ix):
    store.update_node_drain(ix, nodes[2].id, structs.DrainStrategy())
    store.update_node_drain(ix + 1, nodes[2].id, None, mark_eligible=True)


def _eligibility_off(store, nodes, ix):
    store.update_node_eligibility(ix, nodes[3].id,
                                  structs.NODE_SCHED_INELIGIBLE)


def _eligibility_back(store, nodes, ix):
    store.update_node_eligibility(ix, nodes[5].id,
                                  structs.NODE_SCHED_ELIGIBLE)


def _delete(store, nodes, ix):
    store.delete_node(ix, nodes[6].id)


def _upsert_unchanged(store, nodes, ix):
    store.upsert_node(ix, copy.copy(store.node_by_id(nodes[0].id)))


def _restore(store, nodes, ix):
    other = StateStore()
    cluster(other, index=ix)
    StateFSM(store).restore(StateFSM(other).snapshot())


WRITES = [_register, _down, _down_then_ready, _back_to_ready, _drain_on,
          _drain_off, _eligibility_off, _eligibility_back, _delete,
          _upsert_unchanged, _restore]


@pytest.mark.parametrize("write", WRITES, ids=lambda f: f.__name__[1:])
def test_a_node_write_gives_later_snapshots_a_new_view(write):
    store = StateStore()
    nodes = cluster(store)
    before = store.snapshot()
    old = {tuple(d): assert_matches_walk(before, d) for d in DC_SETS}
    write(store, nodes, 500)
    after = store.snapshot()
    for d in DC_SETS:
        new = assert_matches_walk(after, d)
        # nothing is carried over: a later snapshot walks afresh
        assert new[0] is not old[tuple(d)][0] or new[0] == ()
        # the older snapshot keeps the view it was given, which is
        # still the walk of the table it holds
        assert all(x is y for x, y in
                   zip(before.ready_node_view(d), old[tuple(d)]))
        assert_matches_walk(before, d)
    # the write is seen where it changes readiness
    assert [n.id for n in after.ready_node_view(["*"])[0]] == \
        [n.id for n in oracle_walk(after, ["*"])[0]]


def test_an_older_snapshot_never_sees_a_later_write():
    store = StateStore()
    nodes = cluster(store)
    before = store.snapshot()
    ids_before = [n.id for n in before.ready_node_view(["dc1"])[0]]
    assert nodes[0].id in ids_before
    store.update_node_status(600, nodes[0].id, structs.NODE_STATUS_DOWN)
    # first use after the write: the old snapshot still walks its own
    # table, the new one the written one
    assert [n.id for n in before.ready_node_view(["dc1"])[0]] == ids_before
    after = store.snapshot()
    assert nodes[0].id not in after.ready_node_view(["dc1"])[2]


def test_built_counts_one_walk_per_node_write_and_dc_set():
    store = StateStore()
    nodes = cluster(store)
    b0 = built()
    for _ in range(5):
        snap = store.snapshot()
        snap.ready_node_view(["dc1"])
        snap.ready_nodes_in_dcs(["dc1"])
        snap.ready_node_view(["*"])
        store.ready_nodes_in_dcs(["dc1", "dc2"])
    assert built() - b0 == 3
    store.update_node_status(700, nodes[0].id, structs.NODE_STATUS_DOWN)
    for _ in range(3):
        store.snapshot().ready_node_view(["dc1"])
    assert built() - b0 == 4
    # writes to other tables keep the view
    job = mock.job()
    store.upsert_job(701, job)
    store.upsert_allocs(702, [mock.alloc(job=job, node_id=nodes[1].id)])
    store.snapshot().ready_node_view(["dc1"])
    assert built() - b0 == 4


def test_two_readers_that_miss_at_once_both_get_the_walk():
    store = StateStore()
    for i in range(400):
        store.upsert_node(10 + i, mock.node(datacenter=f"dc{1 + i % 2}"))
    snap = store.snapshot()
    want = oracle_walk(snap, ["dc1"])
    start = threading.Barrier(8)
    got, errors = [], []

    def reader():
        try:
            start.wait()
            got.append(snap.ready_node_view(["dc1"]))
        except Exception as e:        # relayed to the asserting thread
            errors.append(e)

    threads = [threading.Thread(target=reader) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors and len(got) == 8
    for nodes, by_dc, by_id in got:
        assert [n.id for n in nodes] == [n.id for n in want[0]]
        assert dict(by_dc) == want[1] and dict(by_id) == want[2]
    # after the race settles every reader is handed the one stored
    assert all(snap.ready_node_view(["dc1"]) is snap.ready_node_view(["dc1"])
               for _ in range(2))


def _register_job(h, job):
    h.store.upsert_job(h.next_index(), job)
    ev = mock.eval_(job_id=job.id, type=job.type,
                    triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER)
    h.store.upsert_evals(h.next_index(), [ev])
    return ev


def test_containers_come_back_unchanged_after_an_eval():
    h = Harness()
    cluster(h.store)
    job = mock.job()
    job.datacenters = ["dc1", "dc2"]
    snap = h.store.snapshot()
    nodes, by_dc, by_id = snap.ready_node_view(job.datacenters)
    kept = (list(nodes), dict(by_dc), dict(by_id))
    b0 = built()
    h.process("service", _register_job(h, job))
    assert h.store.allocs_by_job(job.namespace, job.id)
    assert (list(nodes), dict(by_dc), dict(by_id)) == kept
    # the eval took the view of the unchanged nodes table: no walk
    assert built() == b0
    assert snap.ready_node_view(job.datacenters) == (nodes, by_dc, by_id)


def _same_cluster_two_stores():
    """Two harnesses over one node set: equal nodes with equal ids and
    indexes.  Ties are what the nodes' order decides, so most nodes are
    alike."""
    nodes = []
    for i in range(24):
        n = mock.node(datacenter=f"dc{1 + i % 3}")
        if i % 5 == 0:
            n.node_resources.cpu = 8000
        nodes.append(n)
    nodes[4].status = structs.NODE_STATUS_DOWN
    nodes[9].scheduling_eligibility = structs.NODE_SCHED_INELIGIBLE
    nodes[13].drain = True
    out = []
    for _ in range(2):
        h = Harness()
        for n in nodes:
            h.store.upsert_node(h.next_index(), copy.deepcopy(n))
        out.append(h)
    return out


def _plan_rows(plan):
    rows = {}
    for nid, allocs in plan.node_allocation.items():
        rows[nid] = sorted(
            (a.name, dataclasses.replace(a.metrics, allocation_time_ns=0))
            for a in allocs)
    return rows


def test_placements_are_those_of_the_walk(monkeypatch):
    with_view, with_walk = _same_cluster_two_stores()
    jobs = []
    for dcs in (["dc1", "dc2"], ["*"], ["dc3"]):
        job = mock.job()
        job.datacenters = dcs
        job.task_groups[0].count = 7
        jobs.append(job)
    for h in (with_view, with_walk):
        if h is with_walk:
            monkeypatch.setattr(StateSnapshot, "ready_node_view",
                                lambda self, dcs: oracle_walk(self, dcs))
        for job in jobs:
            h.process("service", _register_job(h, copy.deepcopy(job)))
    assert len(with_view.plans) == len(with_walk.plans) == len(jobs)
    for a, b in zip(with_view.plans, with_walk.plans):
        rows = _plan_rows(a)
        assert sum(len(r) for r in rows.values()) == 7
        assert rows == _plan_rows(b)
        for r in rows.values():
            for _name, metric in r:
                assert metric.nodes_available and metric.score_meta
