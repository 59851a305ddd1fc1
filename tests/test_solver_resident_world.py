"""The Solver's resident cluster world (ISSUE 2 tentpole, worker side)
must be placement-identical to the per-eval full pack while never
re-walking the world: state advances by plan-apply feeds plus the store
change log, across alloc placements, client-side terminal updates, node
drains, joins, and interning-table invalidations."""
import numpy as np
import pytest

from nomad_tpu import mock, structs
from nomad_tpu.scheduler.harness import Harness
from nomad_tpu.solver.kernel import TOP_K
from nomad_tpu.solver.solve import LazyAllocsView, Solver
from nomad_tpu.solver.tensorize import PlacementAsk
from nomad_tpu.state.store import StateStore
from nomad_tpu.utils.metrics import global_metrics


def _mk_node(i, store, index):
    n = mock.node()
    n.attributes["rack"] = f"r{i % 4}"
    n.node_resources.cpu = 8000
    n.node_resources.memory_mb = 16384
    store.upsert_node(index, n)
    return n


def _asks(job):
    return [PlacementAsk(job=job, tg=tg, count=tg.count)
            for tg in job.task_groups]


def _eager_allocs(snapshot, nodes):
    out = {}
    for n in nodes:
        live = [a for a in snapshot.allocs_by_node(n.id)
                if not a.terminal_status()]
        if live:
            out[n.id] = live
    return out


def _placements(out):
    return [(p.ask_index,
             p.node.id if p.node is not None else None,
             round(p.score, 9))
            for p in out.placements]


def _solve_both(resident, store, job):
    """Same snapshot through the resident path and a FRESH full-pack
    solver; returns (resident placements, full placements)."""
    snapshot = store.snapshot()
    nodes, by_dc = snapshot.ready_nodes_in_dcs(job.datacenters)
    abn = _eager_allocs(snapshot, nodes)
    asks = _asks(job)
    full = Solver().solve(nodes, asks, abn, by_dc)
    res = resident.solve(nodes, asks, abn, by_dc, snapshot=snapshot,
                         proposed_delta=((), ()))
    return _placements(res), _placements(full)


def test_resident_world_tracks_store_changes():
    store = StateStore()
    ix = [100]

    def nix():
        ix[0] += 1
        return ix[0]

    nodes = [_mk_node(i, store, nix()) for i in range(10)]
    resident = Solver(store=store, resident_min_nodes=1)

    job = mock.job()
    job.task_groups[0].count = 4
    job.task_groups[0].tasks[0].resources.networks = []
    # reference ${attr.rack} so the rack column is in the interned
    # universe (round 6 relies on an unseen rack VALUE invalidating it)
    job.constraints = list(job.constraints) + [
        structs.Constraint("${attr.rack}", "r-none", "!=")]
    store.upsert_job(nix(), job)

    # round 1: fresh cluster
    got, want = _solve_both(resident, store, job)
    assert got == want
    assert resident.resident_counters() is not None

    # round 2: allocs placed through the store (another worker's plan)
    allocs = []
    for k in range(6):
        a = mock.alloc()
        a.node_id = nodes[k % 5].id
        a.job_id, a.namespace = job.id, job.namespace
        tr = a.allocated_resources.tasks["web"]
        tr.cpu, tr.memory_mb, tr.networks = 1500, 1024, []
        allocs.append(a)
    store.upsert_allocs(nix(), allocs)
    got, want = _solve_both(resident, store, job)
    assert got == want
    assert resident.resident_counters()["delta_syncs"] >= 1
    assert resident.resident_counters()["repack_fallbacks"] == 0

    # round 3: a client frees capacity (terminal update) — a write the
    # plan feed never sees, only the change log
    import copy
    upd = copy.copy(allocs[0])
    upd.client_status = structs.ALLOC_CLIENT_FAILED
    store.update_allocs_from_client(nix(), [upd])
    got, want = _solve_both(resident, store, job)
    assert got == want

    # round 4: drain a node (valid-mask flip, no re-pack)
    store.update_node_eligibility(nix(), nodes[1].id,
                                  structs.NODE_SCHED_INELIGIBLE)
    got, want = _solve_both(resident, store, job)
    assert got == want
    assert resident.resident_counters()["repack_fallbacks"] == 0

    # round 5: a node joins inside the interned universe
    _mk_node(2, store, nix())
    got, want = _solve_both(resident, store, job)
    assert got == want

    # round 6: a join with an unseen attr value invalidates the rank
    # tables -> full rebuild, still identical
    weird = mock.node()
    weird.attributes["rack"] = "r-unseen"
    store.upsert_node(nix(), weird)
    got, want = _solve_both(resident, store, job)
    assert got == want
    assert resident.resident_counters()["repack_fallbacks"] >= 1


def test_resident_world_plan_feed_and_changelog_dedup():
    store = StateStore()
    ix = [100]

    def nix():
        ix[0] += 1
        return ix[0]

    for i in range(8):
        _mk_node(i, store, nix())
    resident = Solver(store=store, resident_min_nodes=1)
    job = mock.job()
    job.task_groups[0].count = 2
    job.task_groups[0].tasks[0].resources.networks = []
    store.upsert_job(nix(), job)
    got, want = _solve_both(resident, store, job)
    assert got == want
    world = resident._world
    used_before = world.template.used0.copy()

    # plan applied: fed eagerly AND written to the store; the follow-up
    # change-log sync must not double-charge
    a = mock.alloc()
    a.job_id, a.namespace = job.id, job.namespace
    a.node_id = next(iter(world.node_index))
    tr = a.allocated_resources.tasks["web"]
    tr.cpu, tr.memory_mb, tr.networks = 1000, 512, []
    from nomad_tpu.structs import PlanResult
    store.upsert_allocs(nix(), [a])
    resident.note_plan_result(None, PlanResult(
        node_allocation={a.node_id: [a]}))
    world.sync(store.snapshot())
    slot = world.node_index[a.node_id]
    delta_cpu = (world.template.used0 - used_before)[slot, 0]
    assert delta_cpu == pytest.approx(1000.0)   # charged exactly once


def test_lazy_allocs_view_matches_eager():
    store = StateStore()
    nodes = [_mk_node(i, store, 100 + i) for i in range(4)]
    job = mock.job()
    allocs = []
    for k in range(5):
        a = mock.alloc()
        a.node_id = nodes[k % 3].id
        a.job_id = job.id
        allocs.append(a)
    store.upsert_allocs(200, allocs)
    snap = store.snapshot()
    excluded = {allocs[0].id}
    view = LazyAllocsView(snap, excluded)
    eager = {}
    for n in nodes:
        live = [a for a in snap.allocs_by_node(n.id)
                if not a.terminal_status() and a.id not in excluded]
        if live:
            eager[n.id] = live
    # point reads before materialization
    assert view.get(nodes[0].id) == eager.get(nodes[0].id)
    assert (nodes[3].id in view) == (nodes[3].id in eager)
    # mutation sticks
    view.setdefault(nodes[3].id, []).append(allocs[0])
    # full iteration materializes the rest without disturbing mutations
    # (per-node order may differ — usage math is order-insensitive)
    assert {k: {a.id for a in v} for k, v in view.items()} == {
        k: {a.id for a in v} for k, v in list(eager.items())
        + [(nodes[3].id, [allocs[0]])]}


def _view_counters():
    c = global_metrics.dump()["counters"]
    return (c.get("solver.allocs_view.nodes", 0.0),
            c.get("solver.allocs_view.materialized", 0.0))


def test_lazy_allocs_view_truth_test_does_not_materialize():
    store = StateStore()
    nodes = [_mk_node(i, store, 100 + i) for i in range(4)]
    assert not LazyAllocsView(store.snapshot())     # empty cluster
    allocs = []
    for k in range(6):
        a = mock.alloc()
        a.node_id = nodes[k % 3].id
        allocs.append(a)
    store.upsert_allocs(200, allocs)
    view = LazyAllocsView(store.snapshot())
    nodes0, walks0 = _view_counters()
    assert bool(view)
    assert view, "a view over a snapshot that holds allocs is true"
    assert _view_counters() == (nodes0, walks0)
    assert not view._all and not dict.__len__(view)
    # a node filled (and mutated) before a materialize keeps its list
    # (the store's index is a set of ids: a node's allocs in no order)
    first = view.get(nodes[0].id)
    assert {a.id for a in first} == {allocs[0].id, allocs[3].id}
    kept = {allocs[0].id, allocs[3].id} - {first.pop().id}
    assert _view_counters() == (nodes0 + 1, walks0)
    assert view.get(nodes[0].id) is first            # read once
    assert _view_counters() == (nodes0 + 1, walks0)
    # len(), items() and iteration need the whole world: one walk
    assert len(view) == 3
    assert _view_counters() == (nodes0 + 3, walks0 + 1)
    assert view[nodes[0].id] is first
    assert {k: {a.id for a in v} for k, v in view.items()} == {
        nodes[0].id: kept,
        nodes[1].id: {allocs[1].id, allocs[4].id},
        nodes[2].id: {allocs[2].id, allocs[5].id}}
    assert sorted(view) == sorted(n.id for n in nodes[:3])
    assert _view_counters() == (nodes0 + 3, walks0 + 1)  # walked once
    for walks in (len, iter, dict, lambda v: v.items(),
                  lambda v: v.keys(), lambda v: v.values()):
        fresh = LazyAllocsView(store.snapshot())
        _, w = _view_counters()
        walks(fresh)
        assert _view_counters()[1] == w + 1, walks
    # every alloc terminal: reads true without a walk, every get empty
    for a in allocs:
        a.desired_status = structs.ALLOC_DESIRED_STOP
        a.client_status = structs.ALLOC_CLIENT_COMPLETE
    stopped = LazyAllocsView(store.snapshot())
    assert stopped and stopped.get(nodes[0].id, ()) == ()
    assert len(stopped) == 0 and not stopped


def _fixup_cluster(kind):
    """8 nodes (2 GPUs each for `device`); nodes 0 and 1 hold a
    resident alloc that the bin-pack score prefers and whose discrete
    holdings the kernel cannot see: reserved port 8080 and one GPU."""
    store = StateStore()
    nodes = []
    for i in range(8):
        n = mock.gpu_node(n_gpus=2) if kind == "device" else mock.node()
        n.node_resources.cpu, n.node_resources.memory_mb = 8000, 16384
        store.upsert_node(100 + i, n)
        nodes.append(n)
    held = []
    for n in nodes[:2]:
        a = mock.alloc()
        a.node_id = n.id
        tr = a.allocated_resources.tasks["web"]
        tr.cpu, tr.memory_mb = 2000, 2048
        tr.networks = [structs.NetworkResource(
            device="eth0", ip=n.node_resources.networks[0].ip, mbits=50,
            reserved_ports=[structs.Port(label="admin", value=8080)])]
        if kind == "device":
            dev = n.node_resources.devices[0]
            tr.devices = [structs.AllocatedDeviceResource(
                vendor=dev.vendor, type=dev.type, name=dev.name,
                device_ids=[dev.instances[0].id])]
        held.append(a)
    store.upsert_allocs(200, held)
    job = mock.job()
    tg = job.task_groups[0]
    tg.count = 3
    res = tg.tasks[0].resources
    res.networks = []
    if kind == "ports":
        res.networks = [structs.NetworkResource(
            mbits=10, reserved_ports=[structs.Port("admin", 8080)],
            dynamic_ports=[structs.Port(label="http")])]
    elif kind == "device":
        res.devices = [structs.RequestedDevice(name="nvidia/gpu", count=1)]
    store.upsert_job(201, job)
    return store, nodes, held, job


def _offers(out):
    """(node, ports, device ids) of every placement, in ask order."""
    rows = []
    for p in out.placements:
        tr = p.resources.tasks["web"] if p.node is not None else None
        rows.append((
            p.node.id if p.node is not None else None, round(p.score, 9),
            [(pt.label, pt.value) for net in (tr.networks if tr else ())
             for pt in net.reserved_ports + net.dynamic_ports],
            [i for d in (tr.devices if tr else ()) for i in d.device_ids]))
    return rows


@pytest.mark.parametrize("kind", ["plain", "ports", "device"])
def test_lazy_view_fixup_reads_touched_nodes_only(kind):
    """The served path's solve (resident world, LazyAllocsView) never
    walks the cluster, and its offers equal the eager dict's."""
    store, nodes, held, job = _fixup_cluster(kind)
    solver = Solver(store=store, resident_min_nodes=1)
    snapshot = store.snapshot()
    ready, by_dc = snapshot.ready_nodes_in_dcs(job.datacenters)
    asks = _asks(job)

    def solve(allocs_by_node):
        assert solver.resident_active(snapshot)
        return solver.solve(ready, asks, allocs_by_node, by_dc,
                            snapshot=snapshot, proposed_delta=((), ()))

    nodes0, walks0 = _view_counters()
    lazy = solve(LazyAllocsView(snapshot))
    nodes1, walks1 = _view_counters()
    assert walks1 == walks0
    n_place = len(lazy.placements)
    assert n_place == 3 and all(p.node is not None
                                for p in lazy.placements)
    assert 0 < nodes1 - nodes0 <= n_place * TOP_K
    eager = solve(_eager_allocs(snapshot, ready))
    assert _view_counters() == (nodes1, walks1)    # a plain dict counts nothing
    assert _offers(lazy) == _offers(eager)
    # what the resident allocs hold is never offered again
    holders = {a.node_id: a for a in held}
    for node_id, _score, ports, device_ids in _offers(lazy):
        if kind == "ports":
            assert node_id not in holders
            assert ("admin", 8080) in ports
        if kind == "device" and node_id in holders:
            taken = holders[node_id].allocated_resources.tasks[
                "web"].devices[0].device_ids
            assert device_ids and set(device_ids).isdisjoint(taken)


def test_lazy_view_refuses_held_port_and_device_instance():
    """One node, its reserved port and one of its two GPUs held by a
    resident alloc: through the lazy view a second ask for the port is
    refused, and the GPU offered is the free one, then none."""
    store, nodes, held, job = _fixup_cluster("device")
    node, snapshot = nodes[0], store.snapshot()
    tg = job.task_groups[0]
    ask = PlacementAsk(job=job, tg=tg, count=1)
    taken = held[0].allocated_resources.tasks["web"].devices[0].device_ids
    net_cache, dev_cache = {}, {}
    view = LazyAllocsView(snapshot)
    got = Solver._host_commit(node, 0, ask, net_cache, dev_cache, view)
    ids = got.tasks["web"].devices[0].device_ids
    assert len(ids) == 1 and ids[0] not in taken
    # both instances are spoken for now (one resident, one in-batch)
    assert Solver._host_commit(node, 0, ask, net_cache, dev_cache,
                               view) is None
    tg.tasks[0].resources.devices = []
    tg.tasks[0].resources.networks = [structs.NetworkResource(
        mbits=10, reserved_ports=[structs.Port("admin", 8080)])]
    assert Solver._host_commit(node, 0, ask, {}, {}, view) is None
    assert Solver._host_commit(nodes[5], 5, ask, {}, {}, view) is not None
    # the eager dict and no dict at all, for contrast
    eager = _eager_allocs(snapshot, nodes)
    assert Solver._host_commit(node, 0, ask, {}, {}, eager) is None
    assert Solver._host_commit(node, 0, ask, {}, {}, None) is not None
    assert not view._all


def test_changelog_window_and_truncation():
    store = StateStore()
    n = _mk_node(0, store, 101)
    store.update_node_eligibility(105, n.id,
                                  structs.NODE_SCHED_INELIGIBLE)
    assert store.changes_since(100, 105) == [
        (101, "node", n.id), (105, "node", n.id)]
    assert store.changes_since(101, 104) == []
    # truncation: a consumer below the floor must rebuild
    store.changelog.floor = 103
    assert store.changes_since(102, 105) is None
    assert store.changes_since(103, 105) == [(105, "node", n.id)]


def test_harness_end_to_end_with_resident_solver():
    """Same eval stream through the harness twice — default solver vs
    store-attached resident solver — must produce identical plans
    (alloc names and node assignment counts)."""
    h = Harness()
    ns = [_mk_node(i, h.store, h.next_index()) for i in range(10)]

    h2 = Harness(store=h.store)          # SAME store/world
    h2.solver = Solver(store=h2.store, resident_min_nodes=1)

    job = mock.job()
    job.task_groups[0].count = 6
    h.store.upsert_job(h.next_index(), job)
    ev = mock.eval_(job_id=job.id, type=job.type,
                    triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER)
    h.store.upsert_evals(h.next_index(), [ev])
    h2.process("service", ev)
    placed = h.store.allocs_by_job("default", job.id)
    assert len(placed) == 6
    assert h2.solver._world is not None
    # scale up: the second eval must run the delta path, not re-pack
    job2 = mock.job()
    job2.id, job2.name = job.id, job.name
    job2.task_groups[0].count = 9
    h.store.upsert_job(h2.next_index(), job2)
    ev2 = mock.eval_(job_id=job.id, type=job.type,
                     triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER)
    h.store.upsert_evals(h2.next_index(), [ev2])
    h2.process("service", ev2)
    placed = [a for a in h.store.allocs_by_job("default", job.id)
              if not a.terminal_status()]
    assert len(placed) == 9
    counters = h2.solver.resident_counters()
    assert counters["plan_feeds"] >= 1
    assert counters["repack_fallbacks"] == 0
