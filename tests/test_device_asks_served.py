"""Device asks on the served path, at the shape the cell
`c4-devices-10k.closed1` runs them: nodes that hold 8 instances of
`google/tpu/v4` on every second generator row, jobs of one group x 16
tasks asking for instances.

(a), (f) whole runs of the cell through `Server` at rehearsal size (512
nodes, where the resident world with its carried `dev_used` is on),
held to the plain reference (`benchmark/reference.py` with
`benchmark/rules/devices.py`, which import nothing of the program), and
the counters and the sample the solve writes for them; (b) the JAX
program with `has_devices=True` against the numpy twin on one packed
batch of that shape (the rehearsal answers from the twin, the chip from
the program); (c) the carried `dev_used` across two solves; (d) asks of
2 and 4 instances a task; (e) an ask no device matches.

`tests/test_host_solver.py` holds the twin comparison at 30 nodes with
one asking group in three, `tests/test_solver.py` one GPU node,
`tests/test_solver_resident_world.py` the lazy view's offers: none of
them is repeated here.
"""
import json
import os
import sys

import numpy as np
import pytest

from test_host_solver import assert_same

from nomad_tpu import mock, structs
from nomad_tpu.client.sim import wait_until
from nomad_tpu.solver.host import host_solve_kernel
from nomad_tpu.solver.kernel import solve_kernel
from nomad_tpu.solver.solve import Solver, _kernel_args
from nomad_tpu.solver.tensorize import PlacementAsk, Tensorizer
from nomad_tpu.state.store import StateStore
from nomad_tpu.utils.metrics import global_metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "c4-devices-10k.closed1"
DEVICE = ("google", "tpu", "v4")
INSTANCES = 8


# ------------------------------------------------------------- fixtures
@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules, importable by their bare names as
    `benchmark/run.py` imports them; `run.run` turns JAX's persistent
    cache to keep every program, which is put back afterwards."""
    import jax
    keep = {k: getattr(jax.config, k) for k in (
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    sys.path.insert(0, BENCH)
    import check
    import cluster
    import load
    import run
    yield {"run": run, "cluster": cluster, "check": check, "load": load}
    sys.path.remove(BENCH)
    for k, v in keep.items():
        jax.config.update(k, v)


def device_nodes(n):
    """Generator rows 0..n-1 of `bench.make_nodes(devices=True)`: eight
    cpu sizes, four memory sizes, 8 instances on the even rows."""
    nodes = []
    for i in range(n):
        nd = mock.node(datacenter=f"dc{i % 4}")
        nd.name = f"node-{i}"
        nd.reserved_resources.cpu = 0
        nd.reserved_resources.memory_mb = 0
        nd.reserved_resources.disk_mb = 0
        nd.node_resources.cpu = 4000 + (i % 8) * 1000
        nd.node_resources.memory_mb = 8192 + (i % 4) * 4096
        nd.node_resources.disk_mb = 100_000
        if i % 2 == 0:
            nd.node_resources.devices = [structs.NodeDeviceResource(
                vendor=DEVICE[0], type=DEVICE[1], name=DEVICE[2],
                instances=[structs.NodeDevice(id=f"tpu-{i}-{k}",
                                              healthy=True)
                           for k in range(INSTANCES)])]
        nd.compute_class()
        nodes.append(nd)
    return nodes


def device_job(count=16, per_task=1, name="google/tpu/v4"):
    job = mock.job()
    job.datacenters = [f"dc{i}" for i in range(4)]
    job.constraints = []
    tg = job.task_groups[0]
    tg.count = count
    tg.constraints = []
    res = tg.tasks[0].resources
    res.networks = []
    res.cpu, res.memory_mb = 400, 256
    res.devices = [structs.RequestedDevice(name=name, count=per_task)]
    tg.ephemeral_disk.size_mb = 300
    return job


def holding(node, ids, cpu=200):
    """A resident alloc on `node` that holds the instances `ids`."""
    a = mock.alloc()
    a.node_id = node.id
    tr = a.allocated_resources.tasks["web"]
    tr.cpu, tr.memory_mb, tr.networks = cpu, 256, []
    tr.devices = [structs.AllocatedDeviceResource(
        vendor=DEVICE[0], type=DEVICE[1], name=DEVICE[2],
        device_ids=list(ids))] if ids else []
    return a


def instance_ids(resources):
    return [i for tr in resources.tasks.values() for d in tr.devices
            for i in d.device_ids]


def device_metrics():
    d = global_metrics.dump()
    out = {k: v for k, v in d["counters"].items()
           if k.startswith(("solver.device.", "solver.solve."))}
    out["samples"] = d["samples"].get("span.solve.devices",
                                      {"count": 0, "sum": 0.0})
    return out


def moved(before, after, key):
    return after.get(key, 0.0) - before.get(key, 0.0)


# ------------------------------- (a), (f) the cell through Server, 3 seeds
@pytest.mark.parametrize("seed", [5, 2**31 + 17, 1234567])
def test_the_cell_through_server_against_the_reference(
        bench, capsys, monkeypatch, seed):
    run, cluster, check = bench["run"], bench["cluster"], bench["check"]
    # one caller, as the cell has (a rehearsal's default is four)
    for k, v in (("clients", 1), ("warmup_bursts", [1]),
                 ("wait_timeout_s", 10)):
        monkeypatch.setitem(cluster.REHEARSE_TRAFFIC, k, v)
    seen = {}
    real_rows = check.rows_from_snapshot

    def rows_from_snapshot(cfg, snapshot, plain):
        seen["rows"], seen["plain"] = real_rows(cfg, snapshot, plain), plain
        return seen["rows"]
    monkeypatch.setattr(check, "rows_from_snapshot", rows_from_snapshot)
    # `run.run` reads `off_device_solves` from the process's counters as
    # they stand, and an earlier test file of this worker may have left
    # a degraded solve or a watchdog failover in them
    global_metrics.reset()
    m0 = device_metrics()
    assert run.run(run.parse_args([
        "--workload", CELL, "--seed", str(seed), "--seconds", "0.5",
        "--trace", "0", "--rehearse"])) == 0
    m1 = device_metrics()
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    # against the plain reference: every job at its count, the choice
    # the reference's, the scores its float64 ones, the rule's numbers
    c = {k: v["value"] for k, v in line["compared"].items()}
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 3 and c["failed"] == 0
    assert c["jobs_off_count"] == 0 and c["overcommitted_nodes"] == 0
    assert c["constraint_violations"] == 0
    assert c["device_overbooked"] == 0 and c["device_unmet"] == 0
    assert c["choice_gap_p90"] == 0.0
    assert c["score_mismatch_p99"] < 2e-5

    # and read from the store's rows directly: one instance each, of the
    # alloc's own node, an even generator row, nobody's twice
    rows, plain = seen["rows"], seen["plain"]
    mine = [k for k, j in enumerate(rows["job_id"])
            if not j.startswith("resident-")]
    assert len(mine) >= 16 * line["attempted"]
    held = set()
    for k in mine:
        (dev, inst), = rows["device_ids"][k]
        row = int(plain.extra["device_row"][rows["node"][k]])
        assert dev == "/".join(DEVICE) and row % 2 == 0
        assert inst in {f"tpu-{row}-{i}" for i in range(INSTANCES)}
        held.add(inst)
    assert len(held) == len(mine)
    per_job = {}
    for k in mine:
        per_job[rows["job_id"][k]] = per_job.get(rows["job_id"][k], 0) + 1
    # the window's jobs and the warm-up's whole ones, at their 16
    assert list(per_job.values()).count(16) >= line["attempted"] + 1

    # (f) what the solves wrote: an instance id for every alloc the
    # run's jobs hold (16 a job), no chosen node refused, one sample a
    # solve
    solves = sum(moved(m0, m1, k) for k in m1
                 if k.startswith("solver.solve."))
    assert solves >= line["attempted"]
    assert moved(m0, m1, "solver.device.instances") == len(mine)
    assert moved(m0, m1, "solver.device.refused") == 0
    assert m1["samples"]["count"] - m0["samples"]["count"] == solves
    assert m1["samples"]["sum"] > m0["samples"]["sum"]


def test_an_alloc_left_without_its_instance_reads_device_unmet(
        bench, capsys, monkeypatch):
    """The fault `device_unmet` is there for (no control of the rule
    reaches it): from the window on `_assign_devices` answers with a
    device and no instance id, which the applier has nothing to refuse
    in, so the allocs reach the store empty-handed."""
    run, cluster, load = bench["run"], bench["cluster"], bench["load"]
    for k, v in (("clients", 1), ("warmup_bursts", [1]),
                 ("wait_timeout_s", 10)):
        monkeypatch.setitem(cluster.REHEARSE_TRAFFIC, k, v)
    armed = {"on": False}
    real_window = load.LoadGen.window

    def window(self, seconds):
        armed["on"] = True
        return real_window(self, seconds)
    monkeypatch.setattr(load.LoadGen, "window", window)
    real = Solver._assign_devices

    def no_ids(acct, node, req):
        got = real(acct, node, req)
        if got is not None and armed["on"]:
            got.device_ids = []
        return got
    monkeypatch.setattr(Solver, "_assign_devices", staticmethod(no_ids))
    assert run.run(run.parse_args([
        "--workload", CELL, "--seed", "77", "--seconds", "0.5",
        "--trace", "0", "--rehearse"])) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    c = {k: v["value"] for k, v in line["compared"].items()}
    assert line["correct"] is False
    assert c["device_unmet"] == 16 * line["attempted"] > 0
    assert c["device_overbooked"] == 0 and c["jobs_off_count"] == 0


def test_a_job_that_asks_for_no_device_writes_none_of_them():
    nodes = device_nodes(16)
    job = device_job(count=8)
    job.task_groups[0].tasks[0].resources.devices = []
    m0 = device_metrics()
    out = Solver().solve(nodes, [PlacementAsk(
        job=job, tg=job.task_groups[0], count=8)])
    m1 = device_metrics()
    assert all(p.node is not None for p in out.placements)
    assert all(instance_ids(p.resources) == [] for p in out.placements)
    assert {k: v for k, v in m1.items() if k.startswith("solver.device")} \
        == {k: v for k, v in m0.items() if k.startswith("solver.device")}
    assert m1["samples"] == m0["samples"]
    # and one that asks writes all three, in the same process
    job = device_job(count=8)
    out = Solver().solve(nodes, [PlacementAsk(
        job=job, tg=job.task_groups[0], count=8)])
    m2 = device_metrics()
    assert moved(m1, m2, "solver.device.instances") == 8
    assert moved(m1, m2, "solver.device.refused") == 0
    assert m2["samples"]["count"] == m1["samples"]["count"] + 1


# -------------------- (b) the JAX program against the numpy twin, c4's shape
def c4_batch(per_task):
    """64 rows, 5 resident allocs a node as in the cell; on four device
    nodes they hold 5 to 8 of the instances, so the fullest nodes, which
    bin-pack takes first, have 3 to 0 left for a job of 16."""
    nodes = device_nodes(64)
    allocs = {}
    for i, nd in enumerate(nodes):
        allocs[nd.id] = [holding(nd, []) for _ in range(5)]
    for i, taken in ((0, 8), (8, 7), (16, 6), (24, 5)):
        allocs[nodes[i].id][0] = holding(
            nodes[i], [f"tpu-{i}-{k}" for k in range(taken)])
    job = device_job(count=16, per_task=per_task)
    asks = [PlacementAsk(job=job, tg=job.task_groups[0], count=16)]
    return nodes, Tensorizer().pack(nodes, asks, allocs)


@pytest.mark.parametrize("pallas_mode", ["off", "topk"])
@pytest.mark.parametrize("per_task", [1, 2, 4])
def test_has_devices_program_matches_the_numpy_twin(per_task, pallas_mode):
    nodes, pb = c4_batch(per_task)
    assert pb.dev_cap.sum() == 32 * INSTANCES
    assert pb.dev_used0.sum() == 8 + 7 + 6 + 5
    assert pb.dev_ask[0, 0] == per_task
    args = _kernel_args(pb)
    res_dev = solve_kernel(*args, 0, has_spread=False, has_devices=True,
                           pallas_mode=pallas_mode)
    res_host = host_solve_kernel(*args, 0, has_spread=False)
    assert_same(res_dev, res_host)
    # and the answer is a device answer: 16 placed, each on a node with
    # instances, no node asked for more than it has free
    choice = np.asarray(res_dev.choice)[:16, 0]
    assert np.asarray(res_dev.choice_ok)[:16, 0].all()
    free = (pb.dev_cap - pb.dev_used0)[:, 0]
    taken = np.bincount(choice, minlength=len(free)) * per_task
    assert (taken <= free).all() and (pb.dev_cap[choice, 0] > 0).all()
    # the device-fit binds: a node takes at most 8 / per_task of the 16
    assert len(set(choice.tolist())) >= 16 * per_task // INSTANCES


# ------------------------------- (c) the carried dev_used, across two solves
def test_a_nodes_last_instance_goes_once_across_two_solves():
    store = StateStore()
    nodes = device_nodes(8)
    for i, nd in enumerate(nodes):
        store.upsert_node(100 + i, nd)
    # node 0 is the fullest on cpu, so bin-pack takes it first, and one
    # of its 8 instances is free
    full = holding(nodes[0], [f"tpu-0-{k}" for k in range(7)], cpu=2000)
    store.upsert_allocs(200, [full])
    solver = Solver(store=store, resident_min_nodes=1)

    def solve(job):
        snapshot = store.snapshot()
        ready, by_dc = snapshot.ready_nodes_in_dcs(job.datacenters)
        assert solver.resident_active(snapshot)
        from nomad_tpu.solver.solve import LazyAllocsView
        out = solver.solve(ready, [PlacementAsk(
            job=job, tg=job.task_groups[0], count=job.task_groups[0].count)],
            LazyAllocsView(snapshot), by_dc, snapshot=snapshot,
            proposed_delta=((), ()))
        assert all(p.node is not None for p in out.placements)
        return out.placements

    first = device_job(count=2)
    store.upsert_job(201, first)
    placed = solve(first)
    on_zero = [p for p in placed if p.node.id == nodes[0].id]
    assert len(on_zero) == 1
    assert instance_ids(on_zero[0].resources) == ["tpu-0-7"]
    committed = []
    for p in placed:
        a = mock.alloc()
        a.node_id, a.job_id = p.node.id, first.id
        a.allocated_resources = p.resources
        committed.append(a)
    store.upsert_allocs(202, committed)

    rebuilds = solver._world.counters["repack_fallbacks"]
    second = device_job(count=1)
    store.upsert_job(203, second)
    (p,) = solve(second)
    # the world moved by the store's change log, not by a rebuild, and
    # carries node 0 as full
    world = solver._world
    assert world.counters["repack_fallbacks"] == rebuilds
    assert world.counters["delta_syncs"] >= 1
    zero = world.node_index[nodes[0].id]
    assert world.template.dev_used0[zero, 0] == INSTANCES
    assert world.template.dev_cap[zero, 0] == INSTANCES
    assert p.node.id != nodes[0].id
    assert len(instance_ids(p.resources)) == 1


# --------------------------------- (d) asks of 2 and of 4 instances a task
@pytest.mark.parametrize("per_task", [2, 4])
def test_asks_of_several_instances_a_task(per_task):
    nodes = device_nodes(16)
    job = device_job(count=16, per_task=per_task)
    out = Solver().solve(nodes, [PlacementAsk(
        job=job, tg=job.task_groups[0], count=16)])
    by_node, seen = {}, set()
    for p in out.placements:
        assert p.node is not None
        ids = instance_ids(p.resources)
        row = int(p.node.name.split("-")[1])
        assert len(ids) == per_task == len(set(ids))
        assert all(i.startswith(f"tpu-{row}-") for i in ids)
        assert seen.isdisjoint(ids)
        seen.update(ids)
        by_node[row] = by_node.get(row, 0) + per_task
    assert all(row % 2 == 0 and n <= INSTANCES
               for row, n in by_node.items())
    # 8 device nodes hold 64 instances: 16 x 4 takes every one of them
    assert len(seen) == 16 * per_task
    assert len(by_node) >= 16 * per_task // INSTANCES


def test_an_ask_larger_than_any_node_holds_places_nothing():
    nodes = device_nodes(8)
    job = device_job(count=2, per_task=INSTANCES + 1)
    out = Solver().solve(nodes, [PlacementAsk(
        job=job, tg=job.task_groups[0], count=2)])
    assert all(p.node is None for p in out.placements)


# ---------------------------- (e) an ask that no device matches, via Server
def test_an_ask_no_device_matches_blocks_the_eval():
    from nomad_tpu.server.server import Server
    server = Server(num_workers=1)
    server.start()
    try:
        for nd in device_nodes(8):
            server.register_node(nd)
        job = device_job(count=4, name="nvidia/gpu")
        server.register_job(job)
        assert wait_until(
            lambda: server.blocked_evals.stats()["total_blocked"]
            + server.blocked_evals.stats()["total_escaped"] > 0, timeout=20)
        evals = server.store.evals_by_job("default", job.id)
        first = next(e for e in evals if e.triggered_by == "job-register")
        assert wait_until(lambda: server.store.eval_by_id(first.id).status
                          == structs.EVAL_STATUS_COMPLETE, timeout=10)
        first = server.store.eval_by_id(first.id)
        metric = first.failed_tg_allocs[job.task_groups[0].name]
        if not isinstance(metric, dict):           # through raft: wire form
            metric = vars(metric)
        assert metric["nodes_evaluated"] == 8
        # feasible nowhere; the program books a node without the device
        # as exhausted where upstream's DeviceChecker filters it
        assert metric["nodes_filtered"] + metric["nodes_exhausted"] == 8
        assert server.store.allocs_by_job("default", job.id) == []
        # the same job asking for what the nodes hold lands at once
        ok = device_job(count=4)
        server.register_job(ok)
        assert wait_until(lambda: len(server.store.allocs_by_job(
            "default", ok.id)) == 4, timeout=20)
        assert all(len(instance_ids(a.allocated_resources)) == 1
                   for a in server.store.allocs_by_job("default", ok.id))
    finally:
        server.stop()


def test_the_device_part_is_one_recorder_span_under_the_fixup():
    """One eval of a device-asking job through a real server: the solve
    writes `solve.devices` once, a child of `solve.fixup`, inside it."""
    from nomad_tpu.server.server import Server
    from nomad_tpu.utils.tracing import global_tracer
    server = Server(num_workers=1)
    server.start()
    try:
        for nd in device_nodes(8):
            server.register_node(nd)
        job = device_job(count=4)
        ev = server.register_job(job)
        assert wait_until(
            lambda: server.store.eval_by_id(ev.id).terminal_status()
            and server.broker.stats()["total_unacked"] == 0, timeout=30)
    finally:
        server.stop()
    spans = global_tracer.get(ev.id)
    (row,) = [s for s in spans if s["name"] == "solve.devices"]
    parent = {s["span_id"]: s for s in spans}[row["parent_id"]]
    assert parent["name"] == "solve.fixup"
    assert 0.0 < row["dur_s"] <= parent["dur_s"]
    assert row["t_end"] <= parent["t_end"]
