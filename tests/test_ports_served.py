"""Ports on the served path, at the shape the cell `c5-ports-10k.closed1`
runs them: every resident alloc holding dynamic ports, port 8080 taken
on a quarter of the nodes, jobs of 4 unlike groups of which the first
reserves 8080.

(a) whole runs of the cell through `Server` at rehearsal size, held to
the plain reference (`benchmark/reference.py` with
`benchmark/rules/ports.py`) and to the counters and the sample the solve
writes; (b) ISSUE 34's probe as a regression: 30 jobs one after another
on 256 nodes lose no static-port placement; (c) a job that reserves no
static port packs the planes commit 80bda71 packed, byte for byte;
(d) the JAX program against the numpy twin on a batch whose capacity-1
column makes the wave's conflict sort decide; (e) the column across two
solves of the resident world; (f) the refusal path: the reason a
refused placement ends with, and when it is retried.
"""
import copy
import json
import os
import sys

import numpy as np
import pytest

import ports_pack_cases as cases
from test_host_solver import assert_same

from nomad_tpu import mock, structs
from nomad_tpu.solver.host import host_solve_kernel
from nomad_tpu.solver.kernel import TOP_K, solve_kernel
from nomad_tpu.solver.solve import (LazyAllocsView, Solver, _kernel_args,
                                    _PortTally)
from nomad_tpu.solver.tensorize import (PlacementAsk, Tensorizer, port_key,
                                        static_port_columns)
from nomad_tpu.state.store import StateStore
from nomad_tpu.utils.metrics import global_metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "c5-ports-10k.closed1"
#: ports one whole job of the cell's template holds:
#: edge 4 x (lb + admin), api 20 x 2, worker 20 x 1, cache 20 x 2
JOB_PORTS = 4 * 2 + 20 * 2 + 20 * 1 + 20 * 2


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules under their bare names (as
    `tests/test_device_asks_served.py` takes them)."""
    import jax
    keep = {k: getattr(jax.config, k) for k in (
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    sys.path.insert(0, BENCH)
    import check
    import cluster
    import run
    yield {"run": run, "cluster": cluster, "check": check}
    sys.path.remove(BENCH)
    for k, v in keep.items():
        jax.config.update(k, v)


def port_metrics():
    d = global_metrics.dump()
    out = {k: v for k, v in d["counters"].items()
           if k.startswith(("solver.ports.", "solver.solve.",
                            "plan.port_refused"))}
    out["samples"] = d["samples"].get("span.solve.ports",
                                      {"count": 0, "sum": 0.0})
    return out


def moved(before, after, key):
    return after.get(key, 0.0) - before.get(key, 0.0)


def network(mbits=10, static=(), dynamic=0):
    return structs.NetworkResource(
        mbits=mbits,
        reserved_ports=[structs.Port(f"s{p}", p) for p in static],
        dynamic_ports=[structs.Port(f"d{j}", 0) for j in range(dynamic)])


def port_job(groups, job_id="ports"):
    """`groups`: (name, count, cpu, static ports, dynamic labels)."""
    jb = mock.job()
    jb.id = jb.name = job_id
    jb.datacenters = [f"dc{i}" for i in range(4)]
    jb.constraints = []
    base = jb.task_groups[0]
    jb.task_groups = []
    for name, count, cpu, static, dynamic in groups:
        tg = copy.deepcopy(base)
        tg.name, tg.count, tg.constraints = name, count, []
        res = tg.tasks[0].resources
        res.cpu, res.memory_mb, res.devices = cpu, 256, []
        res.networks = [network(static=static, dynamic=dynamic)]
        tg.ephemeral_disk.size_mb = 300
        jb.task_groups.append(tg)
    return jb


def asks_of(job):
    return [PlacementAsk(job=job, tg=tg, count=tg.count)
            for tg in job.task_groups]


def holder(node, port, k=0, cpu=200):
    """A live alloc on `node` that holds static `port`."""
    a = cases.resident(node, k)
    a.id = f"holder-{node.name}-{port}-{k}"
    tr = a.allocated_resources.tasks["web"]
    tr.cpu = cpu
    tr.networks = [structs.NetworkResource(
        device="eth0", ip=node.node_resources.networks[0].ip, mbits=10,
        reserved_ports=[structs.Port("lb", port)])]
    return a


def ports_of(resources):
    return [(p.label, p.value) for tr in resources.tasks.values()
            for n in tr.networks for p in n.reserved_ports + n.dynamic_ports]


def as_alloc(job, tg, placement, alloc_id):
    return structs.Allocation(
        id=alloc_id, job_id=job.id, job=job, task_group=tg.name,
        node_id=placement.node.id,
        allocated_resources=placement.resources,
        desired_status=structs.ALLOC_DESIRED_RUN,
        client_status=structs.ALLOC_CLIENT_RUNNING)


# ----------------------------------- (a) the cell through Server, 3 seeds
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
        seen["cfg"] = cfg
        return seen["rows"]
    monkeypatch.setattr(check, "rows_from_snapshot", rows_from_snapshot)
    # `run.run` reads `off_device_solves` from the process's counters as
    # they stand, and an earlier test file of this worker may have left
    # a degraded solve or a watchdog failover in them
    global_metrics.reset()
    m0 = port_metrics()
    assert run.run(run.parse_args([
        "--workload", CELL, "--seed", str(seed), "--seconds", "0.5",
        "--trace", "0", "--rehearse"])) == 0
    m1 = port_metrics()
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    # against the plain reference: every job at its count in every
    # group, nobody shares a port, everybody holds what its group asked
    c = {k: v["value"] for k, v in line["compared"].items()}
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 2 and c["failed"] == 0
    assert c["jobs_off_count"] == 0 and c["overcommitted_nodes"] == 0
    assert c["port_collisions"] == 0 and c["ports_unmet"] == 0
    assert c["bandwidth_overcommitted_nodes"] == 0
    assert c["score_mismatch_p99"] < 2e-5

    # and read from the store's rows directly
    rows, plain, cfg = seen["rows"], seen["plain"], seen["cfg"]
    rule = cluster.load_rule("ports")
    per_group = {s["name"]: s for s in cfg["job"]["ports"]["groups"]}
    mine = [k for k, j in enumerate(rows["job_id"])
            if not j.startswith("resident-")]
    held = set()
    n_ports = 0
    for k, h in enumerate(rows["ports"]):
        ni = int(rows["node"][k])
        assert h["ip"] == rule.address(plain.extra["port_row"][ni])
        for value in list(h["static"].values()) \
                + list(h["dynamic"].values()):
            assert (ni, value) not in held
            held.add((ni, value))
    for k in mine:
        h, spec = rows["ports"][k], per_group[rows["group"][k]]
        assert h["static"] == spec["static"]
        assert sorted(h["dynamic"]) == sorted(spec["dynamic"])
        assert all(20000 <= v <= 32000 for v in h["dynamic"].values())
        assert h["mbits"] == spec["mbits"]
        n_ports += len(h["static"]) + len(h["dynamic"])
    # residents: 2 dynamic ports each, 8080 on a quarter of the nodes
    res = [h for j, h in zip(rows["job_id"], rows["ports"])
           if j.startswith("resident-")]
    assert len(res) == 2560 and all(len(h["dynamic"]) == 2 for h in res)
    assert sum(1 for h in res if h["static"] == {"lb": 8080}) == 128
    # the window's jobs are the whole template: 4 + 20 + 20 + 20
    per_job = {}
    for k in mine:
        key = (rows["job_id"][k], rows["group"][k])
        per_job[key] = per_job.get(key, 0) + 1
    whole = [j for (j, g), n in per_job.items() if g == "edge" and n == 4
             and per_job.get((j, "api")) == 20
             and per_job.get((j, "worker")) == 20
             and per_job.get((j, "cache")) == 20]
    assert len(whole) >= line["attempted"] + 1

    # what the solves wrote: every port the run's allocs hold was handed
    # out once, no candidate was walked past, one sample a solve, a
    # column a solve (every job's first group reserves 8080), and the
    # applier refused no node
    solves = sum(moved(m0, m1, k) for k in m1
                 if k.startswith("solver.solve."))
    assert solves >= line["attempted"]
    assert moved(m0, m1, "solver.ports.assigned") == n_ports
    assert n_ports >= JOB_PORTS * len(whole)
    assert moved(m0, m1, "solver.ports.refused") == 0
    assert moved(m0, m1, "solver.ports.static_columns") == solves
    assert m1["samples"]["count"] - m0["samples"]["count"] == solves
    assert m1["samples"]["sum"] > m0["samples"]["sum"]
    assert "plan.port_refused" in m1
    assert moved(m0, m1, "plan.port_refused") == 0


# --------------------------------------------- (b) the probe, a regression
def test_thirty_jobs_in_a_row_lose_no_static_port_placement():
    """256 c2-sized nodes; each job a group of 4 x (500 MHz, static 8080
    + 1 dynamic port) and a group of 20 x (400 MHz, 2 dynamic ports);
    the allocs of each solve fed to the next.  Bin-pack ranks first the
    nodes the earlier jobs filled, which now hold the port: at commit
    80bda71 29 of 30 jobs lost 1 to 4 of their 4 static placements to
    `resources exhausted` and 45 nodes held the port at the end."""
    nodes = cases.rows(256)
    solver = Solver(host="always")
    by_node = {}
    for j in range(30):
        job = port_job([("edge", 4, 500, (8080,), 1),
                        ("api", 20, 400, (), 2)], job_id=f"job-{j}")
        asks = asks_of(job)
        out = solver.solve(nodes, asks, by_node,
                           {f"dc{i}": 64 for i in range(4)})
        for k, p in enumerate(out.placements):
            tg = asks[p.ask_index].tg
            assert p.node is not None, (j, tg.name, p.failed_reason)
            by_node.setdefault(p.node.id, []).append(
                as_alloc(job, tg, p, f"a-{j}-{k}"))
    holders = [nid for nid, allocs in by_node.items()
               if any(v == 8080 for a in allocs
                      for _l, v in ports_of(a.allocated_resources))]
    assert len(holders) == len(set(holders)) == 30 * 4
    for allocs in by_node.values():
        values = [v for a in allocs
                  for _l, v in ports_of(a.allocated_resources)]
        assert len(values) == len(set(values))


# ------------------- (c) no static port, no column: the parent's planes
@pytest.mark.parametrize("name", sorted(cases.CASES))
def test_a_job_without_a_static_port_packs_the_planes_it_did(name):
    with open(os.path.join(ROOT, "tests", "golden",
                           "ports_pack_parent.json")) as f:
        want = json.load(f)[name]
    pb = cases.pack(name)
    assert cases.digest(pb) == want
    assert pb.dev_cap.shape[1] == 1 and static_port_columns(pb) == 0
    assert not any(key[0] == port_key(0)[0] for key in pb.dev_pattern_ids)


def test_a_static_port_is_a_column_of_capacity_one():
    nodes = cases.rows(16)
    nodes[3].reserved_resources.reserved_host_ports = "22,8080"
    allocs = {nodes[5].id: [holder(nodes[5], 8080)],
              # a dynamic port that fell on the value counts as well
              nodes[6].id: [cases.resident(nodes[6], 0, ports=(8080,))],
              nodes[7].id: [holder(nodes[7], 9090)]}
    job = port_job([("edge", 2, 500, (8080,), 1), ("api", 4, 400, (), 2),
                    ("lb2", 1, 300, (8080, 9090), 0)])
    pb = Tensorizer().pack(nodes, asks_of(job), allocs)
    assert pb.dev_pattern_ids == {port_key(8080): 0, port_key(9090): 1}
    assert static_port_columns(pb) == 2
    cap, used = pb.dev_cap[:16], pb.dev_used0[:16]
    assert cap[:, 0].tolist() == [1] * 3 + [0] + [1] * 12
    assert cap[:, 1].tolist() == [1] * 16
    assert used[:, 0].nonzero()[0].tolist() == [5, 6]
    assert used[:, 1].nonzero()[0].tolist() == [7]
    assert pb.dev_ask[:3].tolist() == [[1, 0], [0, 0], [1, 1]]
    # two groups that reserve the same port share the column; the
    # signature that keys the resident world's cached rows tells two
    # groups apart that differ in the port alone
    a, b = (port_job([("edge", 2, 500, (p,), 1)]).task_groups[0]
            for p in (8080, 9090))
    assert Tensorizer.tg_signature(a) != Tensorizer.tg_signature(b)


# ---------------- (d) the JAX program against the numpy twin, c5's shape
def c5_batch(second_edge=False):
    """64 rows, 5 residents a node holding dynamic ports, 8080 held on
    every fourth row; the cell's four groups cut to 4 + 6 + 6 + 6, and
    with `second_edge` a fifth group that reserves 8080 as well."""
    nodes = cases.rows(64)
    allocs = {}
    for i, nd in enumerate(nodes):
        allocs[nd.id] = [cases.resident(
            nd, k, ports=(20000 + 2 * k, 20001 + 2 * k)) for k in range(5)]
        if i % 4 == 1:
            allocs[nd.id][0] = holder(nd, 8080)
    groups = [("edge", 4, 500, (8080,), 1), ("api", 6, 400, (), 2),
              ("worker", 6, 600, (), 1), ("cache", 6, 300, (), 2)]
    if second_edge:
        groups.append(("edge2", 4, 500, (8080,), 1))
    job = port_job(groups)
    return nodes, Tensorizer().pack(nodes, asks_of(job), allocs)


@pytest.mark.parametrize("pallas_mode", ["off", "topk"])
@pytest.mark.parametrize("second_edge", [False, True])
def test_a_port_column_program_matches_the_numpy_twin(second_edge,
                                                      pallas_mode):
    nodes, pb = c5_batch(second_edge)
    assert pb.dev_cap.sum() == 64 and pb.dev_used0.sum() == 16
    assert pb.dev_ask[:pb.n_asks, 0].tolist() == \
        [1, 0, 0, 0] + [1] * second_edge
    args = _kernel_args(pb)
    res_dev = solve_kernel(*args, 0, has_spread=False, has_devices=True,
                           pallas_mode=pallas_mode)
    res_host = host_solve_kernel(*args, 0, has_spread=False)
    assert_same(res_dev, res_host)
    n = pb.n_place
    choice = np.asarray(res_dev.choice)[:n, 0]
    assert np.asarray(res_dev.choice_ok)[:n, 0].all()
    edge = choice[:4].tolist() + choice[22:n].tolist()
    # each on a node of its own, none of them a holder: rows 1, 5, 9 ..
    # are the fullest on cpu of their size, so bin-pack alone would
    # have taken them first
    assert len(set(edge)) == len(edge) and all(i % 4 != 1 for i in edge)
    # one group's placements are offered its first, second, ... best
    # node, so four of them on a capacity of one settle in one wave;
    # two groups that reserve the same port are offered the same nodes,
    # and there the wave's conflict sort decides who goes round again
    waves = int(np.asarray(res_dev.n_waves))
    assert waves > 1 if second_edge else waves == 1


# ------------------------ (e) the column, carried across two solves
def test_a_nodes_port_goes_once_across_two_solves():
    store = StateStore()
    nodes = cases.rows(8)
    for i, nd in enumerate(nodes):
        store.upsert_node(100 + i, nd)
    # node 0 is by far the fullest, so bin-pack takes it first
    full = cases.resident(nodes[0], 0)
    full.allocated_resources.tasks["web"].cpu = 2500
    store.upsert_allocs(200, [full])
    solver = Solver(store=store, resident_min_nodes=1)

    def solve(job):
        snapshot = store.snapshot()
        ready, by_dc = snapshot.ready_nodes_in_dcs(job.datacenters)
        assert solver.resident_active(snapshot)
        out = solver.solve(ready, asks_of(job), LazyAllocsView(snapshot),
                           by_dc, snapshot=snapshot,
                           proposed_delta=((), ()))
        assert all(p.node is not None for p in out.placements)
        return out.placements

    m0 = port_metrics()
    first = port_job([("edge", 1, 500, (8080,), 1)], job_id="first")
    store.upsert_job(201, first)
    placed = solve(first)
    assert placed[0].node.id == nodes[0].id
    assert ("s8080", 8080) in ports_of(placed[0].resources)
    store.upsert_allocs(202, [as_alloc(first, first.task_groups[0],
                                       placed[0], "first-0")])
    # the world is advanced from the store's change log, not rebuilt,
    # and the wave offers node 0 to nobody who reserves 8080
    rebuilds = global_metrics.dump()["counters"].get(
        "solver.resident.rebuild", 0)
    second = port_job([("edge", 2, 500, (8080,), 1)], job_id="second")
    store.upsert_job(203, second)
    placed = solve(second)
    assert global_metrics.dump()["counters"].get(
        "solver.resident.rebuild", 0) == rebuilds
    assert nodes[0].id not in {p.node.id for p in placed}
    assert len({p.node.id for p in placed}) == 2
    m1 = port_metrics()
    assert moved(m0, m1, "solver.ports.refused") == 0
    assert moved(m0, m1, "solver.ports.assigned") == 3 * 2
    assert moved(m0, m1, "solver.ports.static_columns") == 2
    # a job with dynamic ports only still lands on node 0
    third = port_job([("api", 1, 400, (), 2)], job_id="third")
    store.upsert_job(204, third)
    assert solve(third)[0].node.id == nodes[0].id


def test_a_port_the_world_has_not_seen_rebuilds_it_once():
    """A static port outside the resident template's registry is an ask
    outside its universe: the probes grow and the world is packed again
    with the column, as for an unseen device pattern."""
    store = StateStore()
    nodes = cases.rows(8)
    for i, nd in enumerate(nodes):
        store.upsert_node(100 + i, nd)
    store.upsert_allocs(200, [holder(nodes[0], 9090, cpu=2500)])
    solver = Solver(store=store, resident_min_nodes=1)

    def solve(job):
        snapshot = store.snapshot()
        ready, by_dc = snapshot.ready_nodes_in_dcs(job.datacenters)
        return solver.solve(ready, asks_of(job), LazyAllocsView(snapshot),
                            by_dc, snapshot=snapshot,
                            proposed_delta=((), ())).placements

    plain = port_job([("api", 1, 400, (), 1)], job_id="plain")
    assert solve(plain)[0].node.id == nodes[0].id
    assert solver._world.template.dev_pattern_ids == {}
    asking = port_job([("edge", 1, 500, (9090,), 0)], job_id="asking")
    placed = solve(asking)
    assert solver._world.template.dev_pattern_ids == {port_key(9090): 0}
    assert placed[0].node is not None
    assert placed[0].node.id != nodes[0].id


# ------------------------------------------------ (f) the refusal path
def two_link_nodes(n):
    """Nodes with two networks of 100 mbits on two devices: the wave's
    bandwidth column holds 200, `assign_network` offers one device."""
    nodes = cases.rows(n)
    for i, nd in enumerate(nodes):
        first = nd.node_resources.networks[0]
        first.mbits = 100
        nd.node_resources.networks.append(structs.NetworkResource(
            device="eth1", cidr="10.1.0.0/16", ip=f"10.1.0.{i}",
            mbits=100))
    return nodes


def bandwidth_job(count=1):
    job = port_job([("fat", count, 400, (), 1)])
    job.task_groups[0].tasks[0].resources.networks[0].mbits = 150
    return job


def test_a_placement_every_candidate_refused_is_retried_and_says_why():
    nodes = two_link_nodes(TOP_K + 2)
    m0 = port_metrics()
    out = Solver(host="always").solve(nodes, asks_of(bandwidth_job()), {})
    m1 = port_metrics()
    p, = out.placements
    assert p.node is None
    assert p.failed_reason == "network: bandwidth exceeded"
    # TOP_K candidates walked, two more nodes the wave found placeable
    assert p.retryable is True
    assert p.metrics.dimension_exhausted == {
        "network: bandwidth exceeded": TOP_K}
    assert p.metrics.nodes_exhausted == TOP_K
    assert moved(m0, m1, "solver.ports.refused") == TOP_K
    assert moved(m0, m1, "solver.ports.assigned") == 0


def test_with_no_node_left_to_offer_it_fails_by_its_network_dimension():
    nodes = two_link_nodes(TOP_K)
    out = Solver(host="always").solve(nodes, asks_of(bandwidth_job()), {})
    p, = out.placements
    assert p.node is None and p.retryable is False
    assert p.failed_reason == "network: bandwidth exceeded"


def test_host_commit_tells_the_tally_which_network_reason():
    nodes = cases.rows(2)
    job = port_job([("edge", 1, 500, (8080,), 1)])
    ask = asks_of(job)[0]
    allocs = {nodes[0].id: [holder(nodes[0], 8080)]}
    tally = _PortTally()
    assert Solver._host_commit(nodes[0], 0, ask, {}, {}, allocs,
                               None, tally) is None
    assert (tally.refused, tally.reason) == (1, "reserved port collision")
    got = Solver._host_commit(nodes[1], 1, ask, {}, {}, allocs, None, tally)
    assert sorted(v for _l, v in ports_of(got))[0] == 8080
    assert (tally.commits, tally.assigned, tally.refused) == (2, 2, 1)
    assert tally.seconds > 0
    # the wave would not have offered node 0: its column is taken
    pb = Tensorizer().pack(nodes, [ask], allocs)
    assert pb.dev_used0[:2, 0].tolist() == [1, 0]


def test_a_job_that_asks_for_no_network_writes_none_of_them():
    nodes = cases.rows(16)
    job = port_job([("plain", 8, 400, (), 0)])
    job.task_groups[0].tasks[0].resources.networks = []
    m0 = port_metrics()
    out = Solver().solve(nodes, asks_of(job))
    m1 = port_metrics()
    assert all(p.node is not None for p in out.placements)
    assert {k: v for k, v in m1.items() if k.startswith("solver.ports")} \
        == {k: v for k, v in m0.items() if k.startswith("solver.ports")}
    assert m1["samples"] == m0["samples"]
    # and one that asks writes them, in the same process
    out = Solver().solve(nodes, asks_of(
        port_job([("edge", 2, 500, (8080,), 1)])))
    m2 = port_metrics()
    assert moved(m1, m2, "solver.ports.assigned") == 4
    assert moved(m1, m2, "solver.ports.static_columns") == 1
    assert m2["samples"]["count"] == m1["samples"]["count"] + 1


def test_the_applier_counts_a_node_it_refuses_for_a_port():
    from nomad_tpu.server.plan_apply import evaluate_plan
    store = StateStore()
    nodes = cases.rows(2)
    for i, nd in enumerate(nodes):
        store.upsert_node(100 + i, nd)
    store.upsert_allocs(200, [holder(nodes[0], 8080)])
    job = port_job([("edge", 2, 500, (8080,), 0)])
    plan = structs.Plan(job=job)
    for i, nd in enumerate(nodes):
        a = holder(nd, 8080, k=7)
        a.job, a.job_id = job, job.id
        plan.node_allocation[nd.id] = [a]
    before = global_metrics.dump()["counters"].get("plan.port_refused", 0)
    result = evaluate_plan(store.snapshot(), plan)
    assert list(result.node_allocation) == [nodes[1].id]
    assert global_metrics.dump()["counters"]["plan.port_refused"] \
        == before + 1
