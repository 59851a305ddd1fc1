"""Replicas kept apart on the served path, at the shape the cell
`c6-distinct-10k.closed1` runs them: c3's jobs under group
`distinct_hosts`, a group rack limit and a job zone limit.

(a) the JAX program against the numpy twin on batches under a rack
limit, a zone limit two groups of one job share, both, counts a job
already holds, and a value whose cap is reached inside a wave; what they
place reads 0 by `benchmark/rules/distinct.py`'s numbers; (b) a batch
without a limit packs the arrays commit 7921479 packed, by the full pack
and by the resident repack; (c) the probe rule `distinct_in_wave` passes
here and names a wave given no property slot; (d) whole runs of the cell
through `Server` at rehearsal size, held to the plain reference and to
the counters the solve writes; (e) a fused round of two jobs under
job-scope zone limits; (f) the three per-layer metrics read their
counters and nothing otherwise.
"""
import copy
import json
import os
import sys

import numpy as np
import pytest

import distinct_pack_cases as pack_cases
import ports_pack_cases as cases
from test_host_solver import assert_same

from nomad_tpu import mock, structs
from nomad_tpu.solver import tensorize
from nomad_tpu.solver.host import host_solve_kernel
from nomad_tpu.solver.kernel import solve_kernel
from nomad_tpu.solver.solve import _kernel_args, _run_kernel
from nomad_tpu.solver.tensorize import PlacementAsk, Tensorizer
from nomad_tpu.utils.metrics import global_metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "c6-distinct-10k.closed1"
RACK, ZONE = "${attr.rack}", "${attr.zone}"


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules under their bare names (as
    `tests/test_ports_served.py` takes them)."""
    import jax
    keep = {k: getattr(jax.config, k) for k in (
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    sys.path.insert(0, BENCH)
    import check
    import cluster
    import layers
    import run
    yield {"run": run, "cluster": cluster, "check": check,
           "layers": layers}
    sys.path.remove(BENCH)
    for k, v in keep.items():
        jax.config.update(k, v)


def counters(prefixes=("solver.",)):
    return {k: v for k, v in global_metrics.dump()["counters"].items()
            if k.startswith(prefixes)}


def moved(before, after, key):
    return after.get(key, 0.0) - before.get(key, 0.0)


# ------------------------------------------------ (a) kernel and twin
def rows(n=64):
    """`ports_pack_cases.rows` (rack = row % 8, one cpu size a rack, the
    smallest in r0) with zone = row % 4, as c3's zone is its rack % 16."""
    nodes = cases.rows(n)
    for i, nd in enumerate(nodes):
        nd.attributes["zone"] = f"z{i % 4}"
        nd.compute_class()
    return nodes


def limited_job(groups, count, distinct):
    """A job of `groups` x `count` under `distinct`: (scope, operand,
    attribute, limit) entries as `job.distinct` of a configuration."""
    jb = cases.job(groups, count, affinity=True)
    jb.id = jb.name = f"kept-apart-{groups}x{count}"
    for scope, operand, attr, limit in distinct:
        c = (structs.Constraint(operand=operand) if attr is None else
             structs.Constraint(ltarget=attr, rtarget=str(limit),
                                operand=operand))
        for holder in ([jb] if scope == "job" else jb.task_groups):
            holder.constraints.append(copy.deepcopy(c))
    return jb


def limits_of(jb, tg, held=None):
    """`property_limits` as the generic scheduler builds them (keys of
    scope, job id and group; `held`: value -> allocs of the job there)."""
    out = {}
    for holder, scope in ((jb, f"job:default:{jb.id}"),
                          (tg, f"tg:default:{jb.id}:{tg.name}")):
        for c in holder.constraints:
            if c.operand == structs.CONSTRAINT_DISTINCT_PROPERTY:
                out[(scope, c.ltarget)] = (int(c.rtarget),
                                           dict((held or {}).get(
                                               c.ltarget, {})))
    return out


BATCHES = {
    # one group of 12 under a rack limit of 2: bin-pack ranks r0 first
    "rack": dict(groups=1, count=12, distinct=[
        ("group", "distinct_property", RACK, 2)]),
    # two groups share the job's zone limit: 4 zones x 3 hold all 12
    "zone_shared": dict(groups=2, count=6, distinct=[
        ("job", "distinct_property", ZONE, 3)]),
    "both": dict(groups=2, count=6, distinct=[
        ("group", "distinct_hosts", None, None),
        ("group", "distinct_property", RACK, 2),
        ("job", "distinct_property", ZONE, 4)]),
    # the job already holds 1 in r0 and 2 in r1
    "held": dict(groups=1, count=8, distinct=[
        ("group", "distinct_property", RACK, 2)],
        held={RACK: {"r0": 1, "r1": 2}}),
}


def limited_batch(name):
    spec = BATCHES[name]
    nodes = rows()
    allocs = {nd.id: [cases.resident(nd, k) for k in range(5)]
              for nd in nodes}
    jb = limited_job(spec["groups"], spec["count"], spec["distinct"])
    asks = [PlacementAsk(job=jb, tg=tg, count=tg.count,
                         property_limits=limits_of(jb, tg,
                                                   spec.get("held")))
            for tg in jb.task_groups]
    return nodes, jb, asks, Tensorizer().pack(nodes, asks, allocs)


def distinct_numbers(bench, nodes, jb, pb, res, held=None):
    """`rules/distinct.py`'s numbers over what the solve committed (and
    what the job held before it, as allocs on a node of the value)."""
    rule = bench["cluster"].load_rule("distinct")
    ok = np.asarray(res.choice_ok)[:pb.n_place, 0]
    choice = np.asarray(res.choice)[:pb.n_place, 0]
    out = {"job_id": [], "group": [], "node": []}
    for p in np.flatnonzero(ok):
        out["job_id"].append(jb.id)
        out["group"].append(jb.task_groups[int(pb.p_ask[p])].name)
        out["node"].append(int(choice[p]))
    for attr, by_value in (held or {}).items():
        key = attr[len("${attr."):-1]
        for value, n in by_value.items():
            ni = next(i for i, nd in enumerate(nodes)
                      if nd.attributes[key] == value)
            for _ in range(n):
                for tg in jb.task_groups:
                    out["job_id"].append(jb.id)
                    out["group"].append(tg.name)
                    out["node"].append(ni)
    distinct = []
    for c in jb.constraints:
        distinct.append(_entry("job", c))
    for c in jb.task_groups[0].constraints:
        distinct.append(_entry("group", c))
    cfg = {"job": {"distinct": [e for e in distinct if e]}}

    class Plain:
        cols = {RACK: np.array([nd.attributes["rack"] for nd in nodes]),
                ZONE: np.array([nd.attributes["zone"] for nd in nodes])}
    return rule.numbers(cfg, Plain, out, [(jb.id, None)], {}), int(ok.sum())


def _entry(scope, c):
    if c.operand == structs.CONSTRAINT_DISTINCT_HOSTS:
        return {"scope": scope, "operand": "distinct_hosts"}
    if c.operand == structs.CONSTRAINT_DISTINCT_PROPERTY:
        return {"scope": scope, "operand": "distinct_property",
                "attribute": c.ltarget, "limit": int(c.rtarget)}
    return None


@pytest.mark.parametrize("pallas_mode", ["off", "topk"])
@pytest.mark.parametrize("name", sorted(BATCHES))
def test_the_property_program_matches_the_numpy_twin(bench, name,
                                                     pallas_mode):
    nodes, jb, asks, pb = limited_batch(name)
    assert pb.pl_slot is not None and pb.pl_values > 0
    kw = dict(pl_slot=pb.pl_slot, pl_col=pb.pl_col, pl_left=pb.pl_left)
    args = _kernel_args(pb)
    res_dev = solve_kernel(*args, 0, has_spread=True,
                           pallas_mode=pallas_mode, **kw)
    res_host = host_solve_kernel(*args, 0, has_spread=True, **kw)
    assert_same(res_dev, res_host)
    numbers, placed = distinct_numbers(bench, nodes, jb, pb, res_host,
                                       BATCHES[name].get("held"))
    assert numbers == {"distinct_hosts_shared": 0,
                       "distinct_property_over": 0}
    # every placement lands: the limits leave room for the whole job
    assert placed == pb.n_place
    assert not np.asarray(res_host.unfinished).any()


def test_a_value_reaches_its_cap_inside_one_wave(bench):
    """One wave: bin-pack ranks rack r0's eight nodes first, so the
    wave offers several of the group's placements there; two commit, the
    rest go round again (unfinished), none past the limit."""
    nodes, jb, asks, pb = limited_batch("rack")
    kw = dict(pl_slot=pb.pl_slot, pl_col=pb.pl_col, pl_left=pb.pl_left)
    args = _kernel_args(pb)
    res_dev = solve_kernel(*args, 0, has_spread=True, max_waves=1, **kw)
    res_host = host_solve_kernel(*args, 0, has_spread=True, max_waves=1,
                                 **kw)
    assert_same(res_dev, res_host)
    ok = res_host.choice_ok[:pb.n_place, 0]
    racks = [nodes[int(i)].attributes["rack"]
             for i in res_host.choice[:pb.n_place, 0][ok]]
    assert racks.count("r0") == 2
    assert res_host.unfinished[:pb.n_place].sum() > 0
    numbers, _placed = distinct_numbers(bench, nodes, jb, pb, res_host)
    assert numbers["distinct_property_over"] == 0
    # the same wave without the slots offers r0 more than two
    blind = host_solve_kernel(*args, 0, has_spread=True, max_waves=1)
    ok_b = blind.choice_ok[:pb.n_place, 0]
    assert [nodes[int(i)].attributes["rack"] for i in
            blind.choice[:pb.n_place, 0][ok_b]].count("r0") > 2


def test_a_value_the_job_holds_to_its_limit_is_off_the_wave():
    nodes, jb, asks, pb = limited_batch("held")
    res = host_solve_kernel(*_kernel_args(pb), 0, has_spread=True,
                            pl_slot=pb.pl_slot, pl_col=pb.pl_col,
                            pl_left=pb.pl_left)
    racks = [nodes[int(i)].attributes["rack"]
             for i in res.choice[:pb.n_place, 0]]
    assert "r1" not in racks and racks.count("r0") == 1


def test_a_node_without_the_attribute_is_off_the_wave():
    nodes, jb, asks, pb = limited_batch("rack")
    del nodes[0].attributes["rack"]
    nodes[0].compute_class()
    allocs = {nd.id: [cases.resident(nd, k) for k in range(5)]
              for nd in nodes}
    pb = Tensorizer().pack(nodes, asks, allocs)
    res = _run_kernel(pb, host_mode="always")
    chosen = set(res.choice[:pb.n_place, 0][res.choice_ok[:pb.n_place, 0]])
    assert 0 not in chosen and len(chosen) == pb.n_place


def test_the_slots_a_job_scope_limit_shares():
    nodes, jb, asks, pb = limited_batch("both")
    # two groups: a rack slot each, one zone slot for the job
    assert pb.pl_slot[:2].tolist() == [[0, 1], [0, 2]]
    assert pb.pl_slot[2:].tolist() == [[-1, -1]] * (len(pb.pl_slot) - 2)
    cols = [pb.attr_targets[c] for c in pb.pl_col[:3]]
    assert cols == [ZONE, RACK, RACK]
    assert pb.pl_left[0, :4].tolist() == [4.0] * 4
    assert pb.pl_values == 4 + 8 + 8


# --------------------------------- (b) no limit: the parent's arrays
@pytest.mark.parametrize("name", sorted(pack_cases.digests()))
def test_a_batch_without_a_limit_packs_the_arrays_it_did(name):
    with open(os.path.join(ROOT, "tests", "golden",
                           "distinct_pack_parent.json")) as f:
        want = json.load(f)[name]
    how, case = name.split(":")
    pb = (pack_cases.pack if how == "pack" else pack_cases.repack)(case)
    assert cases.digest(pb) == want
    assert pb.pl_slot is None and pb.pl_col is None and pb.pl_left is None


def test_a_stream_solver_repack_still_refuses_a_limit():
    nodes, jb, asks, _pb = limited_batch("rack")
    tz = Tensorizer()
    template = tz.pack(nodes, asks, {})
    assert tz.repack_asks(nodes, asks, template) is None
    pb = tz.repack_asks(nodes, asks, template, property_slots=True)
    assert pb is not None and pb.pl_slot is not None


# ------------------------------------------------------ (c) the probe
def test_the_probe_passes_here_and_names_a_wave_without_slots(
        bench, monkeypatch):
    rule = bench["cluster"].load_rule("distinct_in_wave")
    before = counters()
    rule.require_property_limits_in_the_wave()
    assert counters() == before         # nothing of the served path
    monkeypatch.setattr(tensorize, "property_planes",
                        lambda *a, **k: None)
    with pytest.raises(RuntimeError, match="does not count a "
                       "distinct_property limit"):
        rule.require_property_limits_in_the_wave()


# --------------------------------------- (d) the cell through Server
@pytest.mark.parametrize("seed", [11, 2**31 + 5])
def test_the_cell_through_server_against_the_reference(
        bench, capsys, monkeypatch, seed):
    run, cluster = bench["run"], bench["cluster"]
    for k, v in (("clients", 1), ("warmup_bursts", [1]),
                 ("wait_timeout_s", 10)):
        monkeypatch.setitem(cluster.REHEARSE_TRAFFIC, k, v)
    global_metrics.reset()
    m0 = counters()
    assert run.run(run.parse_args([
        "--workload", CELL, "--seed", str(seed), "--seconds", "0.5",
        "--trace", "0", "--rehearse"])) == 0
    m1 = counters()
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    c = {k: v["value"] for k, v in line["compared"].items()}
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 2 and c["failed"] == 0
    assert c["jobs_off_count"] == 0 and c["overcommitted_nodes"] == 0
    assert c["distinct_hosts_shared"] == 0
    assert c["distinct_property_over"] == 0
    assert c["score_mismatch_p99"] < 2e-5
    # the resident world took every batch; the wave counted the slots
    # and the fixup's re-check refused nothing
    solves = sum(moved(m0, m1, k) for k in m1
                 if k.startswith("solver.solve."))
    assert solves >= line["attempted"]
    assert "solver.resident.passed_over" in m1
    assert moved(m0, m1, "solver.resident.passed_over") == 0
    assert moved(m0, m1, "solver.property.columns") >= solves * (64 + 16)
    assert "solver.property.refused" in m1
    assert moved(m0, m1, "solver.property.refused") == 0


# ------------------------------------------------ (e) a fused round
@pytest.mark.parametrize("min_nodes", [512, 1])
def test_a_fused_round_of_two_jobs_keeps_each_to_its_zone_limit(
        monkeypatch, min_nodes):
    """Sixteen nodes in four zones, the eight of z0 the fullest (so
    ranked first by bin-pack, and every candidate a placement's window
    offers lies there); two jobs of 2 groups x 2 whose job-scope zone
    limit is 1: each job needs all four zones, and neither job's charges
    count against the other's."""
    from nomad_tpu.scheduler.fleet import process_fleet
    from nomad_tpu.server.server import Server
    from nomad_tpu.server.worker import Worker
    from nomad_tpu.solver import solve as solve_mod
    monkeypatch.setattr(solve_mod, "RESIDENT_MIN_NODES", min_nodes)
    server = Server(num_workers=0)
    server.start()
    try:
        zone_of = {}
        for i in range(16):
            n = mock.node()
            n.attributes["zone"] = "z0" if i < 8 else f"z{1 + i % 3}"
            n.reserved_resources.cpu = 2000 if i < 8 else 0
            n.compute_class()
            server.register_node(n)
            zone_of[n.id] = n.attributes["zone"]
        jobs = []
        for j in range(2):
            job = mock.job()
            job.constraints = [structs.Constraint(
                ltarget=ZONE, rtarget="1",
                operand=structs.CONSTRAINT_DISTINCT_PROPERTY)]
            base = job.task_groups[0]
            base.count = 2
            for t in base.tasks:
                t.resources.networks = []
            second = copy.deepcopy(base)
            second.name = "api"
            job.task_groups.append(second)
            jobs.append(job)
            server.register_job(job)
        batch = server.broker.dequeue_batch(["service"], 8, 1.0)
        assert len(batch) == 2
        process_fleet(server, Worker(server, ["service"]), batch)
        for job in jobs:
            allocs = [a for a in server.store.allocs_by_job("default",
                                                            job.id)
                      if not a.terminal_status()]
            assert len(allocs) == 4, f"{job.id} left short"
            assert sorted(zone_of[a.node_id] for a in allocs) == \
                ["z0", "z1", "z2", "z3"]
    finally:
        server.stop()


# ------------------------------------------ (f) the per-layer metrics
METRICS = {
    "property_columns_per_solve": (
        {"solver.property.columns": 1920.0, "solver.solve.tpu": 24.0},
        80.0),
    "property_refused_per_solve": (
        {"solver.property.refused": 0.0, "solver.solve.tpu": 24.0}, 0.0),
    "resident_passover_share": (
        {"solver.resident.passed_over": 3.0, "solver.solve.tpu": 24.0},
        12.5),
}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_property_metrics_read_their_counters_and_nothing_otherwise(
        bench, name):
    layers = bench["layers"]
    have, reads = METRICS[name]
    assert layers.read_metric(name, layers.Observed(
        counters=have)) == pytest.approx(reads)
    # a program without the counter reports nothing, and raises nothing
    solves = {"solver.solve.tpu": 24.0}
    assert layers.read_metric(name, layers.Observed(
        counters=solves)) is None
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == name)
    if name == "resident_passover_share":
        assert CELL in entry["workloads"] and len(entry["workloads"]) == 6
    else:
        assert entry["workloads"] == [CELL]
