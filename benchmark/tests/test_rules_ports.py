"""The ports rule (`benchmark/rules/ports.py`) and the configuration that
names it, `c5-ports-10k`, at 1,024 nodes: against its control and
`isolated_round`, a golden of its numbers (`rules_ports_golden.json`,
beside `test_rules.py`'s digests of c5), the share test (the resident
ports `resident_alloc` lays out on the wire are the plain side's
columns), the template's shapes, the planes, and the rule's probe of the
program.  `test_rules.py`'s test of what a rule file may import covers
the file by its glob.
"""
import json
import os

import numpy as np
import pytest

import bytes_models
import check
import cluster
import controls
import load

HERE = os.path.dirname(os.path.abspath(__file__))
GOLDEN = os.path.join(HERE, "rules_ports_golden.json")
NAME = "c5-ports-10k"
SEEDS = [3, 2**31 + 3, 77]
JOBS = 40


def small(nodes=1024):
    cfg = cluster.load_config(NAME)
    cfg["cluster"]["nodes"] = nodes
    cfg["resident"]["allocs"] = nodes * 5
    return cfg


@pytest.fixture(scope="module")
def rule():
    return cluster.load_rule("ports")


# ------------------------------------------------------ the job template
def test_the_template_is_four_unlike_groups_and_warm_up_jobs_keep_theirs():
    cfg = small()
    groups = cluster.job_groups(cfg)
    assert [(g["name"], g["count"], g["cpu"], g["mem"]) for g in groups] \
        == [("edge", 4, 500.0, 512.0), ("api", 20, 400.0, 256.0),
            ("worker", 20, 600.0, 384.0), ("cache", 20, 300.0, 640.0)]
    assert cluster.job_count(cfg) == 64
    assert sum(g["count"] * g["cpu"] for g in groups) == 28_000
    assert sum(g["count"] * g["mem"] for g in groups) == 27_648
    assert groups[0]["ports"] == {"mbits": 100, "static": {"lb": 8080},
                                  "dynamic": ["admin"]}
    assert [len(g["ports"]["dynamic"]) for g in groups] == [1, 2, 1, 2]
    # no warm-up shape has the template's count, so none is cut, and
    # its first group is `edge` whatever the shape
    traffic = load.load_traffic("closed1")
    shapes = cluster.leftover_shapes(cfg, traffic["warmup_leftover_totals"])
    assert len(shapes) == 18
    assert all(c != cfg["job"]["count_per_group"] for _g, c in shapes)
    edge = 0
    for g, c in shapes:
        got = cluster.job_groups(cfg, (g, c))
        assert [x["name"] for x in got] == \
            ["edge", "api", "worker", "cache"][:g]
        assert all(x["count"] == c for x in got)
        edge += c
    # what `assumed.capacity` states
    assert edge == 221
    free = cfg_nodes_with_the_port_free(cluster.load_config(NAME))
    assert free == 7_500
    assert cluster.ceiling_jobs(cluster.load_config(NAME)) * 4 + 233 <= free


def cfg_nodes_with_the_port_free(cfg):
    r = cfg["resident"]["ports"]
    n = cfg["cluster"]["nodes"]
    return n - len([i for i in range(n)
                    if i % r["static_every"] == r["static_at"]])


def test_the_cells_bytes_are_sixteen_planes():
    cfg = cluster.load_config(NAME)
    assert bytes_models.least_solve_bytes(cfg, 1, 0) == 16 * 10_000 * 4


# ------------------------------------- against its controls, and a golden
@pytest.mark.parametrize("seed", SEEDS)
def test_ports_rule_against_its_controls(seed, rule):
    cfg = small()
    check.validate(cfg)
    plain = cluster.make_plain_nodes(cfg, seed)
    # every seed the same multiset: a quarter of the rows hold 8080
    assert plain.extra["port_resident_static"].sum() == 256
    assert sorted(plain.extra["port_row"]) == list(range(1024))
    res = controls.run_controls(cfg, seed, JOBS)
    value = lambda c, n: res[c]["compared"][n]["value"]       # noqa: E731
    assert res["sound"]["correct"], res["sound"]
    for n in rule.NUMBERS:
        assert value("sound", n) == 0
    for control in ("ports_unaccounted", "isolated_round"):
        assert not res[control]["correct"]
        assert value(control, "port_collisions") > 0
        assert value(control, "ports_unmet") == 0
    assert value("ports_unaccounted", "bandwidth_overcommitted_nodes") == 0
    # the c2 controls are caught by the numbers that caught them there
    assert value("bfloat16", "score_mismatch_p99") > 6e-5
    assert value("sampled_14_nodes", "choice_gap_p90") \
        > cfg["correct"]["limits"]["choice_gap_p90"]["limit"]
    with open(GOLDEN, encoding="utf-8") as f:
        want = json.load(f)
    got = {c: {n: repr(v["value"]) for n, v in r["compared"].items()}
           for c, r in res.items()}
    assert got == want[str(seed)]


# ---------------------------------------------------------- the share test
@pytest.mark.parametrize("seed", SEEDS[:2])
def test_resident_ports_on_the_wire_are_the_plain_sides_columns(seed, rule):
    from nomad_tpu.structs import (AllocatedResources,
                                   AllocatedSharedResources,
                                   AllocatedTaskResources, Allocation)
    from nomad_tpu.utils.codec import from_wire, to_wire
    cfg = small(256)
    plain = cluster.make_plain_nodes(cfg, seed)
    n = len(plain)
    template = to_wire(Allocation(
        id="", task_group="r", allocated_resources=AllocatedResources(
            tasks={"web": AllocatedTaskResources(cpu=200, memory_mb=256)},
            shared=AllocatedSharedResources(disk_mb=300))))
    node_of = cluster.resident_node_index(cfg)
    rows = {"job_id": [], "group": [], "node": [], "ports": []}
    for k in range(int(cfg["resident"]["allocs"])):
        ni = int(node_of[k])
        w = dict(template, node_name=plain.names[ni])
        rule.resident_alloc(w, k, cfg)
        # the template is shared between the allocs: never written
        assert template["allocated_resources"]["tasks"]["web"][
            "networks"] == []
        alloc = from_wire(Allocation, w)
        rows["job_id"].append(f"resident-{k // 64}")
        rows["group"].append("r")
        rows["node"].append(ni)
        rows["ports"].append(rule.alloc_row(alloc)["ports"])
    rows["node"] = np.asarray(rows["node"])
    # the plain side's account of the same layout, from no row at all
    blind = {"job_id": rows["job_id"], "node": rows["node"]}
    want = rule._held(cfg, plain, blind)
    got = rule._held(cfg, plain, rows)
    assert [{k: h[k] for k in ("ip", "mbits", "static", "dynamic")}
            for h in got] == want
    # and the placer's starting columns
    st = rule._resident_state(cfg, plain)
    holds = np.zeros(n, bool)
    count = np.zeros(n, int)
    for ni, h in zip(rows["node"], got):
        holds[ni] |= 8080 in h["static"].values()
        count[ni] += len(h["static"]) + len(h["dynamic"])
        assert h["ip"] == rule.address(plain.extra["port_row"][ni])
    assert (st["static"][8080] == holds).all()
    assert (holds == plain.extra["port_resident_static"]).all()
    assert holds.sum() == n // 4
    assert (st["n_ports"] == count).all() and (st["mbits"] == 50).all()
    assert rule.numbers(cfg, plain, rows, [], {}) == {
        "port_collisions": 0, "ports_unmet": 0,
        "bandwidth_overcommitted_nodes": 0}


def test_the_numbers_see_a_shared_port_a_wrong_port_and_a_full_link(rule):
    cfg = small(64)
    plain = cluster.make_plain_nodes(cfg, 1)
    sent = [("job-1-0", None)]
    rows = check.reference_rows(cfg, plain, sent)
    zero = rule.numbers(cfg, plain, dict(rows), sent, {})
    assert set(zero.values()) == {0}
    mine = [k for k, j in enumerate(rows["job_id"]) if j == "job-1-0"]
    edge = [k for k in mine if rows["group"][k] == "edge"]

    def altered(change):
        r = {k: (list(v) if isinstance(v, list) else v)
             for k, v in rows.items() if not k.startswith("ports_")}
        r["ports"] = [None if h is None else json.loads(json.dumps(h))
                      for h in rows["ports"]]
        change(r)
        return rule.numbers(cfg, plain, r, sent, {})

    def same_dynamic(r):       # two allocs of one node share a value
        a, b = next((x, y) for x in mine for y in mine
                    if x < y and rows["node"][x] == rows["node"][y])
        label = next(iter(r["ports"][b]["dynamic"]))
        r["ports"][b]["dynamic"][label] = next(iter(
            r["ports"][a]["dynamic"].values()))
    assert altered(same_dynamic)["port_collisions"] == 1

    def static_moved(r):
        r["ports"][edge[0]]["static"]["lb"] = 8081
    assert altered(static_moved)["ports_unmet"] == 1

    def out_of_range(r):
        label = next(iter(r["ports"][mine[-1]]["dynamic"]))
        r["ports"][mine[-1]]["dynamic"][label] = 19999
    assert altered(out_of_range)["ports_unmet"] == 1

    def other_address(r):
        r["ports"][edge[1]]["ip"] = "10.9.9.9"
    assert altered(other_address)["ports_unmet"] == 1

    def full_link(r):
        r["ports"][edge[2]]["mbits"] = 2000
    got = altered(full_link)
    assert got["bandwidth_overcommitted_nodes"] == 1
    assert got["ports_unmet"] == 1


# ------------------------------------------------ the probe of the program
def test_the_probe_passes_here_and_names_a_program_blind_to_the_port(
        rule, monkeypatch):
    rule.require_static_ports_in_the_wave()
    from nomad_tpu.solver import tensorize
    real = tensorize.group_column_asks
    monkeypatch.setattr(
        tensorize, "group_column_asks",
        lambda tg: {k: v for k, v in real(tg).items()
                    if k[0] != tensorize.port_key(0)[0]})
    with pytest.raises(RuntimeError, match="static port in the wave"):
        rule.require_static_ports_in_the_wave()

