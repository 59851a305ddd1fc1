"""The distinct rule (`benchmark/rules/distinct.py`) on c3's file at 1,024
nodes with the rule's keys: each limit held by the sound reference and
broken by `distinct_ignored` on every seed, a limit that many nodes share
held after every placement (`reference.Placer` once re-read a rule's fit
on the chosen node alone), what the numbers count, and the rule's
constraints on the program's `Job` through `build_group` and
`build_job`.

No test here drives the rule through `Server`: the program as it stands
places a group under a rack limit of 1 short of its count at rehearsal
size (PERF.md, section 7).
"""
import copy

import pytest

import bytes_models
import check
import cluster
import controls
import reference

SEEDS = [3, 2**31 + 3, 77]
NAME = "c3-affinity-spread-10k"
RACK, ZONE = "${attr.rack}", "${attr.zone}"
#: a job of 64 over c3's 15 feasible zones: binds (the sound reference
#: fills some zone to it in every job) and leaves room
ZONE_LIMIT = 8
JOBS = 24


def small(nodes=1024):
    cfg = cluster.load_config(NAME)
    cfg["cluster"]["nodes"] = nodes
    cfg["resident"]["allocs"] = nodes * 10
    return cfg


def with_distinct(cfg, distinct=None):
    """c3's file plus what a configuration that names the rule adds:
    group-level distinct_hosts and one alloc a rack, job-level at most
    ZONE_LIMIT a zone."""
    cfg["rules"] = ["distinct"]
    cfg["job"]["distinct"] = distinct if distinct is not None else [
        {"scope": "group", "operand": "distinct_hosts"},
        {"scope": "group", "operand": "distinct_property",
         "attribute": RACK, "limit": 1},
        {"scope": "job", "operand": "distinct_property",
         "attribute": ZONE, "limit": ZONE_LIMIT}]
    for n in ("distinct_hosts_shared", "distinct_property_over"):
        cfg["correct"]["limits"][n] = {"limit": 0}
    cfg["correct"]["controls"] = cfg["correct"]["controls"] \
        + ["distinct_ignored"]
    return cfg


@pytest.fixture(scope="module")
def rule():
    return cluster.load_rule("distinct")


def per_entry(rule, cfg, plain, rows, sent):
    """The rule's numbers with each entry of `job.distinct` alone."""
    out = []
    for e in cfg["job"]["distinct"]:
        one = copy.deepcopy(cfg)
        one["job"]["distinct"] = [e]
        out.append(sum(rule.numbers(one, plain, rows, sent, {}).values()))
    return out


def most_a_value(rows, sent, col, scope):
    """The most allocs of one job (and group, at group scope) on one
    value of `col`."""
    mine = {jid for jid, _s in sent}
    counts = {}
    for jid, grp, ni in zip(rows["job_id"], rows["group"], rows["node"]):
        if jid in mine:
            key = (jid, grp if scope == "group" else None, col[ni])
            counts[key] = counts.get(key, 0) + 1
    return max(counts.values())


# ---------------------------------------------- against its controls
@pytest.mark.parametrize("seed", SEEDS)
def test_distinct_rule_against_its_controls(seed, rule):
    cfg = with_distinct(small())
    check.validate(cfg)
    plain = cluster.make_plain_nodes(cfg, seed)
    # every node holds a rack and a zone: the mask is c3's
    assert (reference.constraint_mask(cfg, plain)
            == reference.constraint_mask(small(), plain)).all()
    # c3's 13 planes and a value index for the rack and for the zone
    assert bytes_models.least_solve_bytes(cfg, 1, 0) == 15 * 1024 * 4

    res = controls.run_controls(cfg, seed, JOBS)
    value = lambda c, n: res[c]["compared"][n]["value"]       # noqa: E731
    assert res["sound"]["correct"], res["sound"]
    for n in rule.NUMBERS:
        assert value("sound", n) == 0
    assert not res["distinct_ignored"]["correct"]
    assert value("distinct_ignored", "distinct_hosts_shared") > 0
    assert value("distinct_ignored", "distinct_property_over") > 0
    for control in cfg["correct"]["controls"]:
        assert not res[control]["correct"], control

    # each limit binds: the control breaks every one of them alone, and
    # the sound reference fills each property limit in some job
    sent = [(f"job-{seed}-{i}", None) for i in range(JOBS)]
    ignored = check.reference_rows(cfg, plain, sent, distinct_fits=False)
    assert all(n > 0 for n in per_entry(rule, cfg, plain, ignored, sent))
    sound = check.reference_rows(cfg, plain, sent)
    assert per_entry(rule, cfg, plain, sound, sent) == [0, 0, 0]
    assert most_a_value(sound, sent, plain.attr(RACK), "group") == 1
    assert most_a_value(sound, sent, plain.attr(ZONE), "job") == ZONE_LIMIT
    assert most_a_value(ignored, sent, plain.attr(ZONE), "job") > ZONE_LIMIT


# ------------------------------------------- the shared-fit regression
@pytest.mark.parametrize("seed", SEEDS)
def test_a_limit_many_nodes_share_holds_after_every_placement(seed):
    """A group of 16 under one alloc a rack lands on 16 racks.  A placer
    that re-reads a rule's fit on the chosen node alone leaves the
    rack's other nodes open, which hold the same sizes and score as the
    chosen node did: the second alloc goes to the first one's rack."""
    cfg = with_distinct(small(), [{"scope": "group", "operand":
                                   "distinct_property", "attribute": RACK}])
    plain = cluster.make_plain_nodes(cfg, seed)
    placed = reference.place_sequence(cfg, plain, [(1, 16)])
    racks = [plain.attr(RACK)[ni] for _j, _g, ni, _s, _h in placed["rows"]]
    assert placed["placed"] == [[16]]
    assert len(set(racks)) == 16, sorted(racks)


def test_a_node_without_the_attribute_is_infeasible():
    cfg = with_distinct(small(64), [{"scope": "job", "operand":
                                     "distinct_property",
                                     "attribute": "${attr.no_such}"}])
    plain = cluster.make_plain_nodes(cfg, 1)
    assert not reference.constraint_mask(cfg, plain).any()


# --------------------------------------------------------- the numbers
def test_the_numbers_count_allocs_beyond_each_limit(rule):
    cfg = with_distinct(small(256))
    plain = cluster.make_plain_nodes(cfg, 5)
    sent = [("job-5-0", None), ("job-5-1", None)]
    rows = check.reference_rows(cfg, plain, sent)
    assert rule.numbers(cfg, plain, rows, sent, {}) == {
        "distinct_hosts_shared": 0, "distinct_property_over": 0}
    mine = [k for k, j in enumerate(rows["job_id"]) if j == "job-5-0"]
    a, b = [k for k in mine if rows["group"][k] == "g0"][:2]
    moved = dict(rows, node=rows["node"].copy())
    moved["node"][b] = rows["node"][a]     # a second alloc of g0 there
    got = rule.numbers(cfg, plain, moved, sent, {})
    assert got["distinct_hosts_shared"] == 1
    # its rack holds two of g0 now, and its zone may hold one too many
    assert got["distinct_property_over"] in (1, 2)
    # a job the run did not register is not held to the job's limits
    assert rule.numbers(cfg, plain, moved, sent[1:], {}) == {
        "distinct_hosts_shared": 0, "distinct_property_over": 0}


# ------------------------------------------------ the program's objects
def test_build_job_runs_once_a_job_and_its_constraint_reaches_the_store(
        rule, monkeypatch):
    from nomad_tpu.server.server import Server
    from nomad_tpu.structs import Constraint
    calls = []
    real = rule.build_job

    def counted(job, cfg):
        calls.append(job.id)
        real(job, cfg)
    monkeypatch.setattr(rule, "build_job", counted)
    cfg = with_distinct(small(64))
    jobs = [cluster.build_job(cfg, f"job-9-{i}", shape)
            for i, shape in enumerate([None, (1, 4), (2, 8)])]
    assert calls == ["job-9-0", "job-9-1", "job-9-2"]
    zone = Constraint(ltarget=ZONE, rtarget=str(ZONE_LIMIT),
                      operand="distinct_property")
    group = [Constraint(operand="distinct_hosts"),
             Constraint(ltarget=RACK, rtarget="1",
                        operand="distinct_property")]
    server = Server()
    server.start()
    try:
        for job in jobs:
            server.register_job(job)
        for job in jobs:
            stored = server.store.job_by_id(job.namespace, job.id)
            # c3's two constraints, then the rule's one at job level
            assert stored.constraints[2:] == [zone]
            assert [tg.constraints for tg in stored.task_groups] == \
                [group] * len(job.task_groups)
    finally:
        server.stop()
    assert calls == ["job-9-0", "job-9-1", "job-9-2"]
    assert [len(j.task_groups) for j in jobs] == [4, 1, 2]
