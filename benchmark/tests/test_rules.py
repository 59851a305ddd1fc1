"""Rules (`benchmark/rules/<name>.py`): what a configuration that names
none reads, the devices rule against its controls and through `Server`,
a second rule picked up from a new file alone, and what a rule file may
import.

(a) `rules_golden.json` holds digests of what `make_plain_nodes`,
`job_groups`, `constraint_mask`, `place_sequence`, `reference_rows` and
`compare` gave at the parent commit (664e26a, before any rule could be
loaded) on c2 and c3 at 1,024 nodes; `digests` below computed them there
(this file imported over the parent's modules) and computes them here.
"""
import ast
import glob
import hashlib
import json
import os
import textwrap

import numpy as np
import pytest

import bytes_models
import check
import cluster
import controls
import load
import reference
import run

HERE = os.path.dirname(os.path.abspath(__file__))
GOLDEN = os.path.join(HERE, "rules_golden.json")
SEEDS = [3, 2**31 + 3, 77]
CELL = "c2-binpack-10k.closed1"
DEVICE = "google/tpu/v4"


def small(name, nodes=1024):
    cfg = cluster.load_config(name)
    per_node = cfg["resident"]["allocs"] // cfg["cluster"]["nodes"]
    cfg["cluster"]["nodes"] = nodes
    cfg["resident"]["allocs"] = nodes * per_node
    return cfg


def sha(*parts) -> str:
    h = hashlib.sha256()
    for p in parts:
        h.update(p if isinstance(p, bytes) else
                 json.dumps(p, sort_keys=True).encode())
    return h.hexdigest()[:16]


def digests(name, seed, mask_of) -> dict:
    """`mask_of(cfg, plain)` is `reference.constraint_mask` under the
    signature of the commit that runs this."""
    cfg = small(name)
    plain = cluster.make_plain_nodes(cfg, seed)
    shapes = [None] * 10 + [(1, 4), (2, 8)]
    sent = [(f"job-{seed}-{i}", s) for i, s in enumerate(shapes)]
    placed = reference.place_sequence(cfg, plain, shapes, round_jobs=4,
                                      isolate=True)
    out = {
        "plain": sha(plain.ids, plain.names, plain.cap.tobytes(),
                     {t: c.tolist() for t, c in plain.cols.items()}),
        "groups": sha(cluster.job_groups(cfg),
                      cluster.job_groups(cfg, (2, 8))),
        "mask": sha(np.packbits(mask_of(cfg, plain)).tobytes()),
        "placed": sha([[int(r[0]), int(r[1]), int(r[2]), repr(r[3])]
                       for r in placed["rows"]], placed["placed"])}
    for label, kw in (("sound", {}), ("isolated", {"isolate": True}),
                      ("bfloat16", reference.CONTROLS["bfloat16"]()),
                      ("sampled", {"sample": 14})):
        rows = check.reference_rows(cfg, plain, sent, **kw)
        out[f"rows.{label}"] = sha(
            rows["job_id"], rows["group"], rows["node"].tobytes(),
            rows["res"].tobytes(), rows["create_index"].tobytes(),
            rows["score"].tobytes(), rows["job_index"])
        out[f"compare.{label}"] = {
            k: repr(v) for k, v in sorted(check.compare(
                cfg, plain, rows, sent, None, 0).items())}
    return out


# ------------------------------------------------ (a) no rule, no change
@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", ["c2-binpack-10k",
                                  "c3-affinity-spread-10k"])
def test_without_rules_everything_reads_as_at_the_parent(name, seed):
    with open(GOLDEN, encoding="utf-8") as f:
        want = json.load(f)[f"{name}/{seed}"]
    assert cluster.rules_of(cluster.load_config(name)) == []
    assert digests(name, seed, reference.constraint_mask) == want


def test_the_cells_bytes_are_the_planes_they_were():
    for name, planes in (("c3-affinity-spread-10k", 13),
                         ("c2-binpack-10k", 11)):
        cfg = cluster.load_config(name)
        assert bytes_models.least_solve_bytes(cfg, 1, 0) == \
            planes * 10_000 * 4


# ------------------------------------------------- (b) the devices rule
def with_devices(cfg, instances=8, every=2, count=1):
    """c2's file plus what a configuration that names the rule adds."""
    cfg["rules"] = ["devices"]
    cfg["cluster"]["devices"] = {"name": DEVICE, "instances": instances,
                                 "every": every}
    cfg["job"]["devices"] = {"name": "google/tpu", "count": count}
    for n in ("device_overbooked", "device_unmet"):
        cfg["correct"]["limits"][n] = {"limit": 0}
    cfg["correct"]["controls"] = cfg["correct"]["controls"] \
        + ["devices_unaccounted"]
    return cfg


@pytest.mark.parametrize("seed", SEEDS)
def test_devices_rule_against_its_controls(seed):
    cfg = with_devices(small("c2-binpack-10k"))
    check.validate(cfg)
    plain = cluster.make_plain_nodes(cfg, seed)
    # every seed the same multiset, and the mask the even generator rows
    assert sorted(plain.extra["device_instances"]) == [0] * 512 + [8] * 512
    even = np.array([int(n.split("-")[1]) % 2 == 0 for n in plain.names])
    assert (reference.constraint_mask(cfg, plain) == even).all()
    assert [g["devices"] for g in cluster.job_groups(cfg, (2, 4))] == \
        [{"name": "google/tpu", "count": 1}] * 2
    assert bytes_models.least_solve_bytes(cfg, 1, 0) == 13 * 1024 * 4

    res = controls.run_controls(cfg, seed, 48)
    value = lambda c, n: res[c]["compared"][n]["value"]       # noqa: E731
    assert res["sound"]["correct"], res["sound"]
    assert value("sound", "device_overbooked") == 0
    assert value("sound", "device_unmet") == 0
    assert value("sound", "choice_gap_p90") == 0
    # 48 jobs x 64 on 512 x 8 instances: the devices bind, not the cpu
    for control in ("devices_unaccounted", "isolated_round"):
        assert not res[control]["correct"]
        assert value(control, "device_overbooked") > 0
        assert value(control, "device_unmet") == 0
    # the c2 controls are caught by the numbers that caught them
    assert value("bfloat16", "score_mismatch_p99") > 6e-5
    assert value("sampled_14_nodes", "choice_gap_p90") > 0.03


def test_an_ask_no_device_matches_is_feasible_nowhere():
    cfg = with_devices(small("c2-binpack-10k", 64))
    cfg["job"]["devices"]["name"] = "nvidia/gpu"
    plain = cluster.make_plain_nodes(cfg, 1)
    assert not reference.constraint_mask(cfg, plain).any()


# ------------------------------------- what fails before anything is built
def test_a_missing_rule_file_fails_at_load(monkeypatch, tmp_path):
    src = os.path.join(cluster.HERE, "configs", "c2-binpack-10k.json")
    with open(src, encoding="utf-8") as f:
        cfg = json.load(f)
    cfg["rules"] = ["no_such_rule"]
    os.makedirs(tmp_path / "configs")
    with open(tmp_path / "configs" / "c9.json", "w", encoding="utf-8") as f:
        json.dump(cfg, f)
    monkeypatch.setattr(cluster, "HERE", str(tmp_path))
    with pytest.raises(FileNotFoundError, match="no_such_rule"):
        cluster.load_config("c9")
    with pytest.raises(ValueError, match="letters, digits"):
        cluster.load_rule("../check")


@pytest.mark.parametrize("where, name", [
    ("limits", "device_overbooked"), ("limits", "no_such_number"),
    ("controls", "devices_unaccounted"), ("controls", "no_such_control")])
def test_an_unknown_limit_or_control_fails_before_the_cluster_is_built(
        monkeypatch, where, name):
    cfg = cluster.load_config("c2-binpack-10k", rehearse=True)
    if where == "limits":
        cfg["correct"]["limits"][name] = {"limit": 0}
    else:
        cfg["correct"]["controls"].append(name)
    with pytest.raises(ValueError, match=name):
        check.validate(cfg)
    # and `run.run` stops there: no Server is ever made
    import nomad_tpu.server.server as server_mod
    monkeypatch.setattr(cluster, "load_config", lambda *a, **k: cfg)
    monkeypatch.setattr(server_mod, "Server", None)
    with pytest.raises(ValueError, match=name):
        run.run(run.parse_args(["--workload", CELL, "--seed", "1",
                                "--seconds", "1", "--rehearse"]))


# -------------------------------------- (c), (d) whole runs through Server
def drive(capsys, monkeypatch, cfg_of, seed, seconds="0.5"):
    """`run.run` at rehearsal size on c2's closed1 cell, with
    `cluster.load_config` handing back `cfg_of(c2's file at that size)`
    and one caller (the toy cluster holds some thirty jobs' instances)."""
    real = cluster.load_config
    monkeypatch.setattr(
        cluster, "load_config",
        lambda name, rehearse=False: cfg_of(real(name, rehearse)))
    for k, v in (("wait_timeout_s", 3), ("clients", 1),
                 ("warmup_bursts", [1])):
        monkeypatch.setitem(cluster.REHEARSE_TRAFFIC, k, v)
    a = run.parse_args(["--workload", CELL, "--seed", str(seed),
                        "--seconds", seconds, "--trace", "0",
                        "--rehearse"])
    seen = {}
    real_rows = check.rows_from_snapshot

    def rows_from_snapshot(cfg, snapshot, plain):
        seen["rows"], seen["plain"] = real_rows(cfg, snapshot, plain), plain
        return seen["rows"]
    monkeypatch.setattr(check, "rows_from_snapshot", rows_from_snapshot)
    assert run.run(a) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return line, seen


def arm_at_window(monkeypatch, flag):
    real = load.LoadGen.window

    def window(self, seconds):
        flag["on"] = True
        return real(self, seconds)
    monkeypatch.setattr(load.LoadGen, "window", window)


def devices_at_rehearsal(cfg):
    cfg = with_devices(cfg)
    # 256 nodes x 8 instances hold 32 jobs; over 30 the run says so
    cfg["ceiling"] = {"jobs": 30, "at_nodes": cfg["cluster"]["nodes"]}
    return cfg


def test_devices_through_server(capsys, monkeypatch):
    line, seen = drive(capsys, monkeypatch, devices_at_rehearsal,
                       2**31 + 31)
    assert line["correct"] is True, line["compared"]
    assert line["compared"]["device_overbooked"] == {"value": 0, "limit": 0}
    assert line["compared"]["device_unmet"] == {"value": 0, "limit": 0}
    rows, plain = seen["rows"], seen["plain"]
    mine = [k for k, j in enumerate(rows["job_id"])
            if j.startswith("job-")]
    assert len(mine) >= 64 * 5
    held = set()
    for k in mine:
        (dev, inst), = rows["device_ids"][k]
        row = plain.extra["device_row"][rows["node"][k]]
        assert dev == DEVICE and row % 2 == 0
        assert inst in {f"tpu-{row}-{i}" for i in range(8)}
        held.add(inst)
    assert len(held) == len(mine)          # one instance each, its own
    assert all(rows["device_ids"][k] == []
               for k in range(len(rows["job_id"])) if k not in set(mine))


def test_the_nodes_first_instances_handed_out_again(capsys, monkeypatch):
    """`Solver._assign_devices` broken inside the window: every ask gets
    the node's first instances, free or not."""
    from nomad_tpu.solver.solve import Solver
    from nomad_tpu.structs import AllocatedDeviceResource
    flag = {"on": False}
    arm_at_window(monkeypatch, flag)
    real = Solver._assign_devices

    def first_always(acct, node, req):
        if not flag["on"]:
            return real(acct, node, req)
        for dev in node.node_resources.devices:
            if req.matches(*dev.id_tuple()):
                return AllocatedDeviceResource(
                    *dev.id_tuple(), device_ids=[
                        i.id for i in dev.instances[:req.count]])
        return None
    monkeypatch.setattr(Solver, "_assign_devices",
                        staticmethod(first_always))
    line, _seen = drive(capsys, monkeypatch, devices_at_rehearsal,
                        2**31 + 32)
    assert line["correct"] is False
    c = line["compared"]
    reached_the_store = c["device_overbooked"]["value"] > 0
    refused_by_the_applier = c["failed"]["value"] > 0 \
        and c["jobs_off_count"]["value"] > 0
    assert reached_the_store or refused_by_the_applier
    # which it was, at this commit: the plan applier's own re-check
    # (`DeviceAccounter.add_allocs` reports the collision) refuses the
    # doubled instances, so the job never reaches its count
    print(f"doubled instances: reached_the_store={reached_the_store} "
          f"refused_by_the_applier={refused_by_the_applier}")
    assert refused_by_the_applier and not reached_the_store


QUARANTINE = '''
"""A throw-away rule: every `every`-th generator row is quarantined, no
alloc of the run's jobs may land there, and resident allocs are marked
as seeded."""
import numpy as np

NUMBERS = ("quarantine_breaches", "residents_unmarked")


def node_columns(cfg, order):
    return {"quarantined": order % cfg["cluster"]["quarantine_every"] == 0}


def feasible(cfg, plain):
    return ~plain.extra["quarantined"]


def planes(cfg):
    return 1


def alloc_row(alloc):
    return {"seeded": alloc.desired_description == "seeded"}


def numbers(cfg, plain, rows, sent, ref):
    mine = {jid for jid, _shape in sent}
    bad = sum(1 for j, ni in zip(rows["job_id"], rows["node"])
              if j in mine and ni >= 0 and plain.extra["quarantined"][ni])
    unmarked = sum(1 for j, s in zip(rows["job_id"], rows["seeded"])
                   if (j not in mine) != bool(s))
    return {"quarantine_breaches": bad, "residents_unmarked": unmarked}


def build_node(node, plain, i, cfg):
    node.attributes["quarantined"] = \\
        "yes" if plain.extra["quarantined"][i] else "no"


def build_group(tg, group, cfg):
    from nomad_tpu.structs import Constraint
    tg.constraints = [Constraint("${attr.quarantined}", "yes", "!=")]


def resident_alloc(wire, k, cfg):
    wire["desired_description"] = "seeded"
'''


def with_quarantine(cfg):
    cfg["rules"] = ["quarantine"]
    cfg["cluster"]["quarantine_every"] = 4
    for n in ("quarantine_breaches", "residents_unmarked"):
        cfg["correct"]["limits"][n] = {"limit": 0}
    return cfg


@pytest.fixture
def quarantine_rule(monkeypatch, tmp_path):
    """A rule file in a directory of its own on the loader's path: no
    file of the benchmark is edited."""
    (tmp_path / "quarantine.py").write_text(textwrap.dedent(QUARANTINE))
    monkeypatch.setattr(cluster, "RULE_PATH",
                        cluster.RULE_PATH + [str(tmp_path)])
    yield cluster.load_rule("quarantine")
    cluster._loaded.pop(str(tmp_path / "quarantine.py"), None)


def test_a_second_rule_is_a_new_file_and_nothing_else(
        capsys, monkeypatch, quarantine_rule):
    cfg = with_quarantine(small("c2-binpack-10k"))
    check.validate(cfg)
    plain = cluster.make_plain_nodes(cfg, 5)
    assert reference.constraint_mask(cfg, plain).sum() == 768
    assert bytes_models.least_solve_bytes(cfg, 1, 0) == 12 * 1024 * 4
    line, seen = drive(capsys, monkeypatch, with_quarantine, 2**31 + 33)
    assert line["correct"] is True, line["compared"]
    for n in ("quarantine_breaches", "residents_unmarked",
              "constraint_violations"):
        assert line["compared"][n] == {"value": 0, "limit": 0}
    assert sum(seen["rows"]["seeded"]) == 2560


def test_the_second_rules_mask_bites_on_a_run(capsys, monkeypatch,
                                              quarantine_rule):
    """The program is not told of the quarantine (no `build_group`), the
    reference is: the run is not correct by the mask and by the rule's
    own number."""
    monkeypatch.delattr(quarantine_rule, "build_group")
    line, _seen = drive(capsys, monkeypatch, with_quarantine, 2**31 + 34)
    assert line["correct"] is False
    assert line["compared"]["constraint_violations"]["value"] > 0
    assert line["compared"]["quarantine_breaches"]["value"] == \
        line["compared"]["constraint_violations"]["value"]


# ------------------------------------------------- (e) what a file imports
def module_level_imports(path):
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    names = []
    for node in tree.body:             # module level only
        if isinstance(node, ast.Import):
            names += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            names.append(node.module or "")
    return names


def all_imports(path):
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    names = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            names.append(node.module or "")
    return names


def test_rule_files_import_numpy_and_the_standard_library_at_the_top():
    import sys
    paths = sorted(glob.glob(os.path.join(cluster.HERE, "rules", "*.py")))
    assert [os.path.basename(p) for p in paths] == ["devices.py"]
    for path in paths:
        for name in module_level_imports(path):
            top = name.split(".")[0]
            assert top == "numpy" or top == "__future__" \
                or top in sys.stdlib_module_names, (path, name)


def test_the_reference_imports_nothing_of_the_program():
    for name in ("reference.py", "check.py", "bytes_models.py"):
        for imp in all_imports(os.path.join(cluster.HERE, name)):
            assert imp.split(".")[0] not in ("nomad_tpu", "bench", "jax"), \
                (name, imp)
