"""`BENCHMARK.json` against the files it names: every configuration, cell
and per-layer metric is findable as `benchmark/run.py` finds it (by
name, under `benchmark/`), so that an entry nobody can run, or a file no
entry names, fails here, on the CPU, without git and without the chip.
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")

with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _f:
    BENCH = json.load(_f)

CONFIGS = [c["name"] for c in BENCH["configs"]]
CELLS = [w["name"] for w in BENCH["workloads"]]
PER_LAYER = [m["name"] for m in BENCH["per_layer"]]
END_TO_END = {m["name"]: m for m in BENCH["end_to_end"]}


@pytest.fixture(scope="module")
def cluster():
    """`benchmark/cluster.py` under its bare name, as the harness
    imports it (numpy and the standard library at module level)."""
    sys.path.insert(0, BENCH_DIR)
    import cluster
    yield cluster
    sys.path.remove(BENCH_DIR)


def reports(cell: str, metric: str) -> bool:
    m = END_TO_END[metric]
    return "workloads" not in m or cell in m["workloads"]


def by_name(section: str, name: str) -> dict:
    return next(e for e in BENCH[section] if e["name"] == name)


@pytest.mark.parametrize("name", CONFIGS)
def test_a_configuration_has_its_file_source_and_rules(cluster, name):
    c = by_name("configs", name)
    assert c["file"] == f"benchmark/configs/{name}.json"
    assert os.path.exists(os.path.join(ROOT, c["file"]))
    assert 1 <= len(c["source"]) <= 200 and "\n" not in c["source"]
    assert isinstance(c["reduced"], list)
    cfg = cluster.load_config(name)            # loads its rules too
    assert cfg["name"] == name and cfg["reduced"] == c["reduced"]
    assert [r.__name__ for r in cluster.rules_of(cfg)] == \
        [f"benchmark_rule_{r}" for r in cfg.get("rules", [])]
    assert any(w["config"] == name for w in BENCH["workloads"])
    # every limit says what it holds and carries its two readings
    for number, lim in cfg["correct"]["limits"].items():
        assert {"limit", "holds", "lower", "upper"} <= set(lim), number
        assert "not read yet" not in lim["lower"] + lim["upper"], number


@pytest.mark.parametrize("name", CELLS)
def test_a_cell_names_what_exists(name):
    w = by_name("workloads", name)
    assert w["config"] in CONFIGS
    assert name == f"{w['config']}.{w['traffic']}"
    assert os.path.exists(os.path.join(
        BENCH_DIR, "traffic", f"{w['traffic']}.json"))
    assert w["chips"] in (1, 4)
    assert 1 <= len(w["why"]) <= 200
    # it reports setup_s, another end-to-end metric, a per-layer metric
    assert reports(name, "setup_s")
    assert any(reports(name, m) for m in END_TO_END if m != "setup_s")
    assert any(name in m.get("workloads", CELLS)
               for m in BENCH["per_layer"])


@pytest.mark.parametrize("name", PER_LAYER)
def test_a_per_layer_metric_has_its_file_and_lists_cells_that_report(name):
    m = by_name("per_layer", name)
    path = os.path.join(BENCH_DIR, "layer_metrics", f"{name}.json")
    with open(path, encoding="utf-8") as f:
        spec = json.load(f)
    assert spec["name"] == name and spec["layer"] == m["layer"]
    assert "reducer" in spec
    assert m["moves"] in END_TO_END
    for cell in m.get("workloads", CELLS):
        assert cell in CELLS, (name, cell)
        assert reports(cell, m["moves"]), (name, cell, m["moves"])


def test_no_metric_file_without_an_entry():
    files = {f[:-len(".json")] for f in os.listdir(
        os.path.join(BENCH_DIR, "layer_metrics")) if f.endswith(".json")}
    assert files == set(PER_LAYER)
    assert len(set(PER_LAYER)) == len(PER_LAYER)
    assert len(set(CELLS)) == len(CELLS)
    assert len(set(CONFIGS)) == len(CONFIGS)


def test_the_device_deployment_and_its_cell_are_there(cluster):
    assert "c4-devices-10k" in CONFIGS
    cell = by_name("workloads", "c4-devices-10k.closed1")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("c4-devices-10k", "closed1", 1)
    for metric in ("placements_per_s", "reg_to_visible_p50_ms", "setup_s"):
        assert reports(cell["name"], metric)
    cfg = cluster.load_config("c4-devices-10k")
    assert cfg["rules"] == ["devices"] and cfg["reduced"] == []
    assert cfg["cluster"]["nodes"] == 10_000
    assert cfg["cluster"]["devices"] == {
        "name": "google/tpu/v4", "instances": 8, "every": 2}
    assert cfg["job"]["devices"] == {"name": "google/tpu/v4", "count": 1}
    assert cfg["job"]["count_per_group"] == 16
    assert cfg["resident"]["allocs"] == 50_000
    limits, controls = cfg["correct"]["limits"], cfg["correct"]["controls"]
    assert limits["device_overbooked"]["limit"] == 0
    assert limits["device_unmet"]["limit"] == 0
    assert "devices_unaccounted" in controls
    # the ceiling never asks for more instances than the cluster holds
    d = cfg["cluster"]["devices"]
    held = cfg["cluster"]["nodes"] // d["every"] * d["instances"]
    assert cluster.ceiling_jobs(cfg) * cluster.job_count(cfg) <= held
    # what the program adds for it is read by a metric the cell lists
    for name in ("device_assign_ms", "device_refused_per_solve",
                 "device_instances_per_solve", "plan_evaluate_ms",
                 "wave_loop_roofline", "waves_per_solve",
                 "device_ms_per_wave", "device_idle_share"):
        assert cell["name"] in by_name("per_layer", name)["workloads"]


@pytest.fixture(scope="module")
def layers(cluster):
    """`benchmark/layers.py` under its bare name, as `run.py` imports
    it (the `cluster` fixture holds `benchmark/` on the path)."""
    import layers
    return layers


#: ISSUE 31's three metrics, ISSUE 33's one and ISSUE 35's two: the
#: layer each names, an `Observed` that holds what it reads and what it
#: then says, and which key a program from before the change lacks
STORE_METRICS = {
    "plan_snapshot_ms": (
        "Plan apply", {"samples": {"span.plan.snapshot": (0.9, 300)}},
        3.0, None),
    "index_ids_copied_per_eval": (
        "Plan apply", {"counters": {"state.index.ids_copied": 48_000.0},
                       "harness": {"evals_completed": 320}},
        150.0, "counters"),
    "gc_full_collections": (
        "Interpreter", {"samples": {"span.gc.full": (2.4, 3)}},
        3.0, "samples"),
    # ISSUE 33's: Job trees the plan proposer walked, an eval
    "jobs_encoded_per_eval": (
        "Plan apply", {"counters": {"plan.jobs_encoded": 336.0},
                       "harness": {"evals_completed": 320}},
        1.05, "counters"),
    # ISSUE 35's: what the codec compiled in the window (a window that
    # compiled nothing reads 0, not nothing), what it could not dispatch
    "codec_classes_compiled": (
        "Plan apply", {"counters": {"codec.classes_compiled": 0.0}},
        0.0, "counters"),
    "codec_fallbacks_per_eval": (
        "Plan apply", {"counters": {"codec.fallback": 80.0},
                       "harness": {"evals_completed": 320}},
        0.25, "counters"),
}


@pytest.mark.parametrize("name", sorted(STORE_METRICS))
def test_the_store_metrics_read_what_is_there_and_nothing_otherwise(
        layers, name):
    layer, fields, reads, absent_before = STORE_METRICS[name]
    spec = layers.load_metric(name)
    entry = by_name("per_layer", name)
    assert spec["layer"] == entry["layer"] == layer
    # a layer the table already had, under the same letters
    assert layer in {m["layer"] for m in BENCH["per_layer"]
                     if m["name"] not in STORE_METRICS}
    assert entry["workloads"] == CELLS and entry["better"] == "lower"
    assert layers.read_metric(name, layers.Observed(**fields)) \
        == pytest.approx(reads)
    # a program without the counter or the sample: nothing, no raise
    assert layers.read_metric(name, layers.Observed()) is None
    if absent_before:
        fields = dict(fields, **{absent_before: {}})
        assert layers.read_metric(name, layers.Observed(**fields)) is None


@pytest.mark.parametrize("walks, reads", [(0.0, 0.0), (32.0, 0.1)])
def test_the_ready_view_metric_reads_the_counter_and_nothing_otherwise(
        layers, walks, reads):
    name = "ready_view_walks_per_eval"
    spec = layers.load_metric(name)
    entry = by_name("per_layer", name)
    # PERF.md's State store layer, first named in the table here
    assert spec["layer"] == entry["layer"] == "State store"
    assert entry["workloads"] == CELLS and entry["better"] == "lower"
    evals = {"evals_completed": 320}
    assert layers.read_metric(name, layers.Observed(
        counters={"state.ready_view.built": walks}, harness=evals)) \
        == pytest.approx(reads)
    # a program without the counter: nothing, no raise
    assert layers.read_metric(name, layers.Observed(harness=evals)) is None
    assert layers.read_metric(name, layers.Observed()) is None


def test_the_ports_deployment_and_its_cell_are_there(cluster):
    assert "c5-ports-10k" in CONFIGS
    cell = by_name("workloads", "c5-ports-10k.closed1")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("c5-ports-10k", "closed1", 1)
    for metric in ("placements_per_s", "reg_to_visible_p50_ms", "setup_s"):
        assert reports(cell["name"], metric)
    assert not reports(cell["name"], "reg_to_visible_p95_ms")
    cfg = cluster.load_config("c5-ports-10k")
    c2 = cluster.load_config("c2-binpack-10k")
    assert cfg["rules"] == ["ports"] and cfg["reduced"] == []
    assert cfg["cluster"] == c2["cluster"]
    assert {k: v for k, v in cfg["resident"].items() if k != "ports"} \
        == c2["resident"]
    assert cfg["ceiling"] == c2["ceiling"]
    assert cluster.job_count(cfg) == 64
    assert [g["count"] for g in cluster.job_groups(cfg)] == [4, 20, 20, 20]
    assert len(cfg["guarantees"]) == len(c2["guarantees"]) + 3
    limits, controls = cfg["correct"]["limits"], cfg["correct"]["controls"]
    for number in ("port_collisions", "ports_unmet",
                   "bandwidth_overcommitted_nodes"):
        assert limits[number]["limit"] == 0
    assert set(c2["correct"]["limits"]) <= set(limits)
    assert controls == c2["correct"]["controls"] + ["ports_unaccounted"]
    # the ceiling never asks for more nodes with 8080 free than start so
    r = cfg["resident"]["ports"]
    free = cfg["cluster"]["nodes"] * (r["static_every"] - 1) \
        // r["static_every"]
    assert cluster.ceiling_jobs(cfg) * 4 + 233 <= free
    # what the program adds for it is read by a metric the cell lists,
    # and the three device_* metrics are c4's alone
    for name in ("port_assign_ms", "port_refused_per_solve",
                 "ports_per_solve", "plan_port_refused_per_eval",
                 "plan_evaluate_ms", "wave_loop_roofline",
                 "waves_per_solve", "fixup_ms", "raft_apply_ms"):
        assert cell["name"] in by_name("per_layer", name)["workloads"]
    for name in ("device_assign_ms", "device_refused_per_solve",
                 "device_instances_per_solve"):
        assert cell["name"] not in by_name("per_layer", name)["workloads"]


#: ISSUE 34's four metrics: the layer each names, an `Observed` that
#: holds what it reads, and what it then says
PORT_METRICS = {
    "port_assign_ms": (
        "Host fixup", {"samples": {"span.solve.ports": (3.0, 500)},
                       "counters": {"solver.solve.tpu": 500.0}}, 6.0),
    "port_refused_per_solve": (
        "Host fixup", {"counters": {"solver.ports.refused": 0.0,
                                    "solver.solve.tpu": 500.0}}, 0.0),
    "ports_per_solve": (
        "Host fixup", {"counters": {"solver.ports.assigned": 54_000.0,
                                    "solver.solve.tpu": 500.0}}, 108.0),
    "plan_port_refused_per_eval": (
        "Plan apply", {"counters": {"plan.port_refused": 5.0},
                       "harness": {"evals_completed": 500}}, 0.01),
}


@pytest.mark.parametrize("name", sorted(PORT_METRICS))
def test_the_port_metrics_read_what_is_there_and_nothing_otherwise(
        layers, name):
    layer, fields, reads = PORT_METRICS[name]
    spec = layers.load_metric(name)
    entry = by_name("per_layer", name)
    assert spec["layer"] == entry["layer"] == layer
    assert entry["workloads"] == ["c5-ports-10k.closed1"]
    assert entry["moves"] == "placements_per_s"
    assert layers.read_metric(name, layers.Observed(**fields)) \
        == pytest.approx(reads)
    # a program without the counter or the sample (the parent): nothing
    assert layers.read_metric(name, layers.Observed()) is None
    bare = {k: v for k, v in fields.items() if k == "harness"}
    bare["counters"] = {"solver.solve.tpu": 500.0}
    assert layers.read_metric(name, layers.Observed(**bare)) is None
