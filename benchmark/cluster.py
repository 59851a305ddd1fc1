"""Cluster and job generators, and seeding a `Server` through raft.

A configuration file (`configs/<name>.json`) is turned first into PLAIN
data: numpy columns for the nodes, dicts for the job template.  The
plain reference and the checker read only that plain data.  The
program's own `Node` / `Job` / `Allocation` objects are built FROM the
plain data and are only ever handed to the system under test.

The generators are copies of `bench.py`'s `make_nodes`, `make_job` and
`R_VEC` at gen_seed 0 (later PRs may change bench.py and may not change
the yardstick).  What `--seed` changes is the order nodes are
registered in, every id, and nothing else: every seed gives the same
multiset of node sizes and the same job shape, so every seed is the
same amount of work.

What a node holds and a job asks for beside cpu, memory and disk comes
from the rules a configuration names (`"rules": ["devices"]` ->
`rules/devices.py`, found by name; README.md, "A rule"): each generator
here asks every loaded rule for its part.
"""
from __future__ import annotations

import copy
import importlib.util
import json
import math
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

#: sizes `--rehearse` swaps in (sandbox only, never a cell): a few
#: hundred nodes on the CPU backend
REHEARSE = {"nodes": 512, "resident_allocs": 2560}
#: and the traffic it cuts to fit them (the tiny cluster holds some 80 jobs)
REHEARSE_TRAFFIC = {"warmup_bursts": [1, 2, 4], "clients": 4, "senders": 2,
                    "wait_timeout_s": 30}


#: directories a rule's file is looked for in, in order; a later PR adds
#: `rules/<name>.py` and edits nothing
RULE_PATH = [os.path.join(HERE, "rules")]
_RULE_NAME = re.compile(r"^[A-Za-z0-9_]+$")
_loaded: Dict[str, object] = {}       # file path -> the rule's module


def load_rule(name: str):
    """The module of `rules/<name>.py`, loaded once a process."""
    if not _RULE_NAME.match(name):
        raise ValueError(f"rule name {name!r}: letters, digits and _ only")
    for d in RULE_PATH:
        path = os.path.join(d, f"{name}.py")
        if path in _loaded:
            return _loaded[path]
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"benchmark_rule_{name}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            _loaded[path] = mod
            return mod
    raise FileNotFoundError(
        f"the configuration names the rule {name!r} and no {name}.py is "
        f"in {RULE_PATH}")


def rules_of(cfg: dict) -> list:
    """The rule modules a configuration names under `rules`, in its
    order (none: the harness knows cpu, memory and disk alone)."""
    return [load_rule(name) for name in cfg.get("rules", [])]


def hooks(cfg: dict, name: str) -> list:
    """The function `name` of every rule of `cfg` that defines it."""
    return [getattr(r, name) for r in rules_of(cfg) if hasattr(r, name)]


def load_config(name: str, rehearse: bool = False) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    if rehearse:
        cfg["cluster"]["nodes"] = REHEARSE["nodes"]
        cfg["resident"]["allocs"] = REHEARSE["resident_allocs"]
    rules_of(cfg)         # a rule whose file is missing fails here
    return cfg


def hex_id(rng: np.random.Generator) -> str:
    h = rng.bytes(16).hex()
    return f"{h[:8]}-{h[8:12]}-{h[12:16]}-{h[16:20]}-{h[20:]}"


@dataclass
class PlainNodes:
    """Node columns, in registration order."""
    ids: List[str]
    names: List[str]
    cap: np.ndarray         # [n, 3] float64: cpu MHz, memory MB, disk MB
    cols: Dict[str, np.ndarray]   # target -> the column, as strings
    #: the rules' own columns (`node_columns`), by the names they chose
    extra: Dict[str, np.ndarray] = field(default_factory=dict)

    def __len__(self) -> int:
        return len(self.ids)

    def attr(self, target: str) -> np.ndarray:
        """The column a constraint / affinity / spread target names,
        as an array of strings (the reference compares lexically)."""
        try:
            return self.cols[target]
        except KeyError:
            raise KeyError(f"no node column for target {target!r}: add "
                           "it to the configuration's cluster.attributes")


DATACENTER = "${node.datacenter}"


def attribute_column(spec: dict, order: np.ndarray) -> np.ndarray:
    """One node column from its entry in `cluster.attributes`: row i of
    the generator has the value `prefix` + (i mod `modulus`), or `const`
    on every node."""
    if "const" in spec:
        return np.array([str(spec["const"])] * len(order))
    return np.array([f"{spec['prefix']}{int(v)}"
                     for v in order % int(spec["modulus"])])


def make_plain_nodes(cfg: dict, seed: int) -> PlainNodes:
    c = cfg["cluster"]
    n = int(c["nodes"])
    rng = np.random.default_rng([int(seed), 1])
    order = rng.permutation(n)          # row i of bench.make_nodes
    cpu = np.asarray(c["cpu_mhz"], np.float64)[order % len(c["cpu_mhz"])]
    mem = np.asarray(c["memory_mb"],
                     np.float64)[order % len(c["memory_mb"])]
    disk = np.full(n, float(c["disk_mb"]))
    extra: Dict[str, np.ndarray] = {}
    for node_columns in hooks(cfg, "node_columns"):
        extra.update(node_columns(cfg, order))
    return PlainNodes(
        ids=[hex_id(rng) for _ in range(n)],
        names=[f"node-{int(i)}" for i in order],
        cap=np.stack([cpu, mem, disk], axis=1),
        cols={target: attribute_column(spec, order)
              for target, spec in c["attributes"].items()},
        extra=extra)


def job_groups(cfg: dict, shape=None) -> List[dict]:
    """The job template's groups as plain dicts (name, count, cpu, mem,
    disk, and what the configuration's rules ask for beside them):
    `bench.make_job`'s shapes at gen_seed 0.  `shape` = (groups, count
    per group) cuts the template down for a warm-up job; the window's
    jobs are always the whole template."""
    j = cfg["job"]
    n_groups, count = shape or (int(j["groups"]),
                                int(j["count_per_group"]))
    groups = [{"name": f"g{g}", "count": int(count),
               "cpu": float(j["cpu_mhz"] + (g % j["shape_period"])
                            * j["cpu_step_mhz"]),
               "mem": float(j["memory_mb"] + (g % j["shape_period"])
                            * j["memory_step_mb"]),
               "disk": float(j["disk_mb"])}
              for g in range(int(n_groups))]
    for group_asks in hooks(cfg, "group_asks"):
        for g, group in enumerate(groups):
            group.update(group_asks(cfg, g, group))
    return groups


def job_count(cfg: dict, shape=None) -> int:
    return sum(g["count"] for g in job_groups(cfg, shape))


def leftover_shapes(cfg: dict, totals: List[int]) -> List[tuple]:
    """(groups, count per group) of the warm-up jobs that stand for a
    retry of an eval's undecided placements: every power-of-two number of
    groups up to the template's, with each of `totals` placements."""
    out = []
    g = 1
    while g <= int(cfg["job"]["groups"]):
        out.extend((g, t // g) for t in totals if t >= g)
        g *= 2
    return out


def resident_jobs(cfg: dict) -> List[int]:
    """Alloc count of each resident job: jobs of `allocs_per_job`, the
    last one holding the remainder."""
    r = cfg["resident"]
    total, per = int(r["allocs"]), int(r["allocs_per_job"])
    out = [per] * (total // per)
    if total % per:
        out.append(total % per)
    return out


def resident_node_index(cfg: dict) -> np.ndarray:
    """Node (registration order) of resident alloc k: round-robin, as
    `bench.resident_used0` lays them out."""
    return np.arange(int(cfg["resident"]["allocs"])) \
        % int(cfg["cluster"]["nodes"])


# ------------------------------------------------ program-side objects
def build_nodes(plain: PlainNodes, cfg: dict) -> list:
    """`bench.make_nodes` over the plain columns."""
    from nomad_tpu import mock
    mbits = int(cfg["cluster"]["network_mbits"])
    attrs = {t[len("${attr."):-1]: col for t, col in plain.cols.items()
             if t.startswith("${attr.")}
    dc = plain.cols[DATACENTER]
    build_node = hooks(cfg, "build_node")
    nodes = []
    for i in range(len(plain)):
        n = mock.node(datacenter=str(dc[i]))
        n.id = plain.ids[i]
        n.name = plain.names[i]
        n.reserved_resources.cpu = 0
        n.reserved_resources.memory_mb = 0
        n.reserved_resources.disk_mb = 0
        for key, col in attrs.items():
            n.attributes[key] = str(col[i])
        n.node_resources.cpu = int(plain.cap[i, 0])
        n.node_resources.memory_mb = int(plain.cap[i, 1])
        n.node_resources.disk_mb = int(plain.cap[i, 2])
        for net in n.node_resources.networks:
            net.mbits = mbits
        for build in build_node:
            build(n, plain, i, cfg)
        n.compute_class()
        nodes.append(n)
    return nodes


def _group(base, name, count, cpu, mem, disk):
    tg = copy.deepcopy(base)
    tg.name = name
    tg.count = count
    tg.constraints = []
    t = tg.tasks[0]
    # ports and devices stripped, as every bench config strips them: a
    # configuration that asks for some names the rule that puts them back
    t.resources.networks = []
    t.resources.cpu = int(cpu)
    t.resources.memory_mb = int(mem)
    t.resources.devices = []
    tg.ephemeral_disk.size_mb = int(disk)
    return tg


def build_job(cfg: dict, job_id: str, shape=None):
    """`bench.make_job` from the configuration's job template."""
    from nomad_tpu import mock
    from nomad_tpu.structs import Affinity, Constraint, Spread
    j = cfg["job"]
    job = mock.job()
    job.id = job.name = job_id
    job.datacenters = list(j["datacenters"])
    job.constraints = [Constraint(lt, rt, op)
                       for lt, op, rt in j["constraints"]]
    job.affinities = [Affinity(ltarget=lt, rtarget=rt, operand=op,
                               weight=w)
                      for lt, op, rt, w in j["affinities"]]
    job.spreads = [Spread(attribute=at, weight=w)
                   for at, w in j["spreads"]]
    base = job.task_groups[0]
    base.constraints = []
    build_group = hooks(cfg, "build_group")
    job.task_groups = []
    for g in job_groups(cfg, shape):
        tg = _group(base, g["name"], g["count"], g["cpu"], g["mem"],
                    g["disk"])
        for build in build_group:
            build(tg, g, cfg)
        job.task_groups.append(tg)
    return job


def _resident_job(cfg: dict, job_id: str, count: int):
    from nomad_tpu import mock
    r = cfg["resident"]
    job = mock.job()
    job.id = job.name = job_id
    job.datacenters = list(cfg["job"]["datacenters"])
    job.constraints = []
    base = job.task_groups[0]
    job.task_groups = [_group(base, "r", count, r["cpu_mhz"],
                              r["memory_mb"], r["disk_mb"])]
    return job


def seed_cluster(server, cfg: dict, plain: PlainNodes, seed: int,
                 on_node=None, chunk_allocs: int = 5000) -> dict:
    """Bring `server` to the configuration's resident state: every node
    through `Server.register_node`, every resident job as a
    `job_upsert` entry and its allocs as `plan_results_batch` entries of
    about `chunk_allocs` allocs, proposed through the same
    `raft.propose` that `Server._apply_plan` makes.  The allocs reach the
    store only through the FSM, as a deployment's would."""
    import time
    from nomad_tpu import structs
    from nomad_tpu.structs import (AllocatedResources,
                                   AllocatedSharedResources,
                                   AllocatedTaskResources, Allocation,
                                   PlanResult)
    from nomad_tpu.utils.codec import to_wire
    out = {}
    t0 = time.monotonic()
    for n in build_nodes(plain, cfg):
        server.register_node(n)
        if on_node is not None:
            on_node(n.id)
    out["register_nodes_s"] = time.monotonic() - t0

    t0 = time.monotonic()
    rng = np.random.default_rng([int(seed), 2])
    r = cfg["resident"]
    node_of = resident_node_index(cfg)
    now = time.time()
    template = to_wire(Allocation(
        id="", namespace=structs.DEFAULT_NAMESPACE, eval_id="", name="",
        job_id="", task_group="r", node_id="", node_name="",
        allocated_resources=AllocatedResources(
            tasks={"web": AllocatedTaskResources(
                cpu=int(r["cpu_mhz"]), memory_mb=int(r["memory_mb"]),
                networks=[])},
            shared=AllocatedSharedResources(disk_mb=int(r["disk_mb"]))),
        desired_status=structs.ALLOC_DESIRED_RUN,
        client_status=structs.ALLOC_CLIENT_RUNNING,
        create_time=now, modify_time=now))
    empty_result = to_wire(PlanResult())
    resident_alloc = hooks(cfg, "resident_alloc")
    k = 0
    items, in_chunk, entries = [], 0, 0

    def flush():
        nonlocal items, in_chunk, entries
        if items:
            server._propose("plan_results_batch", {"items": items})
            entries += 1
        items, in_chunk = [], 0

    for j, count in enumerate(resident_jobs(cfg)):
        job = _resident_job(cfg, f"resident-{j}", count)
        server._propose("job_upsert", {"job": to_wire(job)})
        stored = server.store.job_by_id(job.namespace, job.id)
        by_node: Dict[str, list] = {}
        eval_id = hex_id(rng)
        for a in range(count):
            ni = int(node_of[k])
            w = dict(template)
            w["id"] = hex_id(rng)
            w["eval_id"] = eval_id
            w["name"] = f"{job.id}.r[{a}]"
            w["job_id"] = job.id
            w["node_id"] = plain.ids[ni]
            w["node_name"] = plain.names[ni]
            for fill in resident_alloc:
                fill(w, k, cfg)
            by_node.setdefault(plain.ids[ni], []).append(w)
            k += 1
        result = dict(empty_result)
        result["node_allocation"] = by_node
        items.append({"result": result, "job": to_wire(stored)})
        in_chunk += count
        if in_chunk >= chunk_allocs:
            flush()
    flush()
    out["seed_resident_s"] = time.monotonic() - t0
    out["resident_jobs"] = j + 1
    out["resident_entries"] = entries
    out["resident_allocs"] = k
    return out


def ceiling_jobs(cfg: dict) -> int:
    """Jobs a run may register (warm-up and window together) before the
    cluster is too full to stand for the configuration: `bench.CONFIGS`'
    own n_evals, scaled with the cluster for `--rehearse`."""
    full = int(cfg["ceiling"]["jobs"])
    at = int(cfg["ceiling"]["at_nodes"])
    return max(8, math.floor(full * int(cfg["cluster"]["nodes"]) / at))
