"""Ports: task groups that ask for a network (bandwidth, a static port,
dynamic ports), resident allocs that hold some, and the account of who
holds which port on which node.

Data, from the configuration's file:
  cluster.network_mbits   bandwidth of a node's one network
  resident.ports    {"mbits": 10, "dynamic": 2, "static": 8080,
                    "static_every": 4, "static_at": 1}: resident alloc k
                    holds one network of `mbits` with `dynamic` ports,
                    20000 + dynamic * (k // nodes) + j, so distinct on
                    its node and a function of k alone; the first
                    resident (k < nodes) of every node whose generator
                    row has row % static_every == static_at also holds
                    the static port
  job.ports.groups  one entry per group of the job template, in order:
                    {"name", "cpu", "mem", "mbits", "static": {label:
                    port}, "dynamic": [labels]} and, where the group's
                    count is not the template's `count_per_group`,
                    "count".  Group g of ANY shape takes entry g's name
                    and asks; only a group that still has the template's
                    count (the window's whole job) is cut to "count", so
                    a warm-up job keeps the (groups, count) it was given

Semantics, the reference scheduler's (hashicorp/nomad):
  structs/network.go NetworkIndex   per node: the ports taken on each
                    address and the bandwidth used on each device, from
                    the node's reserved ports and every live alloc
                    (SetNode, AddAllocs); AssignNetwork offers an ask the
                    first address whose bandwidth fits, whose static
                    ports are all free and which still has a free
                    dynamic port in 20000-32000 for each dynamic label
  scheduler/rank.go BinPackIterator offers each task's ask on every
                    candidate node; a node that cannot give it is
                    exhausted (`network: reserved port collision`,
                    `bandwidth exceeded`, `dynamic port selection
                    failed`) and the iterator goes on to the next node.
                    A network ask adds NO score term: scores stay
                    bin-pack + job anti-affinity, so score_mismatch_p99
                    and choice_gap_p90 read as in c2 with the port's mask
                    inside `fits` / `rows_fit`
  structs/funcs.go AllocsFit        the plan applier's re-check: a port
                    collision or over-committed bandwidth refuses the node

Departures, all of them narrowings the data above cannot leave: one
address a node (`build_node` gives node row r the address 10.x.y.z of r,
so that a resident's network, which is built from the wire form's
`node_name` alone, names its node's address); one network an alloc, on
the group's one task; no alloc of a run stops, so no port is handed
back and the account only grows.  WHICH dynamic port an alloc gets is the
program's to choose and is not compared (upstream draws 20 at random,
then scans; the reference here takes the lowest free): the numbers hold
both to the range and to "nobody shares".

Numbers (a configuration that names this rule lists all three under
`correct.limits` with the limit 0, and `ports_unaccounted` under
`correct.controls`):
  port_collisions   holders beyond the first of any (node, address,
                    port), residents included
  ports_unmet       live allocs of the run's jobs whose network is not
                    what their group asked for: the bandwidth, each
                    static label at its value, each dynamic label once
                    with a value in 20000-32000, on the address of the
                    node the alloc runs on
  bandwidth_overcommitted_nodes   nodes whose live allocs' mbits sum
                    above the node's network

Planes (`planes`): 5.  A solve must read, for every node, capacity,
reserved and usage of the bandwidth column (3, as BASE_PLANES counts
them for cpu, memory and disk) and capacity and usage of the one static
port the batch asks for (2, a counted resource of capacity 1 a node, as
the devices rule counts its pattern).  Dynamic ports are 12,001 a node
and never bind at these sizes: no plane.
"""
from __future__ import annotations

import re

import numpy as np

NUMBERS = ("port_collisions", "ports_unmet",
           "bandwidth_overcommitted_nodes")
#: the placer never debits a port: the static one is offered again on
#: the node that holds it, and the same dynamic values are handed out
CONTROLS = {"ports_unaccounted": {"ports_debit": False}}

MIN_DYNAMIC, MAX_DYNAMIC = 20000, 32000
_NODE_NAME = re.compile(r"^node-(\d+)$")


def address(row: int) -> str:
    """The one address of the node of generator row `row`."""
    row = int(row)
    return f"10.{row >> 16 & 255}.{row >> 8 & 255}.{row & 255}"


def _asks(spec: dict) -> dict:
    return {"mbits": int(spec["mbits"]),
            "static": {k: int(v) for k, v in spec["static"].items()},
            "dynamic": list(spec["dynamic"])}


def _static_ports(cfg: dict) -> list:
    """Every static port a group or a resident of the configuration
    holds."""
    out = {int(v) for s in cfg["job"]["ports"]["groups"]
           for v in s["static"].values()}
    out.add(int(cfg["resident"]["ports"]["static"]))
    return sorted(out)


def resident_ports(cfg: dict, k: int, row: int) -> dict:
    """What resident alloc k, on the node of generator row `row`, holds."""
    r = cfg["resident"]["ports"]
    i = k // int(cfg["cluster"]["nodes"])
    dyn = int(r["dynamic"])
    static = {}
    if i == 0 and row % int(r["static_every"]) == int(r["static_at"]):
        static["lb"] = int(r["static"])
    return {"ip": address(row), "mbits": int(r["mbits"]), "static": static,
            "dynamic": {f"p{j}": MIN_DYNAMIC + dyn * i + j
                        for j in range(dyn)}}


# ------------------------------------------------------------ plain data
def node_columns(cfg: dict, order: np.ndarray) -> dict:
    r = cfg["resident"]["ports"]
    return {"port_row": order.astype(np.int64),
            "port_resident_static":
                order % int(r["static_every"]) == int(r["static_at"])}


def group_asks(cfg: dict, g: int, group: dict) -> dict:
    spec = cfg["job"]["ports"]["groups"][g]
    out = {"name": str(spec["name"]), "cpu": float(spec["cpu"]),
           "mem": float(spec["mem"]), "ports": _asks(spec)}
    if "count" in spec \
            and group["count"] == int(cfg["job"]["count_per_group"]):
        out["count"] = int(spec["count"])
    return out


def planes(cfg: dict) -> int:
    """Bandwidth capacity, reserved and usage; the static port's column
    capacity and usage (module docstring)."""
    return 5


# ----------------------------------------------------------- the placer
def _resident_state(cfg: dict, plain) -> dict:
    """The account after the resident allocs alone."""
    n = len(plain)
    r = cfg["resident"]["ports"]
    per_node = np.bincount(
        np.arange(int(cfg["resident"]["allocs"])) % n, minlength=n)
    static = {p: np.zeros(n, bool) for p in _static_ports(cfg)}
    held = plain.extra["port_resident_static"] & (per_node > 0)
    static[int(r["static"])] |= held
    return {"static": static,
            "n_ports": per_node * int(r["dynamic"]) + held,
            "mbits": per_node * float(r["mbits"]),
            "next_dyn": MIN_DYNAMIC + per_node * int(r["dynamic"])}


def _copy(st: dict) -> dict:
    return {"static": {p: a.copy() for p, a in st["static"].items()},
            "n_ports": st["n_ports"].copy(), "mbits": st["mbits"].copy(),
            "next_dyn": st["next_dyn"].copy()}


def start(placer) -> None:
    placer.state["ports"] = {
        "used": _resident_state(placer.cfg, placer.plain), "base": None}


def begin_round(placer) -> None:
    st = placer.state["ports"]
    st["base"] = _copy(st["used"]) if placer.isolate else None


def begin_job(placer) -> None:
    st = placer.state["ports"]
    st["seen"] = st["used"] if st["base"] is None else _copy(st["base"])


def _fits(cfg: dict, static: dict, n_ports, mbits, asks: dict):
    ok = mbits + asks["mbits"] <= float(cfg["cluster"]["network_mbits"])
    for port in asks["static"].values():
        ok &= ~static[port]
    room = MAX_DYNAMIC - MIN_DYNAMIC + 1
    return ok & (n_ports + len(asks["static"]) + len(asks["dynamic"])
                 <= room)


def fits(placer, group: dict) -> np.ndarray:
    seen = placer.state["ports"]["seen"]
    return _fits(placer.cfg, seen["static"], seen["n_ports"],
                 seen["mbits"], group["ports"])


def commit(placer, ni: int, group: dict) -> dict:
    st = placer.state["ports"]
    seen, asks = st["seen"], group["ports"]
    first = int(seen["next_dyn"][ni])
    taken = {p for p, a in seen["static"].items() if a[ni]} \
        | set(asks["static"].values())
    dynamic, port = {}, first
    for label in asks["dynamic"]:
        while port in taken:
            port += 1
        dynamic[label] = port
        port += 1
    if placer.rule_kw.get("ports_debit", True):
        for acct in ([seen] if seen is st["used"] else [seen, st["used"]]):
            for p in asks["static"].values():
                acct["static"][p][ni] = True
            acct["n_ports"][ni] += len(asks["static"]) + len(dynamic)
            acct["mbits"][ni] += asks["mbits"]
            acct["next_dyn"][ni] = max(acct["next_dyn"][ni], port)
    return {"ports": {"ip": address(placer.plain.extra["port_row"][ni]),
                      "mbits": asks["mbits"],
                      "static": dict(asks["static"]), "dynamic": dynamic}}


# -------------------------------------------------------------- the rows
def alloc_row(alloc) -> dict:
    nets = [n for t in alloc.allocated_resources.tasks.values()
            for n in t.networks] \
        + list(alloc.allocated_resources.shared.networks)
    if not nets:
        return {"ports": {"ip": "", "mbits": 0, "static": {},
                          "dynamic": {}, "networks": 0}}
    return {"ports": {
        "ip": nets[0].ip, "mbits": sum(int(n.mbits) for n in nets),
        "static": {p.label: int(p.value) for n in nets
                   for p in n.reserved_ports},
        "dynamic": {p.label: int(p.value) for n in nets
                    for p in n.dynamic_ports},
        "networks": len(nets),
        "labels": sum(len(n.reserved_ports) + len(n.dynamic_ports)
                      for n in nets)}}


def _held(cfg: dict, plain, rows: dict) -> list:
    """What each row holds, as `alloc_row` gives it.  A resident's row
    of which nobody said anything (the reference's rows) holds what the
    layout gives the i-th resident of its node.  Kept on the rows."""
    if "ports_held" in rows:
        return rows["ports_held"]
    said = rows.get("ports") or [None] * len(rows["job_id"])
    n = len(plain)
    nth: dict = {}
    out = []
    for jid, ni, h in zip(rows["job_id"], rows["node"], said):
        if h is None and ni >= 0 and jid.startswith("resident-"):
            i = nth.get(int(ni), 0)
            nth[int(ni)] = i + 1
            h = resident_ports(cfg, int(ni) + i * n,
                               plain.extra["port_row"][ni])
        out.append(h or {"ip": "", "mbits": 0, "static": {},
                         "dynamic": {}})
    rows["ports_held"] = out
    rows["ports_mbits"] = np.array([h["mbits"] for h in out], np.float64)
    rows["ports_count"] = np.array(
        [len(h["static"]) + len(h["dynamic"]) for h in out], np.float64)
    for port in _static_ports(cfg):
        rows[f"ports_has_{port}"] = np.array(
            [port in h["static"].values() or port in h["dynamic"].values()
             for h in out], np.float64)
    return out


def rows_fit(cfg: dict, plain, rows: dict, live: np.ndarray,
             group: dict) -> np.ndarray:
    _held(cfg, plain, rows)
    n = len(plain)
    at = rows["node"][live]

    def per_node(key):
        return np.bincount(at, weights=rows[key][live], minlength=n)

    static = {p: per_node(f"ports_has_{p}") > 0
              for p in group["ports"]["static"].values()}
    return _fits(cfg, static, per_node("ports_count"),
                 per_node("ports_mbits"), group["ports"])


def numbers(cfg: dict, plain, rows: dict, sent: list, ref: dict) -> dict:
    held = _held(cfg, plain, rows)
    node = rows["node"]
    n = len(plain)
    holders: dict = {}
    for ni, h in zip(node, held):
        for port in list(h["static"].values()) \
                + list(h["dynamic"].values()):
            key = (int(ni), h["ip"], port)
            holders[key] = holders.get(key, 0) + 1
    ok = node >= 0
    mbits = np.bincount(node[ok], weights=rows["ports_mbits"][ok],
                        minlength=n)
    asking = {jid for jid, _shape in sent}
    specs = {s["name"]: _asks(s) for s in cfg["job"]["ports"]["groups"]}
    unmet = 0
    for jid, grp, ni, h in zip(rows["job_id"], rows["group"], node, held):
        if jid not in asking:
            continue
        ask = specs.get(grp)
        dyn = h["dynamic"]
        if (ask is None or ni < 0
                or h["ip"] != address(plain.extra["port_row"][ni])
                or h["mbits"] != ask["mbits"]
                or h["static"] != ask["static"]
                or sorted(dyn) != sorted(ask["dynamic"])
                or h.get("networks", 1) != 1
                or h.get("labels", len(h["static"]) + len(dyn))
                != len(ask["static"]) + len(ask["dynamic"])
                or not all(MIN_DYNAMIC <= v <= MAX_DYNAMIC
                           for v in dyn.values())):
            unmet += 1
    return {"port_collisions": int(sum(c - 1 for c in holders.values()
                                       if c > 1)),
            "ports_unmet": int(unmet),
            "bandwidth_overcommitted_nodes": int(
                (mbits > float(cfg["cluster"]["network_mbits"])).sum())}


# --------------------------------------------------- the program's objects
def require_static_ports_in_the_wave() -> None:
    """Raise unless the program places a static port on a node that has
    it free when more nodes rank above that node than a placement's
    fall-through candidates.  Six nodes, five of them fuller (so ranked
    first by bin-pack) and holding port 8080, one ask for 8080: a
    program whose wave does not know the port offers the five, its
    fixup finds them all taken and gives up.  Such a program cannot run
    a configuration of this rule: its jobs never reach their count (at
    rehearsal size the warm-up job of 1 group x 8 never did, PERF.md
    section 6, PR 34) and the harness would wait its whole warm-up
    patience for it; this says so in the first seconds of the run."""
    from nomad_tpu import mock, structs
    from nomad_tpu.solver.solve import _run_kernel
    from nomad_tpu.solver.tensorize import PlacementAsk, Tensorizer
    held = {"ip": "", "mbits": 10, "static": {"lb": 8080}, "dynamic": {}}
    nodes, by_node = [], {}
    for i in range(6):
        n = mock.node(datacenter="dc1")
        n.node_resources.networks[0].ip = address(i)
        nodes.append(n)
        if i < 5:
            by_node[n.id] = [structs.Allocation(
                id=f"holder-{i}", node_id=n.id, job_id="holders",
                task_group="r",
                allocated_resources=structs.AllocatedResources(
                    tasks={"web": structs.AllocatedTaskResources(
                        cpu=1000, memory_mb=2048, networks=[_network(
                            "eth0", dict(held, ip=address(i)))])}),
                desired_status=structs.ALLOC_DESIRED_RUN,
                client_status=structs.ALLOC_CLIENT_RUNNING)]
    job = mock.job()
    job.constraints = []
    tg = job.task_groups[0]
    tg.constraints = []
    tg.tasks[0].resources.devices = []
    tg.tasks[0].resources.networks = [_network("", held)]
    # the wave alone, on the host twin: no counter of the served path
    # moves (a `Solver.solve` here would count one solve off the device)
    pb = Tensorizer().pack(nodes, [PlacementAsk(job=job, tg=tg, count=1)],
                           by_node)
    res = _run_kernel(pb, host_mode="always")
    first = int(res.choice[0, 0]) if bool(res.choice_ok[0, 0]) else None
    if first != 5:
        raise RuntimeError(
            "this program does not place a static port in the wave (one "
            "ask for port 8080 over six nodes, the five best-ranked "
            f"holding it: the wave's first choice is node {first}, not "
            "the free node 5); the configuration's jobs would never "
            "reach their count on it (benchmark/rules/ports.py)")


def build_node(node, plain, i: int, cfg: dict) -> None:
    if i == 0:
        require_static_ports_in_the_wave()
    ip = address(plain.extra["port_row"][i])
    for net in node.node_resources.networks:
        net.ip = ip
        net.cidr = f"{ip}/32"


def _network(device: str, held: dict):
    from nomad_tpu.structs import NetworkResource, Port
    return NetworkResource(
        device=device, ip=held["ip"], mbits=held["mbits"],
        reserved_ports=[Port(label=k, value=v)
                        for k, v in held["static"].items()],
        dynamic_ports=[Port(label=k, value=v)
                       for k, v in held["dynamic"].items()])


def build_group(tg, group: dict, cfg: dict) -> None:
    asks = group["ports"]
    tg.tasks[0].resources.networks = [_network("", {
        "ip": "", "mbits": asks["mbits"], "static": asks["static"],
        "dynamic": {label: 0 for label in asks["dynamic"]}})]


def resident_alloc(wire: dict, k: int, cfg: dict) -> None:
    from nomad_tpu.utils.codec import to_wire
    row = int(_NODE_NAME.match(wire["node_name"]).group(1))
    net = to_wire(_network("eth0", resident_ports(cfg, k, row)))
    res = dict(wire["allocated_resources"])
    res["tasks"] = {name: dict(task, networks=[net])
                    for name, task in res["tasks"].items()}
    wire["allocated_resources"] = res
