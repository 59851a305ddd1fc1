"""Device asks: nodes that hold device instances, task groups that ask
for some, and the account of who holds which.

Data, from the configuration's file:
  cluster.devices   {"name": "google/tpu/v4", "instances": 8, "every": 2}
                    generator rows with i % every == 0 hold `instances`
                    healthy instances of that device, ids tpu-<i>-<k>
                    (`bench.make_nodes(devices=True)`); `name` is
                    <vendor>/<type>/<model>
  job.devices       {"name": "google/tpu/v4", "count": 1}: the one task
                    of every group asks for `count` instances of a device
                    that `name` matches: <vendor>/<type>/<model>,
                    <vendor>/<type> or <type>, a part left out matching
                    anything (structs.RequestedDevice.ID)

Semantics, the reference scheduler's (hashicorp/nomad `scheduler/`):
  feasible.go DeviceChecker   a node is feasible when it holds a device
                    that matches the ask with at least `count` healthy
                    instances
  device.go AssignDevice over structs.DeviceAccounter: it fits when
                    `count` of them are free; a placement takes `count`
                    free instances, and no instance has two holders
  rank.go           an ask without a device affinity adds no `devices`
                    term: scores stay bin-pack + job anti-affinity (+
                    node affinity and spread where the job has them)

Departures, all of them narrowings the data above cannot leave: one
device group a node, every instance healthy; no constraint or affinity
on the ask (so DeviceChecker's attribute checks and the rank's device
term have nothing to do); no alloc of a run stops, so an instance is
never handed back and the reference's account is a count per node.
WHICH free instances a placement takes is the program's to choose and is
not compared: the reference takes the lowest-numbered, and the numbers
below hold both to "nobody shares, nobody holds a stranger's".

Numbers (a configuration that names this rule lists both under
`correct.limits` with the limit 0, and `devices_unaccounted` under
`correct.controls`):
  device_overbooked   instance ids held by more than one live alloc
                      (each holder beyond the first), or by an alloc on a
                      node that does not own them, or of a device that
                      does not match the ask
  device_unmet        live allocs of the run's jobs that hold a number
                      of instances other than their group asked for
"""
from __future__ import annotations

import re
from typing import Tuple

import numpy as np

NUMBERS = ("device_overbooked", "device_unmet")
#: the placer never debits, so it hands the first instances out again
CONTROLS = {"devices_unaccounted": {"devices_debit": False}}

_INSTANCE = re.compile(r"^tpu-(\d+)-(\d+)$")


def id_tuple(name: str) -> Tuple[str, str, str]:
    """(vendor, type, model), "" for a part the name leaves out."""
    parts = name.split("/")
    if len(parts) == 1:
        return ("", parts[0], "")
    if len(parts) == 2:
        return (parts[0], parts[1], "")
    return (parts[0], parts[1], "/".join(parts[2:]))


def matches(ask_name: str, device_name: str) -> bool:
    return all(not p or p == d for p, d in
               zip(id_tuple(ask_name), id_tuple(device_name)))


def instance_id(row: int, k: int) -> str:
    return f"tpu-{int(row)}-{int(k)}"


# ------------------------------------------------------------ plain data
def node_columns(cfg: dict, order: np.ndarray) -> dict:
    d = cfg["cluster"]["devices"]
    return {"device_row": order.astype(np.int64),
            "device_instances": np.where(order % int(d["every"]) == 0,
                                         int(d["instances"]), 0)}


def group_asks(cfg: dict, g: int, group: dict) -> dict:
    d = cfg["job"]["devices"]
    return {"devices": {"name": str(d["name"]), "count": int(d["count"])}}


def feasible(cfg: dict, plain) -> np.ndarray:
    ask = cfg["job"]["devices"]
    if not matches(ask["name"], cfg["cluster"]["devices"]["name"]):
        return np.zeros(len(plain), bool)
    return plain.extra["device_instances"] >= int(ask["count"])


def planes(cfg: dict) -> int:
    """Capacity and usage of the one device pattern."""
    return 2


# ----------------------------------------------------------- the placer
def start(placer) -> None:
    placer.state["devices"] = {
        "used": np.zeros(len(placer.plain), np.int64), "base": None}


def begin_round(placer) -> None:
    st = placer.state["devices"]
    st["base"] = st["used"].copy() if placer.isolate else None


def begin_job(placer) -> None:
    st = placer.state["devices"]
    st["seen"] = st["used"] if st["base"] is None else st["base"].copy()


def fits(placer, group: dict) -> np.ndarray:
    seen = placer.state["devices"]["seen"]
    return seen + group["devices"]["count"] \
        <= placer.plain.extra["device_instances"]


def commit(placer, ni: int, group: dict) -> dict:
    st = placer.state["devices"]
    count = group["devices"]["count"]
    first = int(st["seen"][ni])
    row = placer.plain.extra["device_row"][ni]
    name = placer.cfg["cluster"]["devices"]["name"]
    if placer.rule_kw.get("devices_debit", True):
        st["seen"][ni] += count
        if st["seen"] is not st["used"]:
            st["used"][ni] += count
    return {"device_ids": [(name, instance_id(row, first + k))
                           for k in range(count)]}


# -------------------------------------------------------------- the rows
def alloc_row(alloc) -> dict:
    return {"device_ids": [
        (f"{d.vendor}/{d.type}/{d.name}", inst)
        for t in alloc.allocated_resources.tasks.values()
        for d in t.devices for inst in d.device_ids]}


def _ids(rows: dict) -> list:
    """What each row holds, as (device name, instance id) pairs; None
    for a row no one said anything of."""
    return rows.get("device_ids") or [None] * len(rows["job_id"])


def _held(rows: dict) -> np.ndarray:
    """Instances each row holds; kept on the rows, which `rows_fit` is
    asked about once a state of every plan."""
    if "device_held" not in rows:
        rows["device_held"] = np.array(
            [len(ids or ()) for ids in _ids(rows)], np.float64)
    return rows["device_held"]


def rows_fit(cfg: dict, plain, rows: dict, live: np.ndarray,
             group: dict) -> np.ndarray:
    held = np.bincount(rows["node"][live], weights=_held(rows)[live],
                       minlength=len(plain))
    return held + group["devices"]["count"] \
        <= plain.extra["device_instances"]


def numbers(cfg: dict, plain, rows: dict, sent: list, ref: dict) -> dict:
    own = cfg["cluster"]["devices"]["name"]
    ask = cfg["job"]["devices"]
    node = rows["node"]
    row_of, n_inst = plain.extra["device_row"], \
        plain.extra["device_instances"]
    holders: dict = {}
    overbooked = 0
    for k, ids in enumerate(_ids(rows)):
        ni = int(node[k])
        for dev, inst in ids or ():
            m = _INSTANCE.match(inst)
            if (ni < 0 or dev != own or m is None
                    or int(m.group(1)) != row_of[ni]
                    or int(m.group(2)) >= n_inst[ni]
                    or not matches(ask["name"], dev)):
                overbooked += 1
            else:
                holders[(ni, inst)] = holders.get((ni, inst), 0) + 1
    overbooked += sum(c - 1 for c in holders.values() if c > 1)
    asking = {jid for jid, _shape in sent}
    unmet = sum(1 for jid, ids in zip(rows["job_id"], _ids(rows))
                if jid in asking and len(ids or ()) != int(ask["count"]))
    return {"device_overbooked": int(overbooked),
            "device_unmet": int(unmet)}


# --------------------------------------------------- the program's objects
def build_node(node, plain, i: int, cfg: dict) -> None:
    n_inst = int(plain.extra["device_instances"][i])
    if not n_inst:
        return
    from nomad_tpu.structs import NodeDevice, NodeDeviceResource
    vendor, typ, model = id_tuple(cfg["cluster"]["devices"]["name"])
    row = plain.extra["device_row"][i]
    node.node_resources.devices = [NodeDeviceResource(
        vendor=vendor, type=typ, name=model,
        instances=[NodeDevice(id=instance_id(row, k), healthy=True)
                   for k in range(n_inst)])]


def build_group(tg, group: dict, cfg: dict) -> None:
    from nomad_tpu.structs import RequestedDevice
    d = group["devices"]
    tg.tasks[0].resources.devices = [
        RequestedDevice(name=d["name"], count=d["count"])]
