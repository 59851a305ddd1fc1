"""Jobs that reserve no static port, packed over 64 generator rows, and
the digest of every array of each `PackedBatch`: what
`tests/golden/ports_pack_parent.json` holds for commit 80bda71 (before
the wave knew a static port) and `test_ports_served.py` computes again.
Nothing here names anything that commit lacks."""
import copy
import dataclasses
import hashlib

import numpy as np

from nomad_tpu import mock, structs
from nomad_tpu.solver.tensorize import PlacementAsk, Tensorizer


def rows(n=64, devices=False):
    nodes = []
    for i in range(n):
        nd = mock.node(datacenter=f"dc{i % 4}")
        nd.name = f"node-{i}"
        nd.attributes["rack"] = f"r{i % 8}"
        nd.reserved_resources.cpu = 0
        nd.reserved_resources.memory_mb = 0
        nd.reserved_resources.disk_mb = 0
        nd.node_resources.cpu = 4000 + (i % 8) * 1000
        nd.node_resources.memory_mb = 8192 + (i % 4) * 4096
        nd.node_resources.disk_mb = 100_000
        nd.node_resources.networks[0].ip = f"10.0.0.{i}"
        if devices and i % 2 == 0:
            nd.node_resources.devices = [structs.NodeDeviceResource(
                vendor="google", type="tpu", name="v4",
                instances=[structs.NodeDevice(id=f"tpu-{i}-{k}",
                                              healthy=True)
                           for k in range(8)])]
        nd.compute_class()
        nodes.append(nd)
    return nodes


def resident(node, k, device_ids=(), ports=()):
    a = mock.alloc()
    a.id = f"resident-{node.name}-{k}"
    a.node_id = node.id
    tr = a.allocated_resources.tasks["web"]
    tr.cpu, tr.memory_mb = 200, 256
    tr.networks = [structs.NetworkResource(
        device="eth0", ip=node.node_resources.networks[0].ip, mbits=10,
        dynamic_ports=[structs.Port(f"p{j}", v)
                       for j, v in enumerate(ports)])] if ports else []
    tr.devices = [structs.AllocatedDeviceResource(
        vendor="google", type="tpu", name="v4",
        device_ids=list(device_ids))] if device_ids else []
    return a


def job(groups, count, cpu=400, mem=256, devices=0, dynamic=0,
        affinity=False):
    jb = mock.job()
    jb.id = jb.name = f"job-{groups}x{count}"
    jb.datacenters = [f"dc{i}" for i in range(4)]
    jb.constraints = []
    if affinity:
        jb.affinities = [structs.Affinity(ltarget="${attr.rack}",
                                          rtarget="r3", operand="=",
                                          weight=35)]
        jb.spreads = [structs.Spread(attribute="${node.datacenter}",
                                     weight=50)]
    base = jb.task_groups[0]
    jb.task_groups = []
    for g in range(groups):
        tg = copy.deepcopy(base)
        tg.name, tg.count, tg.constraints = f"g{g}", count, []
        res = tg.tasks[0].resources
        res.cpu, res.memory_mb = cpu + 150 * g, mem + 128 * g
        res.networks = [structs.NetworkResource(
            mbits=10, dynamic_ports=[structs.Port(f"d{j}", 0)
                                     for j in range(dynamic)])] \
            if dynamic else []
        res.devices = [structs.RequestedDevice(name="google/tpu/v4",
                                               count=devices)] \
            if devices else []
        tg.ephemeral_disk.size_mb = 300
        jb.task_groups.append(tg)
    return jb


CASES = {
    "c2": dict(job=dict(groups=1, count=64)),
    "c3": dict(job=dict(groups=4, count=16, affinity=True)),
    "c4": dict(job=dict(groups=1, count=16, devices=1), devices=True),
    "dynamic_only": dict(job=dict(groups=2, count=8, dynamic=2),
                         ports=True),
}


def pack(name):
    case = CASES[name]
    nodes = rows(devices=case.get("devices", False))
    allocs = {}
    for i, nd in enumerate(nodes):
        allocs[nd.id] = [
            resident(nd, k,
                     device_ids=[f"tpu-{i}-{k}"]
                     if case.get("devices") and i % 2 == 0 and k < 2
                     else (),
                     ports=(20000 + 2 * k, 20001 + 2 * k)
                     if case.get("ports") else ())
            for k in range(5)]
    jb = job(**case["job"])
    asks = [PlacementAsk(job=jb, tg=tg, count=tg.count)
            for tg in jb.task_groups]
    return Tensorizer().pack(nodes, asks, allocs)


def digest(pb) -> dict:
    out = {}
    for f in dataclasses.fields(pb):
        v = getattr(pb, f.name)
        if isinstance(v, np.ndarray):
            h = hashlib.sha256()
            h.update(str((v.dtype, v.shape)).encode())
            h.update(np.ascontiguousarray(v).tobytes())
            out[f.name] = h.hexdigest()[:16]
    return out


def digests() -> dict:
    return {name: digest(pack(name)) for name in CASES}
