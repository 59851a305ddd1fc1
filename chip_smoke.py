#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that the system starts on the chip.

Drives the two paths that matter, once, at the size the reference's
users run (BASELINE config 3: 10,000 heterogeneous nodes x 100,000
resident allocs, constraints + affinity + spread), on ONE TPU chip, in
ONE process, and checks what comes out with code that shares nothing
with the solver:

  1. served     Server().start(), 10,000 register_node, a heartbeat
                pump, the cluster filled to 100,000 allocs by
                registering jobs, then a few dozen fresh registrations
                (config-3 jobs, a job whose cpu/memory are not
                bf16-exact, a job that keeps its ports, a device job).
                From the store alone: counts, constraints, float64
                capacity, ports, device instances.  From the program's
                counters: every solve ran on the device, with a pallas
                mode that is not "off", no watchdog failover.
  2. kernel     solve_kernel vs the numpy twin below the approx_max_k
                threshold, pallas off / score / topk, compiled.
  3. resident   ResidentSolver at config-3 size: solve_stream,
                solve_stream_pipelined, a donating apply_delta, plane
                checksum, float64 capacity of the carried usage.
  4. health     the device health kernel equals its numpy twin.

`--chips 4` instead runs the node-sharded resident solver on four real
chips (102,400 nodes).

Exits non-zero, before building anything, when JAX finds no TPU.
`--allow-cpu --nodes N --allocs M` exists only so the same command can
be rehearsed at toy size and by a tier-1 test; its summary says
`platform: cpu` and is never a pass.

The last two lines of standard output are JSON objects.  First the
summary,
  {"ok": true, "device": {...}, "phases": {...}, ..., "claim": null}
which is also written to chiprun_out/chip_smoke.json (host-clock
durations in it are labelled set-up or wall; none is a rate), and then,
as the LAST line, the verdict in exactly the shape the driver reads:
  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import threading
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

#: BASELINE config 3 (bench.CONFIGS[3]); R_VEC is bench.R_VEC, the
#: resident alloc shape that config's 100,000 allocs carry
NODES, ALLOCS = 10_000, 100_000
#: asks chosen so that a bf16-rounded prior-usage sum over-commits: bf16
#: keeps 8 significant bits, 1251 rounds DOWN to 1248, and on a
#: 5,000 MHz node the fourth such alloc (4 x 1251 = 5004) then passes
#: a check it must fail
ODD_CPU, ODD_MEM = 1251, 1027


class SmokeFailure(Exception):
    """A check did not hold."""


def need(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


# ------------------------------------------------ independent checkers
def _node_value(node, target):
    """Resolve the constraint targets this script's jobs use.  Written
    here, not imported: the checker must not share code with the
    scheduler it checks."""
    if target == "${node.datacenter}":
        return node.datacenter
    if target.startswith("${attr.") and target.endswith("}"):
        return node.attributes.get(target[len("${attr."):-1])
    raise SmokeFailure(f"checker cannot resolve target {target!r}")


def _constraint_ok(node, c):
    val = _node_value(node, c.ltarget)
    if c.operand in ("=", "==", "is"):
        return val is not None and val == c.rtarget
    if c.operand in ("!=", "not"):
        return val != c.rtarget          # passes when the attr is missing
    if c.operand in (">=", ">", "<=", "<"):   # lexical, as the reference
        if val is None:
            return False
        return {">=": val >= c.rtarget, ">": val > c.rtarget,
                "<=": val <= c.rtarget, "<": val < c.rtarget}[c.operand]
    raise SmokeFailure(f"checker has no rule for operand {c.operand!r}")


def _alloc_networks(alloc):
    ar = alloc.allocated_resources
    for tr in ar.tasks.values():
        yield from tr.networks
    yield from ar.shared.networks


def check_store(snapshot, expected):
    """From the store alone: live allocs per (job, group) == asked, every
    alloc's node satisfies its job's constraints, per-node usage summed
    from scratch in float64 fits capacity on every dimension, no port
    and no device instance handed out twice on a node.  `expected` is
    {job_id: {group: count}} for every job ever registered."""
    import numpy as np
    nodes = {n.id: n for n in snapshot.nodes()}
    slot = {nid: i for i, nid in enumerate(nodes)}
    usage = np.zeros((len(nodes), 4), np.float64)
    ports = [set() for _ in nodes]
    instances = [set() for _ in nodes]
    live = {}
    n_live = n_ports = n_instances = 0
    for a in snapshot.allocs():
        if a.terminal_status():
            continue
        n_live += 1
        key = (a.job_id, a.task_group)
        live[key] = live.get(key, 0) + 1
        node = nodes.get(a.node_id)
        need(node is not None, f"alloc {a.id} on unknown node {a.node_id}")
        job = snapshot.job_by_id(a.namespace, a.job_id)
        need(job is not None, f"alloc {a.id} of unknown job {a.job_id}")
        tg = next(g for g in job.task_groups if g.name == a.task_group)
        need(node.datacenter in job.datacenters,
             f"alloc {a.id}: node dc {node.datacenter} not in "
             f"{job.datacenters}")
        cons = list(job.constraints) + list(tg.constraints)
        for t in tg.tasks:
            cons += list(t.constraints)
        for c in cons:
            need(_constraint_ok(node, c),
                 f"alloc {a.id} on node {node.name}: constraint "
                 f"{c.ltarget} {c.operand} {c.rtarget} violated")
        i = slot[a.node_id]
        ar = a.allocated_resources
        for tr in ar.tasks.values():
            usage[i, 0] += tr.cpu
            usage[i, 1] += tr.memory_mb
            for d in tr.devices:
                have = {inst.id for dev in node.node_resources.devices
                        for inst in dev.instances}
                for did in d.device_ids:
                    need(did in have, f"alloc {a.id}: device instance "
                                      f"{did} not on node {node.name}")
                    need(did not in instances[i],
                         f"device instance {did} handed out twice on "
                         f"node {node.name}")
                    instances[i].add(did)
                    n_instances += 1
        usage[i, 2] += ar.shared.disk_mb
        for net in _alloc_networks(a):
            usage[i, 3] += net.mbits
            for p in list(net.reserved_ports) + list(net.dynamic_ports):
                need(p.value > 0, f"alloc {a.id}: port {p.label} unset")
                need((net.ip, p.value) not in ports[i],
                     f"port {net.ip}:{p.value} handed out twice on node "
                     f"{node.name}")
                ports[i].add((net.ip, p.value))
                n_ports += 1
    for nid, i in slot.items():
        n = nodes[nid]
        nr, rr = n.node_resources, n.reserved_resources
        cap = (nr.cpu - rr.cpu, nr.memory_mb - rr.memory_mb,
               nr.disk_mb - rr.disk_mb,
               sum(net.mbits for net in nr.networks))
        for d, name in enumerate(("cpu", "memory", "disk", "network")):
            need(usage[i, d] <= cap[d],
                 f"node {n.name} over-committed on {name}: "
                 f"{usage[i, d]} > {cap[d]}")
    for job_id, groups in expected.items():
        for g, count in groups.items():
            got = live.get((job_id, g), 0)
            need(got == count, f"job {job_id} group {g}: {got} live "
                               f"allocs, asked {count}")
    need(sum(live.values()) == sum(sum(g.values())
                                   for g in expected.values()),
         "live allocs exist for jobs this run never registered")
    return {"live_allocs": n_live, "ports_checked": n_ports,
            "device_instances_checked": n_instances}


def check_stream(nodes, template, asks_by_batch, batches, choice, status,
                 used_before, used_after):
    """Resident-stream validity from the packed results: every committed
    placement sits on a real node that satisfies its job's constraints,
    and the carried usage equals the starting usage plus the committed
    asks, recomputed in float64, and fits capacity on every dimension.
    Returns (committed, retry, failed)."""
    import numpy as np
    from nomad_tpu.solver.resident import (STATUS_COMMITTED, STATUS_FAILED,
                                           STATUS_RETRY)
    n_real = template.n_real
    add = np.zeros(used_before.shape, np.float64)
    committed = retry = failed = 0
    for b, pb in enumerate(batches):
        st = status[b][:pb.n_place]
        committed += int((st == STATUS_COMMITTED).sum())
        retry += int((st == STATUS_RETRY).sum())
        failed += int((st == STATUS_FAILED).sum())
        for p in np.nonzero(st == STATUS_COMMITTED)[0]:
            ni = int(choice[b][p, 0])
            g = int(pb.p_ask[p])
            need(0 <= ni < n_real,
                 f"batch {b} placement {p}: node slot {ni} is padding")
            ask = asks_by_batch[b][g]
            for c in ask.job.constraints:
                need(_constraint_ok(nodes[ni], c),
                     f"batch {b} placement {p}: node {nodes[ni].name} "
                     f"violates {c.ltarget} {c.operand} {c.rtarget}")
            add[ni] += pb.ask_res[g].astype(np.float64)
    want = used_before.astype(np.float64) + add
    need(np.array_equal(want, used_after.astype(np.float64)),
         "carried usage != starting usage + committed asks (float64): "
         f"max |diff| {np.abs(want - used_after).max()}")
    over = used_after.astype(np.float64) > template.avail.astype(np.float64)
    need(not over[:n_real].any(),
         f"{int(over[:n_real].any(axis=1).sum())} nodes over-committed "
         "in the carried usage")
    return committed, retry, failed


# ------------------------------------------------------------- phases
class HeartbeatPump(threading.Thread):
    """What a deployment's clients do: heartbeat every node well inside
    its TTL.  Client-less nodes otherwise expire (the TTL is
    rate-scaled: 10 s at toy size, ~200 s at 10,000 nodes), go down,
    and flood the broker with node-update evals."""

    def __init__(self, server, node_ids, sweep_s):
        super().__init__(daemon=True, name="heartbeat-pump")
        self.server, self.node_ids, self.sweep_s = server, node_ids, sweep_s
        self.stop_evt = threading.Event()
        self.sweeps = 0
        self.unknown = 0

    def run(self):
        while not self.stop_evt.is_set():
            ids = list(self.node_ids)      # grows while nodes register
            chunk = max(1, len(ids) // 50)
            for i in range(0, max(len(ids), 1), chunk):
                for nid in ids[i:i + chunk]:
                    if self.server.node_heartbeat(nid) is None:
                        self.unknown += 1
                if self.stop_evt.wait(self.sweep_s / 50):
                    return
            self.sweeps += 1


def wait_quiescent(server, timeout_s, what):
    """Poll snapshots until every eval is terminal and the broker holds
    nothing, twice in a row.  Returns the last snapshot."""
    from nomad_tpu.structs import (EVAL_STATUS_CANCELLED,
                                   EVAL_STATUS_COMPLETE,
                                   EVAL_STATUS_FAILED)
    terminal = (EVAL_STATUS_COMPLETE, EVAL_STATUS_FAILED,
                EVAL_STATUS_CANCELLED)
    deadline = time.monotonic() + timeout_s
    quiet = 0
    said = time.monotonic()
    while True:
        st = server.broker.stats()
        snap = server.store.snapshot()
        open_evals = {}
        for e in snap.evals():
            if e.status not in terminal:
                open_evals[e.status] = open_evals.get(e.status, 0) + 1
        busy = (st["total_ready"] + st["total_unacked"]
                + st["total_waiting"] + st["total_blocked"])
        quiet = quiet + 1 if not open_evals and not busy else 0
        if quiet >= 2:
            return snap
        if time.monotonic() >= deadline:
            stuck = sorted({e.job_id for e in snap.evals()
                            if e.status not in terminal})[:8]
            why = [(e.job_id, {g: (m.nodes_evaluated, m.nodes_filtered,
                                   m.nodes_exhausted,
                                   dict(m.dimension_exhausted))
                               for g, m in e.failed_tg_allocs.items()})
                   for e in snap.evals() if e.failed_tg_allocs][:4]
            raise SmokeFailure(
                f"{what}: not quiescent after {timeout_s:.0f}s — open "
                f"evals {open_evals} (jobs {stuck}), failed placements "
                f"{why}, broker {st}")
        if time.monotonic() - said > 15.0:
            said = time.monotonic()
            print(f"  {what}: {sum(1 for _ in snap.allocs())} allocs, "
                  f"open evals {open_evals}, ready {st['total_ready']} "
                  f"unacked {st['total_unacked']}", flush=True)
        time.sleep(0.5)


def phase_served(a, watch):
    import bench
    from nomad_tpu.server.server import Server
    from nomad_tpu.solver.solve import BROWNOUT_MAX_WAVES
    from nomad_tpu.solver.watchdog import global_watchdog
    from nomad_tpu.structs import NetworkResource, Port
    from nomad_tpu.utils.metrics import global_metrics

    need(not global_watchdog.enabled,
         "the solve watchdog must be at its default (off)")
    out = {}
    t0 = time.monotonic()
    nodes = bench.make_nodes(a.nodes, devices=True, gen_seed=a.seed)
    server = Server()
    server.start()
    pump = None
    try:
        # the TTL a node is granted grows with the cluster
        # (heartbeat.rate_scaled_interval): sweep well inside it, and
        # start sweeping while nodes still register — the first nodes
        # get the 10 s floor
        hb = server.heartbeater
        ids = []
        pump = HeartbeatPump(
            server, ids, sweep_s=min(20.0, max(
                a.nodes / hb.max_rate, hb.min_ttl) / 4))
        pump.start()
        for n in nodes:
            server.register_node(n)
            ids.append(n.id)
        out["setup_register_nodes_wall_s"] = round(time.monotonic() - t0, 1)

        expected = {}

        def register(job):
            expected[job.id] = {tg.name: tg.count
                                for tg in job.task_groups}
            server.register_job(job)

        # ---- fill: config-3 jobs (constraints + affinity + spread, four
        # groups) carrying the resident alloc shape, registered all at
        # once — the main path under load
        per_job = max(4, min(a.fill_count, a.allocs) // 4 * 4)
        n_fill = a.allocs // per_job
        c0 = watch.snapshot()
        t1 = time.monotonic()
        for i in range(n_fill):
            job = bench.make_job(3, i, per_job, gen_seed=a.seed)
            for tg in job.task_groups:
                res = tg.tasks[0].resources
                res.cpu, res.memory_mb = (int(v) for v in bench.R_VEC[:2])
            register(job)
        wait_quiescent(server, a.timeout, "fill")
        out["fill_jobs"] = n_fill
        out["fill_allocs"] = n_fill * per_job
        out["fill_wall_s"] = round(time.monotonic() - t1, 1)
        out["fill_compiles"] = watch.diff(c0, watch.snapshot())

        # ---- the checked registrations, on the filled cluster
        c1 = watch.snapshot()
        m0 = global_metrics.dump()["counters"]
        t2 = time.monotonic()
        for i in range(a.fresh_jobs):
            register(bench.make_job(3, 100_000 + i, 64, gen_seed=a.seed))
        # one group, no constraints (bench config 2's shape), asks that
        # are not bf16-exact, enough of them to stack on shared nodes
        odd = bench.make_job(2, 200_000, max(8, a.nodes // 5))
        res = odd.task_groups[0].tasks[0].resources
        res.cpu, res.memory_mb = ODD_CPU, ODD_MEM
        register(odd)
        # ports through NetworkIndex (every bench config strips them):
        # dynamic ports at a count that stacks several allocs per node,
        # and a static port at a count inside one candidate window — the
        # solve cannot see ports, so a static port asked more often than
        # the window is wide wraps onto nodes that already hold it and
        # the leftover placements block (ROADMAP R2)
        ports = bench.make_job(2, 200_001, max(4, a.nodes // 50))
        web = ports.task_groups[0]
        db = copy.deepcopy(web)
        db.name, db.count = "db", 8
        web.tasks[0].resources.networks = [NetworkResource(
            mbits=50, dynamic_ports=[Port(label="http"),
                                     Port(label="admin")])]
        db.tasks[0].resources.networks = [NetworkResource(
            mbits=10, reserved_ports=[Port(label="db", value=5432)])]
        ports.task_groups.append(db)
        register(ports)
        register(bench.make_job(4, 200_002, 16))      # one TPU each
        snap = wait_quiescent(server, a.timeout, "fresh registrations")
        out["fresh_jobs"] = a.fresh_jobs + 3
        out["fresh_wall_s"] = round(time.monotonic() - t2, 1)
        out["fresh_compiles"] = watch.diff(c1, watch.snapshot())
        out["heartbeat_sweeps"] = pump.sweeps
        need(pump.unknown == 0, "heartbeats hit unknown nodes")
        down = sum(1 for n in snap.nodes() if not n.ready())
        need(down == 0, f"{down} nodes are not ready at the end")

        out.update(check_store(snap, expected))
        out["evals"] = sum(1 for _ in snap.evals())

        # ---- the program's own counters
        m1 = global_metrics.dump()["counters"]
        solves = {k[len("solver.solve."):]: int(v) for k, v in m1.items()
                  if k.startswith("solver.solve.")}
        pallas = {k[len("solver.pallas."):]: int(v) for k, v in m1.items()
                  if k.startswith("solver.pallas.")}
        out["solves_by_platform"] = solves
        out["pallas_modes"] = pallas
        out["waves"] = int(m1.get("solver.waves", 0))
        out["rescore_waves"] = int(m1.get("solver.rescore_waves", 0))
        out["degraded_solves"] = int(m1.get("solver.degraded", 0))
        out["brownout_max_waves"] = BROWNOUT_MAX_WAVES
        out["brownout_active"] = server.serving.admission.brownout_active()
        for k in ("watchdog.host_failover", "watchdog.host_quarantine"):
            out[k] = int(m1.get(k, 0))
            need(out[k] == 0, f"{k} = {out[k]}: a solve left the device")
        need(sum(solves.values()) > 0, "no solve was counted")
        if a.nodes >= 4096:
            # at this size prefer_host can never apply: every solve
            # must have answered from the device JAX reports
            need(set(solves) == {a.platform},
                 f"solves answered from {solves}, want only "
                 f"{a.platform}")
            if a.platform == "tpu":
                need("off" not in pallas and pallas,
                     f"pallas resolved to {pallas} on the served shapes")
        out["fresh_solves"] = int(
            sum(v for k, v in m1.items() if k.startswith("solver.solve."))
            - sum(v for k, v in m0.items()
                  if k.startswith("solver.solve.")))
    finally:
        if pump is not None:
            pump.stop_evt.set()
            pump.join(timeout=5.0)
        server.stop()
    return out


def phase_kernel(a, watch):
    """solve_kernel against its numpy twin where the kernel is exact
    (padded nodes < 4096: lax.top_k, not approx_max_k).  The three
    pallas modes run the same arithmetic on the same device and must
    agree with each other bit for bit.  Against numpy the score may
    differ in its last bits — the bin-pack term is 10 ** free, computed
    by the chip's own exp/log — so a near-tie can resolve to another
    node: what must hold is the same feasibility mask and counters, the
    same number placed, every placement valid, and chosen-node scores
    within SCORE_TOL.  SCORE_TOL = 1e-4: scores are O(1) averages of
    terms bounded by 1, f32 pow is good to a few ulp (~1e-6 relative on
    a value <= 10), and the normalisation divides by >= 1, so 1e-4
    leaves two orders of margin while still catching a wrong term (the
    smallest term weight in these batches moves a score by > 1e-2)."""
    import numpy as np
    from nomad_tpu import mock
    from nomad_tpu.solver.host import host_solve_kernel
    from nomad_tpu.solver.kernel import solve_kernel
    from nomad_tpu.solver.solve import _kernel_args
    from nomad_tpu.solver.tensorize import R_CPU, R_MEM

    SCORE_TOL = 1e-4
    out = {"score_tol": SCORE_TOL, "cases": []}
    n_nodes = min(2048, a.nodes)
    # (count, stack_commit): fan-out, then every placement of the group
    # stacked on its best node — forced same-node contention
    for count, stack in ((min(512, n_nodes // 2), False),
                         (min(96, n_nodes // 4), True)):
        pb = mock.rich_solve_batch(n_nodes, count, seed_ix=a.seed)
        pb.ask_res[:pb.n_asks, R_CPU] = ODD_CPU
        pb.ask_res[:pb.n_asks, R_MEM] = ODD_MEM
        need(pb.avail.shape[0] < 4096, "kernel phase must stay exact")
        args = _kernel_args(pb)
        kw = dict(has_spread=True, stack_commit=stack)
        host = host_solve_kernel(*args, 0, **kw)
        dev = {m: solve_kernel(*args, 0, pallas_mode=m, **kw)
               for m in ("off", "score", "topk")}
        K = pb.n_place
        for m in ("score", "topk"):
            for f in ("choice", "choice_ok", "score", "n_feasible",
                      "n_exhausted", "dim_exhausted", "feas",
                      "cons_filtered", "used_final", "unfinished"):
                need(np.array_equal(np.asarray(getattr(dev[m], f)),
                                    np.asarray(getattr(dev["off"], f))),
                     f"pallas {m} != off on {f} (count={count}, "
                     f"stack={stack}): same device, same arithmetic")
        d = dev["off"]
        need(np.array_equal(np.asarray(d.feas), host.feas),
             "feasibility mask differs from the host twin")
        need(np.array_equal(np.asarray(d.cons_filtered),
                            host.cons_filtered),
             "constraint-filter counters differ from the host twin")
        ok_d = np.asarray(d.choice_ok)[:K, 0]
        ok_h = host.choice_ok[:K, 0]
        need(int(ok_d.sum()) == int(ok_h.sum()),
             f"placed {int(ok_d.sum())} on device, {int(ok_h.sum())} on "
             "the host twin")
        ch_d = np.asarray(d.choice)[:K, 0]
        feas = host.feas
        for p in np.nonzero(ok_d)[0]:
            need(feas[pb.p_ask[p], ch_d[p]],
                 f"placement {p} on infeasible node {ch_d[p]}")
        used = np.asarray(d.used_final).astype(np.float64)
        want = pb.used0.astype(np.float64)
        np.add.at(want, ch_d[ok_d],
                  pb.ask_res[pb.p_ask[:K][ok_d]].astype(np.float64))
        need(np.array_equal(want, used),
             "used_final != used0 + committed asks in float64")
        need((used <= pb.avail.astype(np.float64))[:pb.n_real].all(),
             "kernel over-committed a node")
        both = ok_d & ok_h
        sc_d = np.asarray(d.score)[:K, 0][both]
        sc_h = host.score[:K, 0][both]
        # per-placement scores are comparable even when the choice
        # differs: rank r of a group takes the group's r-th best node
        gap = float(np.abs(np.sort(sc_d) - np.sort(sc_h)).max()) \
            if both.any() else 0.0
        need(gap <= SCORE_TOL,
             f"chosen-node scores differ from the host twin by {gap}")
        out["cases"].append({
            "nodes": n_nodes, "count": count, "stack_commit": stack,
            "placed": int(ok_d.sum()),
            "differing_choices": int((ch_d[both]
                                      != host.choice[:K, 0][both]).sum()),
            "max_score_gap": gap, "waves": int(np.asarray(d.n_waves))})
    out["differing_choices"] = sum(c["differing_choices"]
                                   for c in out["cases"])
    return out


def _resident_setup(a, solver_cls, nodes, resident, **kw):
    """A config-3 merged throughput stream over `nodes`, as
    bench.run_ours builds it: (solver, [asks per batch], [PackedBatch])."""
    import bench
    from nomad_tpu.solver.tensorize import Tensorizer
    count, epc = 64, a.evals_per_call
    n_nodes = len(nodes)
    probe = bench.asks_for(bench.make_job(3, 0, count, gen_seed=a.seed))
    gp = len({Tensorizer.ask_signature(x) for x in probe})
    rs = solver_cls(nodes, probe, gp=1 << max(0, (gp - 1).bit_length()),
                    kp=1 << max(0, (count * epc - 1).bit_length()),
                    max_waves=18, **kw)
    rs.reset_usage(used0=bench.resident_used0(rs.template, n_nodes,
                                              resident))
    asks_by_batch, batches = [], []
    for b in range(2 * a.stream_batches):
        jobs = [bench.make_job(3, b * epc + e, count, gen_seed=a.seed)
                for e in range(epc)]
        asks, keys = rs.merge_asks(sum((bench.asks_for(j) for j in jobs),
                                       []))
        pb = rs.pack_batch(asks, job_keys=keys)
        need(pb is not None, "config-3 asks fell outside the universe")
        asks_by_batch.append(asks)
        batches.append(pb)
    return rs, asks_by_batch, batches


def _delta(nodes, n_nodes, w):
    """One plan-apply feedback changeset: 32 resident allocs stop (every
    node carries several R_VEC-shaped ones) and two nodes gain capacity
    — a usage scatter-add and a node-plane scatter-set, both donating.
    (Fewer stops at toy size: a delta over a quarter of the nodes
    repacks instead.)"""
    import bench
    from nomad_tpu.solver.tensorize import ClusterDelta
    d = ClusterDelta()
    for k in range(max(1, min(32, n_nodes // 16))):
        d.stop.append((nodes[(w * 977 + k * 131) % n_nodes].id,
                       bench._steady_alloc()))
    for k in range(2):
        n = copy.deepcopy(nodes[(w * 389 + k * 17) % n_nodes])
        n.node_resources.cpu += 1000
        d.upsert_nodes.append(n)
    return d


def phase_resident(a, watch, state):
    import numpy as np
    from nomad_tpu.solver.resident import ResidentSolver
    from nomad_tpu.solver.tensorize import template_checksum

    out = {}
    t0 = time.monotonic()
    import bench
    nodes = bench.make_nodes(a.nodes, gen_seed=a.seed)
    rs, asks_by_batch, batches = _resident_setup(a, ResidentSolver, nodes,
                                                 a.allocs)
    state["resident"] = rs
    out["setup_wall_s"] = round(time.monotonic() - t0, 1)
    nb = a.stream_batches
    c0 = watch.snapshot()
    traffic = rs.wave_traffic(batches)
    out["pallas_mode"], out["tile"] = traffic["mode"], traffic["tile"]
    if a.platform == "tpu":
        need(traffic["mode"] != "off", "pallas resolved to off for the "
                                       "resident stream")

    # one fused call over nb batches
    u0, _ = rs.usage()
    choice, _ok, _score, status = rs.solve_stream(
        batches[:nb], seeds=list(range(1, nb + 1)))
    need(next(iter(rs._used.devices())).platform == a.platform,
         "carried usage does not live on the reported device")
    u1, _ = rs.usage()
    c, r, f = check_stream(nodes, rs.template, asks_by_batch[:nb],
                           batches[:nb], choice, status, u0, u1)
    out["fused"] = {"committed": c, "retry": r, "failed": f,
                    **rs.measured_wave_counters()}
    need(c > 0, "the fused stream committed nothing")

    # a donating delta, then the pipelined schedule with one more delta
    # between its batches
    need(rs.apply_delta(_delta(nodes, a.nodes, 0)) == "delta",
         "apply_delta fell back to a full repack")
    u2, _ = rs.usage()
    deltas = [None] + [_delta(nodes, a.nodes, w) for w in range(1, nb)]
    # the deltas' own usage changes are not placements: fold them into
    # the baseline the check starts from
    choice, _ok, _score, status = rs.solve_stream_pipelined(
        batches[nb:2 * nb], seeds=list(range(101, 101 + nb)),
        deltas=deltas)
    u3, _ = rs.usage()
    from nomad_tpu.solver.tensorize import alloc_usage_vector
    base = u2.astype(np.float64)
    for d in deltas[1:]:
        for nid, alloc in d.stop:
            base[rs.node_index[nid]] -= alloc_usage_vector(alloc)
    c, r, f = check_stream(rs.nodes, rs.template, asks_by_batch[nb:2 * nb],
                           batches[nb:2 * nb], choice, status, base, u3)
    out["pipelined"] = {"committed": c, "retry": r, "failed": f,
                        **rs.measured_wave_counters()}
    need(c > 0, "the pipelined stream committed nothing")
    out["delta_counters"] = {k: rs.delta_counters[k] for k in
                             ("delta_applies", "repack_fallbacks")}
    need(rs.delta_counters["repack_fallbacks"] == 0,
         "a delta fell back to a full repack")
    need(rs.plane_checksum() == template_checksum(rs.template),
         "device node planes diverged from the host template")
    out["compiles"] = watch.diff(c0, watch.snapshot())
    return out


def phase_health(a, watch, state):
    from nomad_tpu.telemetry.health import (device_health_raw, fetch_health,
                                            health_host)
    rs = state.get("resident")
    need(rs is not None, "the resident phase left no solver to sample")
    got = fetch_health(device_health_raw(rs))
    used, dev_used = rs.usage()
    want = health_host(rs.template, used, dev_used)
    need(got == want, f"device health {got} != host twin {want}")
    return {"nodes_valid": got.nodes_valid, "nodes_busy": got.nodes_busy}


def phase_four_chips(a, watch):
    """Node axis sharded over four real chips at 102,400 nodes."""
    import jax
    import numpy as np
    from nomad_tpu.parallel.sharded import (ShardedResidentSolver,
                                            make_node_mesh)
    from nomad_tpu.solver.resident import ResidentSolver
    from nomad_tpu.solver.tensorize import template_checksum

    out = {}
    n_nodes = a.nodes
    nb = a.stream_batches
    t0 = time.monotonic()
    import bench
    nodes = bench.make_nodes(n_nodes, gen_seed=a.seed)
    srs, asks_by_batch, batches = _resident_setup(
        a, ShardedResidentSolver, nodes, a.allocs, mesh=make_node_mesh(4))
    out["setup_wall_s"] = round(time.monotonic() - t0, 1)
    c0 = watch.snapshot()
    # the node planes are split four ways, not resident on device 0
    Np = srs.template.avail.shape[0]
    for name in ("avail", "attr_rank"):
        shards = srs._dev_node[name].addressable_shards
        need(len({s.device for s in shards}) == 4
             and all(s.data.shape[0] == Np // 4 for s in shards),
             f"{name} is not split four ways: "
             f"{[(str(s.device), s.data.shape) for s in shards]}")
    out["shard_rows"] = Np // 4
    out["memory_stats"] = [
        {"device": str(d), "bytes_in_use":
         (d.memory_stats() or {}).get("bytes_in_use")}
        for d in jax.devices()[:4]]

    u0, _ = srs.usage()
    choice, _ok, _score, status = srs.solve_stream(
        batches[:nb], seeds=list(range(1, nb + 1)))
    u1, _ = srs.usage()
    c, r, f = check_stream(nodes, srs.template, asks_by_batch[:nb],
                           batches[:nb], choice, status, u0, u1)
    out["sharded"] = {"committed": c, "retry": r, "failed": f}
    need(srs.apply_delta(_delta(nodes, n_nodes, 0)) == "delta",
         "sharded apply_delta fell back to a full repack")
    need(srs.plane_checksum() == template_checksum(srs.template),
         "sharded node planes diverged from the host template")
    u2, _ = srs.usage()
    choice, _ok, _score, status = srs.solve_stream(
        batches[nb:2 * nb], seeds=list(range(101, 101 + nb)))
    u3, _ = srs.usage()
    c2, r2, f2 = check_stream(srs.nodes, srs.template,
                              asks_by_batch[nb:2 * nb],
                              batches[nb:2 * nb], choice, status, u2, u3)
    out["sharded_after_delta"] = {"committed": c2, "retry": r2,
                                  "failed": f2}

    # same counts as one chip on the same first stream (not the same
    # nodes: approx_max_k runs per shard)
    rs, _a, b1 = _resident_setup(a, ResidentSolver, nodes, a.allocs)
    _c, _ok, _s, st1 = rs.solve_stream(b1[:nb],
                                       seeds=list(range(1, nb + 1)))
    from nomad_tpu.solver.resident import STATUS_COMMITTED
    single = int(sum((st1[b][:b1[b].n_place] == STATUS_COMMITTED).sum()
                     for b in range(nb)))
    out["single_chip_committed"] = single
    need(single == c, f"four chips committed {c}, one chip {single}")
    out["compiles"] = watch.diff(c0, watch.snapshot())
    return out


# --------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal only: run without a TPU")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--nodes", type=int, default=None)
    ap.add_argument("--allocs", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fill-count", type=int, default=64,
                    help="allocs per fill job")
    ap.add_argument("--fresh-jobs", type=int, default=24)
    ap.add_argument("--evals-per-call", type=int, default=128)
    ap.add_argument("--stream-batches", type=int, default=3)
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="seconds to wait for the server to quiesce")
    ap.add_argument("--only", default="",
                    help="debugging: comma-separated phases to run; a "
                         "subset never reports ok")
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out"))
    a = ap.parse_args(argv)

    t_start = time.monotonic()
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"jax {jax.__version__}  platform={device['platform']}  "
          f"device_kind={device['kind']}  count={device['count']}",
          flush=True)
    if device["platform"] != "tpu" and not a.allow_cpu:
        print("chip_smoke: JAX found no TPU; refusing to run "
              "(--allow-cpu rehearses at toy size)", file=sys.stderr)
        return 2
    if a.chips == 4 and device["count"] < 4:
        # this script provisions no virtual devices: four chips means
        # four chips (an --allow-cpu rehearsal brings its own XLA_FLAGS)
        print(f"chip_smoke: --chips 4 needs four devices, have "
              f"{device['count']} {device['platform']}", file=sys.stderr)
        return 2
    a.platform = device["platform"]
    if a.nodes is None:
        a.nodes = 102_400 if a.chips == 4 else NODES
    if a.allocs is None:
        a.allocs = ALLOCS

    from nomad_tpu.solver import pallas_kernel
    from nomad_tpu.utils.compile_cache import (CompileWatch, cache_entries,
                                               enable_compile_cache)
    cache_dir = enable_compile_cache()
    cache0 = cache_entries()
    watch = CompileWatch().install()

    state = {}
    if a.chips == 4:
        phases = [("four_chips", lambda: phase_four_chips(a, watch))]
    else:
        phases = [("served", lambda: phase_served(a, watch)),
                  ("kernel", lambda: phase_kernel(a, watch)),
                  ("resident", lambda: phase_resident(a, watch, state)),
                  ("health", lambda: phase_health(a, watch, state))]
    only = [p for p in a.only.split(",") if p]
    summary = {"ok": False, "device": device, "jax": jax.__version__,
               "nodes": a.nodes, "allocs": a.allocs, "seed": a.seed,
               "pallas_enabled": pallas_kernel.enabled(),
               "pallas_interpreted": pallas_kernel._interpret(),
               "phases": {}, "detail": {}}
    for name, run in phases:
        if only and name not in only:
            summary["phases"][name] = "not run"
            continue
        t0 = time.monotonic()
        print(f"[{name}] start", flush=True)
        try:
            summary["detail"][name] = run()
            summary["phases"][name] = "pass"
        except Exception:
            # the failure is reported, the run fails, and the remaining
            # phases still run so that one chip call says everything
            traceback.print_exc()
            summary["phases"][name] = "fail"
        summary["detail"].setdefault(name, {})["wall_s"] = round(
            time.monotonic() - t0, 1)
        print(f"[{name}] {summary['phases'][name]} "
              f"{json.dumps(summary['detail'][name], default=str)}",
              flush=True)
    summary["compiles"] = watch.snapshot()
    summary["compile_cache"] = {"dir": cache_dir,
                                "entries_before": cache0,
                                "entries_after": cache_entries()}
    summary["wall_s"] = round(time.monotonic() - t_start, 1)
    summary["ok"] = all(v == "pass" for v in summary["phases"].values())
    summary["claim"] = None
    os.makedirs(a.out, exist_ok=True)
    line = json.dumps(summary, default=str)
    with open(os.path.join(a.out, "chip_smoke.json"), "w") as f:
        f.write(line + "\n")
    print(line, flush=True)
    # the driver's contract: the last line holds these two keys only
    print(json.dumps({"ok": summary["ok"], "device": device}), flush=True)
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
