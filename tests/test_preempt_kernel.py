"""In-kernel preemption waves (ISSUE 7): the device eviction pass must
produce (place, evict) pairs AND explainability counters bit-identical
to the host.py twin across pallas modes, shortlist on/off, mesh widths
1/2/4, and random overcommit interleavings — and the scheduler must
commit those pairs without falling back to the host-side walk."""
import numpy as np
import pytest

import jax
from jax.sharding import Mesh, PartitionSpec as P

from nomad_tpu import mock, structs
from nomad_tpu.parallel.sharded import _ARG_SPECS, ShardedResidentSolver, \
    kernel_args
from nomad_tpu.scheduler.harness import Harness
from nomad_tpu.scheduler.preemption import PRIORITY_DELTA
from nomad_tpu.solver.host import host_solve_kernel
from nomad_tpu.solver.kernel import EV_PRIORITY_DELTA, solve_kernel
from nomad_tpu.solver.resident import ResidentSolver
from nomad_tpu.solver.solve import Solver
from nomad_tpu.solver.tensorize import (ClusterDelta, PlacementAsk,
                                        Tensorizer, alloc_usage_vector,
                                        evict_width)
from nomad_tpu.state.store import SchedulerConfiguration
from nomad_tpu.structs import Spread


def test_priority_delta_pinned():
    """The device module duplicates the scheduler's priority gate to
    stay import-light; the two constants must never drift."""
    assert EV_PRIORITY_DELTA == PRIORITY_DELTA


def test_evict_width_env(monkeypatch):
    monkeypatch.delenv("NOMAD_TPU_EVICT_E", raising=False)
    assert evict_width() == 8
    monkeypatch.setenv("NOMAD_TPU_EVICT_E", "4")
    assert evict_width() == 4
    monkeypatch.setenv("NOMAD_TPU_EVICT_E", "0")
    assert evict_width() == 0
    monkeypatch.setenv("NOMAD_TPU_EVICT_E", "bogus")
    with pytest.raises(ValueError):
        evict_width()


# ------------------------------------------------------------------
# random overcommitted worlds
# ------------------------------------------------------------------
def _low_alloc(i, k, node, prio, cpu, mem, create_index):
    a = mock.alloc()
    a.id = f"low-{i}-{k}"
    a.node_id = node.id
    a.job.priority = prio
    a.create_index = create_index
    tr = a.allocated_resources.tasks["web"]
    tr.cpu, tr.memory_mb, tr.networks = cpu, mem, []
    a.allocated_resources.shared.networks = []
    a.allocated_resources.shared.disk_mb = 0
    return a


def overcommit_world(seed, n_nodes=32, spread=False):
    """Nodes mostly full of low-priority allocs, plus asks that cannot
    place without evictions.  Returns (nodes, allocs_by_node, asks,
    used0_fn)."""
    rng = np.random.default_rng(seed)
    nodes = []
    for i in range(n_nodes):
        n = mock.node(datacenter=f"dc{i % 3}")
        n.node_resources.cpu = int(rng.choice([3000, 4000, 6000]))
        n.node_resources.memory_mb = 8192
        n.reserved_resources.cpu = 0
        n.reserved_resources.memory_mb = 0
        n.compute_class()
        nodes.append(n)
    allocs_by_node = {}
    ci = 0
    for i, n in enumerate(nodes):
        lst = []
        for k in range(int(rng.integers(2, 6))):
            prio = int(rng.choice([5, 10, 20, 30, 45]))
            cpu = int(rng.choice([400, 700, 900, 1200]))
            lst.append(_low_alloc(i, k, n, prio, cpu,
                                  cpu * 2, ci))
            ci += 1
        allocs_by_node[n.id] = lst
    asks = []
    for g, prio in enumerate((60, 50, 25)):
        j = mock.job(priority=prio)
        j.id = f"hi-{g}"
        j.datacenters = ["dc0", "dc1", "dc2"]
        if spread and g == 0:
            j.spreads = [Spread(attribute="${node.datacenter}",
                                weight=100)]
        tg = j.task_groups[0]
        tg.count = int(rng.integers(4, 9))
        tg.tasks[0].resources.networks = []
        tg.tasks[0].resources.cpu = int(rng.choice([2000, 2500]))
        tg.tasks[0].resources.memory_mb = 2048
        tg.networks = []
        tg.ephemeral_disk.size_mb = 0
        asks.append(PlacementAsk(job=j, tg=tg, count=tg.count))
    return nodes, allocs_by_node, asks


def packed_overcommit(seed, evict_e=8, spread=False):
    nodes, abn, asks = overcommit_world(seed, spread=spread)
    pb = Tensorizer().pack(nodes, asks, abn, evict_e=evict_e)
    used0 = np.zeros_like(pb.used0)
    for i, n in enumerate(nodes):
        for a in abn[n.id]:
            used0[i] += alloc_usage_vector(a)
    pb.used0 = used0
    return pb, nodes, abn, asks


def _ev_kw(pb):
    return dict(has_preempt=True, ev_res=pb.ev_res, ev_prio=pb.ev_prio,
                ask_prio=pb.ask_prio)


def assert_preempt_identical(res, host):
    ok = np.asarray(res.choice_ok)
    np.testing.assert_array_equal(ok, host.choice_ok)
    np.testing.assert_array_equal(
        np.where(ok, np.asarray(res.choice), -1),
        np.where(host.choice_ok, host.choice, -1))
    np.testing.assert_array_equal(np.asarray(res.evict),
                                  np.asarray(host.evict))
    np.testing.assert_array_equal(np.asarray(res.commit_wave),
                                  np.asarray(host.commit_wave))
    np.testing.assert_array_equal(np.asarray(res.unfinished),
                                  host.unfinished)
    np.testing.assert_array_equal(np.asarray(res.n_feasible),
                                  host.n_feasible)
    np.testing.assert_array_equal(np.asarray(res.n_exhausted),
                                  host.n_exhausted)
    np.testing.assert_array_equal(np.asarray(res.dim_exhausted),
                                  host.dim_exhausted)
    np.testing.assert_array_equal(np.asarray(res.used_final),
                                  host.used_final)


@pytest.mark.parametrize("pallas", ["off", "score", "topk"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_device_vs_host_twin(pallas, seed):
    pb, *_ = packed_overcommit(seed, spread=(seed % 2 == 0))
    host = host_solve_kernel(*kernel_args(pb), **_ev_kw(pb))
    res = solve_kernel(*kernel_args(pb), has_distinct=False,
                       pallas_mode=pallas, **_ev_kw(pb))
    assert np.asarray(host.evict).any(), "workload must force evictions"
    assert_preempt_identical(res, host)


@pytest.mark.parametrize("shortlist_c", [0, -1])
def test_shortlist_on_off(shortlist_c):
    pb, *_ = packed_overcommit(3, spread=True)
    host = host_solve_kernel(*kernel_args(pb), **_ev_kw(pb))
    res = solve_kernel(*kernel_args(pb), has_distinct=False,
                       shortlist_c=shortlist_c, **_ev_kw(pb))
    assert_preempt_identical(res, host)


def mesh_solve_preempt(pb, n_shards, **kw):
    """solve_kernel under shard_map with the eviction planes sharded
    on the node axis like every other node plane (their keys ride the
    candidate-key ICI exchange)."""
    args = kernel_args(pb)
    extra = (pb.ev_res, pb.ev_prio, pb.ask_prio)
    in_specs = tuple(_ARG_SPECS) + (P("nodes", None, None),
                                    P("nodes", None), P())
    mesh = Mesh(np.array(jax.devices()[:n_shards]), ("nodes",))

    def body(*a):
        base, (evr, evp, ap) = a[:-3], a[-3:]
        return solve_kernel(*base, mesh_axis="nodes",
                            mesh_shards=n_shards, has_preempt=True,
                            has_distinct=False, ev_res=evr, ev_prio=evp,
                            ask_prio=ap, **kw)

    shape = jax.eval_shape(
        lambda *a: solve_kernel(*a[:-3], has_preempt=True,
                                has_distinct=False, ev_res=a[-3],
                                ev_prio=a[-2], ask_prio=a[-1], **kw),
        *(args + extra))
    out_specs = jax.tree_util.tree_map(lambda _: P(), shape)
    out_specs = out_specs._replace(feas=P(None, "nodes"),
                                   used_final=P("nodes", None),
                                   dev_used_final=P("nodes", None))
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False))
    return f(*(args + extra))


@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_mesh_vs_host_twin(n_shards):
    pb, *_ = packed_overcommit(4, spread=True)
    host = host_solve_kernel(*kernel_args(pb), **_ev_kw(pb))
    res = mesh_solve_preempt(pb, n_shards)
    assert np.asarray(host.evict).any()
    assert_preempt_identical(res, host)


@pytest.mark.parametrize("n_shards", [2, 4])
def test_mesh_shortlist_vs_host_twin(n_shards):
    pb, *_ = packed_overcommit(5)
    host = host_solve_kernel(*kernel_args(pb), **_ev_kw(pb))
    res = mesh_solve_preempt(pb, n_shards, shortlist_c=0)
    assert_preempt_identical(res, host)


# ------------------------------------------------------------------
# stream interleavings: evictions feed back as stop deltas
# ------------------------------------------------------------------
def _stream_world(seed):
    nodes, abn, asks = overcommit_world(seed, n_nodes=32)
    used0 = None
    return nodes, abn, asks


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_stream_interleaved_evictions(seed, n_shards):
    """Random overcommit interleavings through the resident stream:
    solve a batch, feed its evictions back as stop deltas (the worker's
    plan-apply feed), solve the next — single-device, sharded, and the
    host twin all bit-identical per batch."""
    nodes, abn, asks = overcommit_world(seed, n_nodes=32)
    used0 = None

    def build(cls, **kw):
        s = cls(nodes, asks, abn, evict_e=8, pallas="off", **kw)
        u0 = np.zeros_like(s.template.used0)
        for i, n in enumerate(nodes):
            for a in abn[n.id]:
                u0[i] += alloc_usage_vector(a)
        s.reset_usage(used0=u0)
        return s, u0

    rs, u0 = build(ResidentSolver)
    solvers = [rs]
    if n_shards > 1:
        ss, _ = build(ShardedResidentSolver, n_devices=n_shards)
        solvers.append(ss)

    host_used = u0.copy()
    host_tpl = rs.template          # rs's template mirrors host state
    live = {a.id: (n.id, a) for n in nodes for a in abn[n.id]}

    for step in range(3):
        results = []
        for s in solvers:
            pb = s.pack_batch(asks)
            assert pb is not None
            pb.job_keys = None
            choice, ok, score, status = s.solve_stream([pb])
            results.append((np.asarray(choice), np.asarray(ok),
                            np.asarray(status),
                            np.asarray(s.last_evict)[0], pb))
        # host twin against rs's template planes + carried usage
        pb0 = results[0][4]
        import copy
        pbh = copy.copy(pb0)
        pbh.used0 = host_used
        host = host_solve_kernel(*kernel_args(pbh), **_ev_kw(pbh))
        ch, okh = np.asarray(host.choice), np.asarray(host.choice_ok)
        for choice, ok, status, evict, _pb in results:
            np.testing.assert_array_equal(ok[0], okh)
            np.testing.assert_array_equal(
                np.where(ok[0], choice[0], -1), np.where(okh, ch, -1))
            np.testing.assert_array_equal(evict,
                                          np.asarray(host.evict))
        host_used = np.asarray(host.used_final).copy()

        # feed evictions back as stop deltas (worker plan-apply path)
        evict = results[0][3]
        ch0, ok0 = results[0][0][0], results[0][1][0]
        delta = ClusterDelta()
        stopped = set()
        for p in range(pb0.n_place):
            if not ok0[p, 0] or not evict[p].any():
                continue
            ni = int(ch0[p, 0])
            for e in np.nonzero(evict[p])[0]:
                aid = pb0.ev_ids[ni][e]
                if aid and aid not in stopped:
                    stopped.add(aid)
                    delta.stop.append(live.pop(aid))
        if delta.empty():
            break
        for s in solvers:
            # carried device usage already reflects the evictions (the
            # kernel freed victims in-place); only the candidate planes
            # advance here, so zero the delta's usage side by applying
            # a matching place+stop? No: apply_delta charges u_res for
            # stops — compensate by re-adding the freed usage.
            freed_rows = {}
            for nid, a in delta.stop:
                i = s.node_index[nid]
                freed_rows[i] = freed_rows.get(i, 0) + \
                    alloc_usage_vector(a)
            s.apply_delta(delta)
            idx = np.asarray(sorted(freed_rows), np.int32)
            rows = np.stack([freed_rows[i] for i in sorted(freed_rows)])
            s._used = s._delta_add(s._used, idx, rows)
        for nid, a in delta.stop:
            abn[nid] = [x for x in abn[nid] if x.id != a.id]
        # the host template is rs.template (shared object) — only the
        # host carried usage needs the same stop compensation
        # (host_used already advanced through used_final)


# ------------------------------------------------------------------
# end-to-end: scheduler commits kernel-selected (place, evict) pairs
# ------------------------------------------------------------------
def test_scheduler_inkernel_eviction_end_to_end():
    """With a resident world and preemption enabled, an overcommitted
    eval's evictions are selected IN-KERNEL: the plan carries
    node_preemptions, the alloc carries preempted_allocations, and the
    host-side fallback walk never runs."""
    from nomad_tpu.utils.metrics import global_metrics
    global_metrics.reset()
    h = Harness()
    h.store.set_scheduler_config(
        h.next_index(), SchedulerConfiguration(preemption_service=True))
    h.solver = Solver(store=h.store, resident_min_nodes=1)
    for i in range(8):
        n = mock.node()
        n.node_resources.cpu = 3000
        n.node_resources.memory_mb = 8192
        n.reserved_resources.cpu = 0
        n.reserved_resources.memory_mb = 0
        n.compute_class()
        h.store.upsert_node(h.next_index(), n)

    lowjob = mock.job(priority=10)
    tg = lowjob.task_groups[0]
    tg.count = 8
    tg.tasks[0].resources.cpu = 2500
    tg.tasks[0].resources.memory_mb = 1024
    tg.tasks[0].resources.networks = []
    h.store.upsert_job(h.next_index(), lowjob)
    h.process("service", mock.eval_(
        job_id=lowjob.id,
        triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER))
    low = h.store.allocs_by_job("default", lowjob.id)
    assert len(low) == 8
    for a in low:
        a.client_status = structs.ALLOC_CLIENT_RUNNING
    h.store.upsert_allocs(h.next_index(), low)

    hijob = mock.job(priority=50)
    tg = hijob.task_groups[0]
    tg.count = 2
    tg.tasks[0].resources.cpu = 2500
    tg.tasks[0].resources.memory_mb = 1024
    tg.tasks[0].resources.networks = []
    h.store.upsert_job(h.next_index(), hijob)
    h.process("service", mock.eval_(
        job_id=hijob.id, priority=50,
        triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER))

    hi = h.store.allocs_by_job("default", hijob.id)
    assert len(hi) == 2
    preempted = sorted(sum((a.preempted_allocations for a in hi), []))
    assert preempted, "kernel eviction pass must have fired"
    low_ids = {a.id for a in low}
    assert set(preempted) <= low_ids
    for v in preempted:
        assert h.store.alloc_by_id(v).desired_status == \
            structs.ALLOC_DESIRED_EVICT
    counters = global_metrics.dump().get("counters", {})
    assert counters.get("scheduler.preempt.kernel", 0) >= 1
    assert counters.get("scheduler.preempt.host_fallback", 0) == 0
