#!/usr/bin/env python
"""Benchmark: the TPU placement pipeline vs stock scheduler semantics.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
and writes the full per-config results to BENCH_DETAIL.json.

Configs follow BASELINE.md's measurement plan:
  1. 1 service job x 10 task groups on 100 in-mem nodes (latency mode)
  2. 10K nodes, 50K resident allocs - pure bin-pack stream
  3. 10K heterogeneous nodes, 100K resident allocs - constraints +
     affinity + spread + anti-affinity (the primary config)
  4. device scheduling - TPU inventory on every 4th node
  5. multi-region federation - 4 regions x 10K nodes

The DENOMINATOR is honest per VERDICT r2: bench/stock_engine.cc, a
faithful C++ implementation of the reference's placement semantics AND
data layout (string-keyed state, per-eval shuffled node order, lazy
class-memoized feasibility, limit = max(2, ceil(log2 N)) subsampled
ranking - scheduler/stack.go:80-87 - proposed-alloc bin-packing, serial
re-validating plan applier). C++ stands in for Go at comparable speed;
the scenario generators on both sides share the same formulas, so the
engines see identical clusters and jobs.

The NUMERATOR is the production ResidentSolver streaming path: node
tensors packed and device-put once, ask programs packed per eval batch,
usage carried on device, many batches fused per device call, one packed
result fetch. Timings include ask packing, transfer, solve, and result
fetch - everything after one-time startup (reported separately).

Both throughput (fused streams) and latency (single-eval calls) are
measured; placement-QUALITY is compared with a pack-to-capacity duel
(the stock path ranks ~14 of N nodes; this solve scores all N).
"""
import json
import math
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

REPO = os.path.dirname(os.path.abspath(__file__))


def _cache_report(entries_before):
    """Compile-cache hit/miss report for the startup line: programs
    persisted during THIS startup are misses; a fully warm start adds
    none."""
    from nomad_tpu.utils.compile_cache import (cache_entries,
                                               enable_compile_cache)
    d = enable_compile_cache()
    added = cache_entries() - entries_before
    return {"dir": d, "entries_before": entries_before,
            "compiles_persisted": added, "warm_start": added == 0}


#: published per-chip peaks keyed by `jax.devices()[0].device_kind`.
#: v5e: Google Cloud documentation, "TPU v5e" system architecture —
#: 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s.
DEVICE_PEAKS = {
    "TPU v5 lite": {"hbm_gbps": 819.0, "bf16_tflops": 197.0},
}


def device_peaks():
    """Peaks of the device JAX runs on; a device that is not in the
    table is an error, never a default (a roofline share against the
    wrong chip's bandwidth is worse than none)."""
    import jax
    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peaks recorded for device kind {kind!r}; "
            f"add it to bench.DEVICE_PEAKS with its source "
            f"(known: {sorted(DEVICE_PEAKS)})")
    return DEVICE_PEAKS[kind]


STOCK_BIN = os.path.join(REPO, "bench", "stock_engine")
STOCK_SRC = os.path.join(REPO, "bench", "stock_engine.cc")

R_VEC = [200.0, 256.0, 300.0, 0.0]       # resident alloc usage vector


def pct(sorted_ms, p):
    """Nearest-rank percentile over an ASCENDING ms list (the shared
    helper every phase uses — previously copied per phase)."""
    return sorted_ms[int(p * (len(sorted_ms) - 1))] if sorted_ms else 0.0


def latency_summary(latencies_s):
    """p50/p99 (ms) of a latency sample in seconds — the one latency
    summary used by the closed-loop and latency-mode phases."""
    lat_ms = sorted(1000.0 * x for x in latencies_s)
    return {"p50_ms": round(pct(lat_ms, 0.5), 3),
            "p99_ms": round(pct(lat_ms, 0.99), 3)}


# ---------------- scenario (mirrors stock_engine.cc) ----------------

def make_nodes(n_nodes, devices=False, gen_seed=0):
    from nomad_tpu import mock
    nodes = []
    for i in range(n_nodes):
        n = mock.node(datacenter=f"dc{i % 4}")
        # identical effective capacity on both engines: the stock C++
        # generator models no reserved carve-out, and a 100-cpu/node
        # difference alone decides the pack-to-capacity duel (256
        # placements at 512 nodes) — zero it here rather than compare
        # engines against different clusters
        n.reserved_resources.cpu = 0
        n.reserved_resources.memory_mb = 0
        n.reserved_resources.disk_mb = 0
        n.attributes["kernel.name"] = "linux"
        n.attributes["rack"] = f"r{i % 64}"
        n.attributes["zone"] = f"z{i % 16}"
        n.node_resources.cpu = 4000 + ((i + gen_seed) % 8) * 1000
        n.node_resources.memory_mb = 8192 + ((i + gen_seed * 3) % 4) * 4096
        n.node_resources.disk_mb = 100_000
        for net in n.node_resources.networks:
            net.mbits = 1000
        if devices and i % 2 == 0:
            from nomad_tpu.structs import NodeDeviceResource, NodeDevice
            n.node_resources.devices = [NodeDeviceResource(
                vendor="google", type="tpu", name="v4",
                instances=[NodeDevice(id=f"tpu-{i}-{k}", healthy=True)
                           for k in range(8)])]
        n.compute_class()
        nodes.append(n)
    return nodes


def make_job(config, eval_ix, count, gen_seed=0):
    """Mirrors stock_engine.cc make_job exactly."""
    from nomad_tpu import mock
    from nomad_tpu.structs import Affinity, Constraint, RequestedDevice, \
        Spread
    job = mock.job()
    job.id = f"job-{config}-{eval_ix}"
    job.name = job.id
    job.datacenters = [f"dc{d}" for d in range(4)]
    job.constraints = []
    job.affinities = []
    job.spreads = []
    base = job.task_groups[0]
    base.constraints = []

    def group(name, cnt, cpu, mem, devices=0):
        import copy
        tg = copy.deepcopy(base)
        tg.name = name
        tg.count = cnt
        tg.constraints = []
        t = tg.tasks[0]
        t.resources.networks = []
        t.resources.cpu = cpu
        t.resources.memory_mb = mem
        t.resources.devices = ([RequestedDevice(name="google/tpu/v4",
                                                count=devices)]
                               if devices else [])
        tg.ephemeral_disk.size_mb = 300
        return tg

    if config == 1:
        job.constraints = [Constraint("${attr.kernel.name}", "linux", "=")]
        job.task_groups = [
            group(f"g{g}", max(1, count // 10),
                  400 + ((g + gen_seed) % 4) * 150,
                  256 + ((g + gen_seed) % 4) * 128)
            for g in range(10)]
        return job
    if config == 3:
        job.constraints = [
            Constraint("${attr.rack}", "r63", "!="),
            Constraint("${attr.zone}", "z1", ">="),      # lexical
        ]
        job.affinities = [Affinity(ltarget="${attr.rack}", rtarget="r7",
                                   operand="=", weight=35)]
        job.spreads = [Spread(attribute="${node.datacenter}", weight=50)]
        job.task_groups = [
            group(f"g{g}", count // 4,
                  400 + ((g + gen_seed) % 4) * 150,
                  256 + ((g + gen_seed) % 4) * 128)
            for g in range(4)]
        return job
    dev = 1 if config == 4 else 0
    job.task_groups = [group("g0", count, 400, 256, devices=dev)]
    return job


def resident_used0(template, n_nodes, resident):
    import numpy as np
    used0 = np.zeros_like(template.used0)
    counts = np.bincount(np.arange(resident) % n_nodes,
                         minlength=n_nodes).astype(np.float32)
    used0[:n_nodes] = counts[:, None] * np.asarray(R_VEC, np.float32)
    return used0


# ---------------- numerator: resident streaming pipeline -------------

def asks_for(job):
    from nomad_tpu.solver.tensorize import PlacementAsk
    return [PlacementAsk(job=job, tg=tg, count=tg.count)
            for tg in job.task_groups]


def _steady_alloc():
    """A plan-apply-feedback alloc for the steady-state delta waves."""
    from nomad_tpu import mock
    a = mock.alloc()
    tr = a.allocated_resources.tasks["web"]
    tr.cpu, tr.memory_mb, tr.networks = 200, 256, []
    a.allocated_resources.shared.networks = []
    a.allocated_resources.shared.disk_mb = 300
    return a


def _harvest(status_row, pb, asks, STATUS_RETRY):
    """Vectorized per-batch result accounting: (placed, failed,
    [(ask, retry_count), ...])."""
    import numpy as np
    st = status_row[:pb.n_place]
    placed = int((st == 1).sum())
    failed = int((st == 0).sum())
    retry_mask = st == STATUS_RETRY
    if not retry_mask.any():
        return placed, failed, []
    per_ask = np.bincount(pb.p_ask[:pb.n_place][retry_mask],
                          minlength=len(asks))
    return placed, failed, [(a, int(r))
                            for a, r in zip(asks, per_ask) if r]


def run_ours(config, n_nodes, n_evals, count, resident,
             evals_per_call=128, exact=False, gen_seed=0,
             pallas="auto"):
    """Drive the ResidentSolver streaming pipeline over the config's
    eval workload.

    Throughput mode is PIPELINED: each chunk of evals packs on the host
    and dispatches immediately as its own chained device call (JAX
    dispatch is async and chained calls add no round trip — the carried
    usage serializes them on device), so packing rides entirely under
    the previous chunks' solve; ONE concatenated result fetch then pays
    the transport round trip once for the whole workload.  Wave-budget
    leftovers drain in follow-up calls.  Exact mode (quality duel)
    keeps the single fused call.  Returns metrics dict."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    from nomad_tpu.solver.resident import (ResidentSolver, STATUS_RETRY)

    devices = config == 4
    nodes = make_nodes(n_nodes, devices=devices, gen_seed=gen_seed)
    from nomad_tpu.utils.compile_cache import cache_entries
    cache0 = cache_entries()
    t0 = time.perf_counter()
    probe_job = make_job(config, 0, count, gen_seed=gen_seed)
    epc = min(evals_per_call, n_evals)
    # throughput mode merges identical fresh asks at pack time (the
    # columnar payoff of coalescing evals: G shrinks to the number of
    # DISTINCT ask shapes, and every per-wave [G, N] pass shrinks with
    # it); exact mode keeps one group per ask
    merge = not exact
    kp_need = count * epc
    if merge:
        # size the group axis to the workload's REAL distinct-shape
        # count: every per-wave [G, N] pass scales with gp, and the
        # merged stream needs exactly one row per distinct signature
        # (config 2/4: 1, config 3: 4) — not the MERGED_GP_MAX=16 cap.
        # Every eval's job has the same shape, so one job's signature
        # set sizes the whole stream (all bench asks are stateless).
        from nomad_tpu.solver.tensorize import Tensorizer
        gp_need = len({Tensorizer.ask_signature(a)
                       for a in asks_for(probe_job)})
    else:
        gp_need = len(probe_job.task_groups) * epc
    # exact mode uses serial-fidelity stacking commits (the reference's
    # per-placement best-fit packing — placement QUALITY over wave
    # count), with a budget deep enough to stack a full group
    rs = ResidentSolver(nodes, asks_for(probe_job),
                        gp=1 << max(0, (gp_need - 1).bit_length()),
                        kp=1 << max(0, (kp_need - 1).bit_length()),
                        max_waves=(24 if exact else 18),
                        stack_commit=exact, pallas=pallas)
    rs.reset_usage(used0=resident_used0(rs.template, n_nodes, resident))

    # build the whole eval workload up front (job objects are cheap)
    jobs = [make_job(config, e, count, gen_seed=gen_seed)
            for e in range(n_evals)]

    # single-fetch helper for drain rounds (the main pipelined stream's
    # concatenated fetch lives in ResidentSolver.solve_stream_pipelined)
    stack_jit = jax.jit(lambda *xs: jnp.stack(xs))

    NB = -(-n_evals // epc)
    # warm the compiles with the real batch shapes, then reset: the
    # stream shapes (B=1 chained calls in merge mode, one fused B=NB
    # call in exact mode), the concat/stack fetch arities, and the
    # drain-path variants (small per-group counts -> the kernel's floor
    # group_count_hint bucket)
    warm_asks = sum((asks_for(j) for j in jobs[:epc]), [])
    if merge:
        warm_asks, _wk = rs.merge_asks(warm_asks)
    warm = rs.pack_batch(warm_asks)
    warm.job_keys = None        # compile-only: bypass the same-job guard
    if merge:
        # warms the B=1 chained-call kernel AND the solver's own
        # concatenated-fetch jit at the real arity
        rs.solve_stream_pipelined([warm] * NB,
                                  seeds=[b + 1 for b in range(NB)])
    else:
        np.asarray(rs.solve_stream_async([warm] * NB, seeds=None))
    wout_b1 = rs.solve_stream_async([warm], seeds=None if exact else [1])
    for nd in (1, 2, 3, 4):     # drain fetch stacks (B=1 calls)
        np.asarray(stack_jit(*([wout_b1] * nd)))
    drain_warm_asks = [dataclasses.replace(a, count=min(a.count, 8))
                       for a in (warm_asks[:2] or warm_asks)]
    dwarm = rs.pack_batch(drain_warm_asks)
    if dwarm is not None:
        dwarm.job_keys = None
        rs.solve_stream([dwarm], seeds=None if exact else [1])
    rs.reset_usage(used0=resident_used0(rs.template, n_nodes, resident))
    startup_s = time.perf_counter() - t0

    placed = failed = retried = unresolved = 0
    n_fetches = 0
    n_dispatches = 0
    pack_s = dispatch_s = 0.0
    t_start = time.perf_counter()
    asks_all = []
    batches = []

    def pack_one(i):
        asks = sum((asks_for(j) for j in jobs[i:i + epc]), [])
        keys = None
        if merge:
            asks, keys = rs.merge_asks(asks)
        # the whole-batch cache only suits the pipelined one-batch-per-
        # call schedule; exact mode fuses MANY batches into one call and
        # a shared pb object would confuse the same-job stream guard
        pack = rs.pack_batch_cached if merge else rs.pack_batch
        pb = pack(asks, job_keys=keys)
        assert pb is not None, "bench asks must fit the universe"
        asks_all.append(asks)
        batches.append(pb)
        return pb

    if merge:
        # pipelined: pack chunk b+1 while chunk b solves (chained
        # dispatches, no host sync), then ONE concatenated fetch —
        # the double-buffered pack→dispatch overlap now lives in
        # ResidentSolver.solve_stream_pipelined
        _, _, _, status = rs.solve_stream_pipelined(
            [b * epc for b in range(NB)],
            seeds=[b + 1 for b in range(NB)], pack=pack_one)
        st = rs.last_pipeline_stats
        pack_s += st["pack_s"]
        dispatch_s += st["dispatch_s"]
        fetch_wait_s = st["fetch_s"]
        n_dispatches += st["n_dispatches"]
        n_fetches += 1
    else:
        t_p = time.perf_counter()
        for b in range(NB):
            pack_one(b * epc)
        t_d = time.perf_counter()
        out1 = rs.solve_stream_async(batches, seeds=None)
        n_dispatches += 1
        t_f = time.perf_counter()
        packed = np.asarray(out1)                      # ONE fetch
        fetch_wait_s = time.perf_counter() - t_f
        pack_s = t_d - t_p
        dispatch_s = t_f - t_d
        n_fetches += 1
        status = packed[:, :, -1].astype(np.int32)     # [NB, K]

    # wave-budget leftovers: resubmit ONLY the undecided counts, all
    # batches' leftovers fused into one reduced batch per drain round
    # (counted in the timing)
    cur = []                    # (ask, retry_count) flattened
    for b, pb in enumerate(batches):
        pl, fl, retries = _harvest(status[b], pb, asks_all[b],
                                   STATUS_RETRY)
        placed += pl
        failed += fl
        cur.extend(retries)
    gp_cap, kp_cap = rs.gp, rs.kp
    for t_retry in range(4):
        if not cur:
            break
        retried += sum(r for _, r in cur)
        # keep every drain row's count inside the kernel's floor-64
        # group_count_hint bucket (the ONLY drain variant the warm block
        # compiled): a bigger retry count splits into <=64-count rows —
        # same merged-population semantics, no compile in the timed
        # region.  Exact mode never splits (counts are already <=64).
        if merge:
            # merged drain rows are stateless by merge eligibility, so
            # they may span chunks freely: flatten the splits, then fill
            # chunks greedily under the gp/kp caps
            split = []
            for a, r in cur:
                while r > 64:
                    split.append(dataclasses.replace(a, count=64))
                    r -= 64
                split.append(dataclasses.replace(a, count=r))
            chunks, cur_chunk, cur_k = [], [], 0
            for a in split:
                if cur_chunk and (len(cur_chunk) + 1 > gp_cap
                                  or cur_k + a.count > kp_cap):
                    chunks.append(cur_chunk)
                    cur_chunk, cur_k = [], 0
                cur_chunk.append(a)
                cur_k += a.count
            if cur_chunk:
                chunks.append(cur_chunk)
        else:
            # exact mode: asks may carry job-scoped state — a job's
            # asks stay in ONE chunk (stream invariant)
            drain_asks = [dataclasses.replace(a, count=r)
                          for a, r in cur]
            by_job = {}
            for a in drain_asks:
                by_job.setdefault((a.job.namespace, a.job.id),
                                  []).append(a)
            chunks, cur_chunk, cur_k = [], [], 0
            for job_asks in by_job.values():
                jk = sum(a.count for a in job_asks)
                if cur_chunk and (len(cur_chunk) + len(job_asks) > gp_cap
                                  or cur_k + jk > kp_cap):
                    chunks.append(cur_chunk)
                    cur_chunk, cur_k = [], 0
                cur_chunk.extend(job_asks)
                cur_k += jk
            if cur_chunk:
                chunks.append(cur_chunk)
        pbs = [rs.pack_batch(c) for c in chunks]
        assert all(pb is not None for pb in pbs), \
            "drain chunk fell outside the resident universe"
        douts = []
        for i, pb in enumerate(pbs):
            douts.append(rs.solve_stream_async(
                [pb], seeds=None if exact else [1009 + 17 * t_retry + i]))
            n_dispatches += 1
        # fetch in warmed-arity groups (the warm block compiled stack
        # arities 1-4): a heavy drain round must never compile inside
        # the timed region
        drows = []
        for i in range(0, len(douts), 4):
            grp = douts[i:i + 4]
            drows.append(np.asarray(stack_jit(*grp)))
            n_fetches += 1
        dpacked = np.concatenate(drows, axis=0)
        dstatus = dpacked[:, 0, :, -1].astype(np.int32)
        nxt = []
        for b, (pb, chunk) in enumerate(zip(pbs, chunks)):
            pl, fl, retries = _harvest(dstatus[b], pb, chunk,
                                       STATUS_RETRY)
            placed += pl
            failed += fl
            nxt.extend(retries)
        cur = nxt
    # anything still RETRY after the retry budget is reported, not
    # silently dropped (placed + failed + unresolved == workload)
    unresolved += sum(r for _, r in cur)
    total_evals = n_evals
    elapsed_all = time.perf_counter() - t_start

    # ---- steady-state delta waves (ISSUE 2 acceptance) ----
    # The store-stable-jobs regime: the SAME eval population
    # re-dispatched (blocked-eval retries, drain re-evals, rollouts)
    # with a plan-apply usage changeset applied between waves.  Packing
    # is the eval-cache hit, dispatch re-ships nothing (device-cached
    # stacked args), and the device scatters only the delta rows —
    # measured against the first-pass per-wave pack+dispatch cost.
    steady = None
    if merge and batches:
        from nomad_tpu.solver.tensorize import ClusterDelta
        n_steady = min(4, len(batches))
        # warm the scatter-apply kernels at the steady shape (pow2-
        # padded slot cardinality) outside the timed region
        warm_d = ClusterDelta()
        for k in range(32):
            nid = nodes[(k * 41 + 3) % n_nodes].id
            a = _steady_alloc()
            warm_d.place.append((nid, a))
            warm_d.stop.append((nid, a))
        rs.apply_delta(warm_d)
        deltas = []
        for w in range(n_steady):
            d = ClusterDelta()
            for k in range(32):
                nid = nodes[(w * 977 + k * 131) % n_nodes].id
                a = _steady_alloc()
                d.place.append((nid, a))
                d.stop.append((nid, a))   # net-zero churn: place+stop
            deltas.append(d)
        t_s = time.perf_counter()
        rs.solve_stream_pipelined(
            batches[:n_steady], seeds=[7001 + b for b in range(n_steady)],
            deltas=deltas)
        steady_elapsed = time.perf_counter() - t_s
        st = rs.last_pipeline_stats
        main_pd = (pack_s + dispatch_s) / max(n_dispatches, 1)
        steady_pd = (st["pack_s"] + st["dispatch_s"]) / n_steady
        steady = {
            "waves": n_steady,
            "pack_ms_per_wave": round(1000 * st["pack_s"] / n_steady, 3),
            "dispatch_ms_per_wave": round(
                1000 * st["dispatch_s"] / n_steady, 3),
            "delta_apply_ms_per_wave": round(
                1000 * st["delta_apply_s"] / n_steady, 3),
            "bytes_dispatched_delta_waves": st["bytes_dispatched"],
            "elapsed_s": round(steady_elapsed, 4),
            "first_pass_pack_dispatch_ms_per_wave": round(
                1000 * main_pd, 3),
            "steady_pack_dispatch_ms_per_wave": round(
                1000 * steady_pd, 3),
            "pack_dispatch_reduction": round(
                main_pd / max(steady_pd, 1e-9), 1),
        }
    # every eval in a fused call completes when the call completes
    latencies = [elapsed_all] * n_evals
    elapsed = elapsed_all
    lat = latency_summary(latencies)

    return {
        "engine": "nomad-tpu resident stream",
        "evals": total_evals, "placements": placed, "failed": failed,
        "retried": retried, "unresolved": unresolved,
        "n_device_calls": n_fetches, "n_dispatches": n_dispatches,
        "breakdown_ms": {
            "pack": round(1000 * pack_s, 1),
            "dispatch": round(1000 * dispatch_s, 1),
            "solve_and_fetch_wait": round(1000 * fetch_wait_s, 1),
        },
        "steady_state": steady,
        "delta_counters": dict(rs.delta_counters),
        "compile_cache": _cache_report(cache0),
        "elapsed_s": round(elapsed, 4),
        "startup_s": round(startup_s, 2),
        "evals_per_sec": round(total_evals / elapsed, 1),
        "placements_per_sec": round(placed / elapsed, 1),
        "p50_ms": lat["p50_ms"], "p99_ms": lat["p99_ms"],
        "nodes_scored_per_placement": n_nodes,
    }


def measure_device_ceiling(config=3):
    """Device-only solve ceiling for one config (VERDICT r4 item 1):
    every argument resident on device, chained re-runs, the transport
    round trip subtracted — placements/s with transport at zero.  Plus
    a memory-roofline estimate of ONE wave so the distance from the
    chip is explicit: the wave's dominant traffic is the [G, N] score/
    feasibility passes (f32) + the [N, R] usage updates, far below
    MXU-relevant arithmetic intensity — the kernel is HBM-bound by
    design, so the roofline is bytes/bandwidth, not FLOPs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from nomad_tpu.solver.resident import ResidentSolver, _stream_kernel
    from nomad_tpu.solver.tensorize import Tensorizer

    p = CONFIGS[config]
    n_nodes, n_evals, count, resident = (p["n_nodes"], p["n_evals"],
                                         p["count"], p["resident"])
    epc = min(128, n_evals)
    NB = -(-n_evals // epc)
    nodes = make_nodes(n_nodes, devices=config == 4)
    probe_job = make_job(config, 0, count)
    gp_need = len({Tensorizer.ask_signature(a)
                   for a in asks_for(probe_job)})
    rs = ResidentSolver(nodes, asks_for(probe_job),
                        gp=1 << max(0, (gp_need - 1).bit_length()),
                        kp=1 << max(0, (count * epc - 1).bit_length()),
                        max_waves=18)
    used0 = resident_used0(rs.template, n_nodes, resident)
    rs.reset_usage(used0=used0)
    jobs = [make_job(config, e, count) for e in range(n_evals)]
    batches = []
    for i in range(0, n_evals, epc):
        asks, keys = rs.merge_asks(
            sum((asks_for(j) for j in jobs[i:i + epc]), []))
        batches.append(rs.pack_batch(asks, job_keys=keys))
    stacked = rs._stack_args(batches)
    dev = {k: (jax.device_put(v) if isinstance(v, np.ndarray) else v)
           for k, v in stacked.items()}
    n_places = np.asarray([pb.n_place for pb in batches], np.int32)
    seeds = np.asarray(range(1, NB + 1), np.int32)
    kw = dict(has_spread=rs._has_spread(batches),
              group_count_hint=rs._group_count_hint(batches),
              max_waves=rs.max_waves, wave_mode=rs.wave_mode,
              has_distinct=rs._has_distinct(batches),
              has_devices=rs._has_devices(batches),
              stack_commit=False, compact=rs._compact,
              pallas_mode=rs.pallas, shortlist_c=rs.shortlist_c)
    args = (rs._dev_node["avail"], rs._dev_node["reserved"],
            rs._dev_node["valid"], rs._dev_node["node_dc"],
            rs._dev_node["attr_rank"], rs._dev_node["dev_cap"])
    ts = []
    waves_total = rescore_total = 0
    for trial in range(4):
        rs.reset_usage(used0=used0)
        t0 = time.perf_counter()
        _u, _d, o, w, rw = _stream_kernel(*args, rs._used, rs._dev_used,
                                          dev, n_places, seeds, **kw)
        np.asarray(o)
        ts.append(time.perf_counter() - t0)
        waves_total = int(np.asarray(w).sum())   # same every trial
        rescore_total = int(np.asarray(rw).sum())
    solve_s = min(ts[1:])               # trial 0 warms the compile
    placements = int(n_places.sum())

    # two-tier per-wave memory model (resident.wave_traffic: full-N
    # first/rescore waves vs shortlist-resident contention waves) ×
    # MEASURED per-batch wave counters gives the achieved-bandwidth
    # figure the roofline claim is audited by.  Counters come from the
    # stream kernel in EVERY pallas mode (off/score/topk), so no field
    # here is ever left pending.
    traffic = rs.wave_traffic(batches)
    b_wave1 = traffic["bytes_wave1"]
    b_rewave = traffic["bytes_rewave"]
    sl_waves = waves_total - rescore_total
    bytes_total = b_wave1 * rescore_total + b_rewave * sl_waves
    HBM_GBPS = device_peaks()["hbm_gbps"]
    wave_floor_us = b_wave1 / (HBM_GBPS * 1e3)
    achieved_gbps = bytes_total / solve_s / 1e9
    # the merged-throughput stream carries a 1024-wide candidate
    # window, and bit-identity pins the shortlist at C >= TK — the
    # rewave reduction there is window-bounded.  The STANDARD window
    # (exact/interactive regime, the quality duel's shape) is where the
    # shortlist's full cut shows; model it at this config's node scale
    # so the two regimes sit side by side in the record.
    from nomad_tpu.solver.kernel import resolve_shortlist_c
    from nomad_tpu.solver.resident import model_wave_bytes
    t = rs.template
    S = t.sp_desired.shape[1]
    Np_pad = t.avail.shape[0]
    TK_std = 132
    C_std = resolve_shortlist_c(Np_pad, TK_std, 0)
    Gp_m = max(pb.ask_res.shape[0] for pb in batches)
    sb1, sbrw, _ = model_wave_bytes(
        Np_pad, Gp_m, 256, S, t.avail.shape[1],
        rs._has_spread(batches), traffic["mode"], TK_std, C_std)
    std_window = {
        "window_tk": TK_std, "shortlist_c": C_std,
        "bytes_wave1": sb1, "bytes_rewave": sbrw,
        "rewave_reduction": round(sb1 / max(sbrw, 1), 1),
    }
    return {
        "config": config,
        "device_only_solve_s": round(solve_s, 4),
        "device_only_placements_per_sec": round(placements / solve_s, 1),
        "roofline": {
            "wave_bytes_est": b_wave1,
            "bytes_wave1": b_wave1,
            "bytes_rewave": b_rewave,
            "rewave_reduction": round(b_wave1 / max(b_rewave, 1), 1),
            "shortlist_c": traffic["shortlist_c"],
            "waves_total": waves_total,
            "rescore_waves": rescore_total,
            "shortlist_waves": sl_waves,
            "modeled_bytes_total": int(bytes_total),
            "hbm_gbps_assumed": HBM_GBPS,
            "achieved_hbm_gbps": round(achieved_gbps, 1),
            "wave_floor_us_est": round(wave_floor_us, 1),
            "pallas_mode": traffic["mode"],
            "tile_size": traffic["tile"],
            "fused_pass_count": traffic["fused_pass_count"],
            "standard_window": std_window,
            "note": ("the wave kernel is HBM-bound; the floor is "
                     "bytes_wave1 + bytes_rewave x (waves - 1) per "
                     "batch over bandwidth.  Full-N passes run on wave "
                     "1 and on every shortlist-escape rescore "
                     "(rescore_waves); the remaining contention waves "
                     "re-rank the carried top-C shortlist in VMEM "
                     "(bytes_rewave, kernel.py).  achieved_hbm_gbps = "
                     "(bytes_wave1 x rescore_waves + bytes_rewave x "
                     "shortlist_waves) / solve_s, read against "
                     "hbm_gbps_assumed"),
        },
    }


def run_multichip(n_devices=8, sizes=None, n_evals=16, count=64,
                  evals_per_call=8, write_detail=True, n_hosts=None):
    """Multichip phase (ISSUE 5): the mesh-resident sharded solve vs
    the stateless GSPMD wrapper, per node-scale.

    Per size: pack once, then (a) the stateless path — one
    `sharded_solve` per eval batch, re-shipping the whole packed batch
    every call and leaving the collectives to XLA — and (b) the
    mesh-resident path — ShardedResidentSolver.solve_stream with the
    node planes living sharded in HBM and candidate-only ICI traffic.
    Both are timed steady-state (round 2, after the compile round).
    The record carries solve timings, per-shard HBM bytes, and the
    modeled ICI bytes with the candidate-keys acceptance check
    (`ici_within_bound`: bytes_ici_per_wave <= TK_local x G x devices
    x key_bytes — no [G, N] plane crosses chips).

    Self-provisions a virtual n-device CPU platform when fewer real
    chips are attached (same forcing as the graft dryrun) — the phase
    can NOT silently skip on a 1-device host.  Sizes default to the
    50k/100k-node configs (NOMAD_TPU_MULTICHIP_NODES overrides)."""
    import importlib
    graft = importlib.import_module("__graft_entry__")
    if n_hosts is None:
        # dcn_tier leg (ISSUE 8): simulated host grouping on the CPU
        # mesh — NOMAD_TPU_MESH_HOSTS overrides the default 4
        from nomad_tpu.parallel.sharded import env_mesh_hosts
        n_hosts = env_mesh_hosts() or 4
    n_devices, n_hosts = graft._ensure_devices(n_devices, n_hosts)
    import jax
    import numpy as np
    from nomad_tpu.parallel.sharded import (
        ElasticShardedResidentSolver, ShardedResidentSolver,
        kernel_args, make_mesh, make_node_mesh, make_two_tier_mesh,
        sharded_solve_args)
    from nomad_tpu.solver.tensorize import Tensorizer

    if sizes is None:
        raw = os.environ.get("NOMAD_TPU_MULTICHIP_NODES", "50000,100000")
        sizes = [int(s) for s in raw.split(",") if s.strip()]
    out = {"phase": "multichip", "n_devices": int(n_devices),
           "n_hosts": int(n_hosts), "skipped": False,
           "backend": jax.default_backend(), "configs": []}
    mesh_stateless = make_mesh(n_devices, n_regions=1)
    for n_nodes in sizes:
        nodes = make_nodes(n_nodes)
        probe_job = make_job(2, 0, count)
        gp_need = len({Tensorizer.ask_signature(a)
                       for a in asks_for(probe_job)})
        epc = min(evals_per_call, n_evals)
        rs = ShardedResidentSolver(
            nodes, asks_for(probe_job),
            n_devices=n_devices,
            gp=1 << max(0, (gp_need - 1).bit_length()),
            kp=1 << max(0, (count - 1).bit_length()),
            max_waves=18, pallas="off")
        jobs = [make_job(2, e, count) for e in range(n_evals)]
        # pack_batch (not _cached): the cached path dedups the
        # identical-signature jobs to ONE PackedBatch, which the
        # same-job stream guard rightly rejects inside a chunk
        batches = [rs.pack_batch(asks_for(j)) for j in jobs]
        assert all(pb is not None for pb in batches)
        NB = -(-n_evals // epc)

        # ---- stateless wrapper: re-ship + re-solve per batch ----
        t_stateless = None
        stateless_bytes = sum(int(np.asarray(a).nbytes)
                              for a in kernel_args(batches[0]))
        for round_ in range(2):          # round 0 compiles
            t0 = time.perf_counter()
            last = None
            for pb in batches:
                last = sharded_solve_args(kernel_args(pb),
                                          mesh_stateless)
            jax.block_until_ready(last.choice)
            t_stateless = time.perf_counter() - t0

        # ---- mesh-resident stream ----
        t_resident = None
        resident_bytes = 0
        for round_ in range(2):
            rs.reset_usage()
            t0 = time.perf_counter()
            outs = []
            resident_bytes = 0
            for b in range(NB):
                chunk = batches[b * epc:(b + 1) * epc]
                outs.append(rs.solve_stream_async(chunk))
                resident_bytes += rs.last_dispatch_bytes
            jax.block_until_ready(outs[-1])
            t_resident = time.perf_counter() - t0
        wt = rs.wave_traffic(batches[:epc])
        ici = wt["ici"]
        rec = {
            "n_nodes": n_nodes,
            "np_padded": int(rs.template.avail.shape[0]),
            "n_evals": n_evals, "count": count,
            "stateless_wrapper_s": round(t_stateless, 4),
            "mesh_resident_s": round(t_resident, 4),
            "steady_state_speedup": round(
                t_stateless / max(t_resident, 1e-9), 2),
            # host->device bytes per eval: the stateless wrapper
            # re-ships the WHOLE packed batch (node planes included)
            # every solve; the resident path ships only the ask side.
            # On a virtual CPU mesh "shipping" is a same-host memcpy,
            # so wall-clock understates this gap — the byte counters
            # are the platform-independent transport story.
            "stateless_bytes_per_eval": int(stateless_bytes),
            "resident_bytes_per_eval": int(
                resident_bytes / max(n_evals, 1)),
            "ship_reduction_x": round(
                stateless_bytes * n_evals / max(resident_bytes, 1), 1),
            "per_shard_hbm": wt["per_shard"],
            "ici": ici,
            "ici_within_bound": bool(
                ici["bytes_ici_per_wave"]
                <= ici["bound_candidate_keys"]),
            "measured": wt.get("measured"),
        }

        # ---- dcn_tier leg (ISSUE 8): two-tier hierarchical exchange
        # on a simulated host grouping, vs the flat PR-5 exchange.
        # Plain ShardedResidentSolver on the two-tier mesh: same
        # extraction semantics as the flat run (incl. the approx_max_k
        # window at large Np), so the parity spot check is exact ----
        if n_hosts > 1 and n_devices % n_hosts == 0:
            rs2 = ShardedResidentSolver(
                nodes, asks_for(probe_job),
                mesh=make_two_tier_mesh(n_hosts, n_devices),
                gp=1 << max(0, (gp_need - 1).bit_length()),
                kp=1 << max(0, (count - 1).bit_length()),
                max_waves=18, pallas="off")
            b2 = [rs2.pack_batch(asks_for(j)) for j in jobs]
            t_tiered = None
            for round_ in range(2):
                rs2.reset_usage()
                t0 = time.perf_counter()
                outs2 = []
                for b in range(NB):
                    outs2.append(rs2.solve_stream_async(
                        b2[b * epc:(b + 1) * epc]))
                jax.block_until_ready(outs2[-1])
                t_tiered = time.perf_counter() - t0
            # placement parity spot check vs the flat mesh run
            rs.reset_usage()
            rs2.reset_usage()
            c1, o1, _, st1 = rs.solve_stream(batches[:epc])
            c2, o2, _, st2 = rs2.solve_stream(b2[:epc])
            parity = bool(np.array_equal(o1, o2)
                          and np.array_equal(st1, st2)
                          and np.array_equal(np.where(o1, c1, -1),
                                             np.where(o2, c2, -1)))
            wt2 = rs2.wave_traffic(b2[:epc])
            dcn = wt2["dcn"]
            rec["dcn_tier"] = {
                "n_hosts": int(n_hosts),
                "chips_per_host": dcn["chips_per_host"],
                "tiered_wall_s": round(t_tiered, 4),
                "bytes_dcn_per_wave": dcn["bytes_dcn_total_per_wave"],
                "flat_dcn_per_wave": dcn["flat_dcn_total_per_wave"],
                "dcn_cut_vs_flat": round(dcn["dcn_cut_vs_flat"], 4),
                "dcn_within_quarter": bool(
                    dcn["dcn_cut_vs_flat"] <= 0.25),
                "bytes_ici_per_wave": dcn["bytes_ici_per_wave"],
                "placements_match_flat": parity,
            }

            # ---- kill-one-shard recovery-time probe (the elastic
            # solver: tile layout + fail/recover state machine) ----
            es = ElasticShardedResidentSolver(
                nodes, asks_for(probe_job),
                mesh=make_two_tier_mesh(n_hosts, n_devices),
                gp=1 << max(0, (gp_need - 1).bit_length()),
                kp=1 << max(0, (count - 1).bit_length()),
                max_waves=18, pallas="off")
            b2 = [es.pack_batch(asks_for(j)) for j in jobs]
            victim = es.n_shards - 1
            lost = es.fail_shard(victim)
            t0 = time.perf_counter()
            es.solve_stream(b2[:epc])          # degraded, fast path
            t_degraded = time.perf_counter() - t0
            rc = es.reshard_counters
            rec_bytes = es.recover()
            es.reset_usage()
            t0 = time.perf_counter()
            es.solve_stream(b2[:epc])
            t_recovered = time.perf_counter() - t0
            grown = es.grow_tiles(1)
            rec["recovery_probe"] = {
                "killed_shard": int(victim),
                "lost_tiles": len(lost),
                "degraded_solve_s": round(t_degraded, 4),
                "degraded_on_fast_path": rc["degraded_solves"] >= 1,
                "recovery_s": round(rc["last_recovery_s"], 4),
                "recovery_bytes": int(rec_bytes),
                "recovered_solve_s": round(t_recovered, 4),
                "grow_tiles": grown,
                "grow_bytes_measured": rc["last_reshard_bytes"],
            }
        out["configs"].append(rec)
    out["ok"] = all(c["ici_within_bound"] for c in out["configs"])
    out["dcn_ok"] = all(
        c["dcn_tier"]["dcn_within_quarter"]
        and c["dcn_tier"]["placements_match_flat"]
        for c in out["configs"] if "dcn_tier" in c)
    if write_detail:
        with open(os.path.join(REPO, "MULTICHIP_DETAIL.json"),
                  "w") as f:
            json.dump(out, f, indent=1)
    return out


# --------------- multi-region WAN federation phase (ISSUE 13) -------

def _region_queue_sim(arrivals, regions, svc, router=None,
                      watermark=None):
    """Deterministic FIFO queue simulation shared by the multiregion
    legs.  arrivals: [(t, home_region)] ascending; each region is one
    server with fixed per-eval service time `svc` (the measured
    device rate).  With a SpilloverRouter the router picks the region
    per arrival (backlogs fed via note_ready, shed lane drained as
    capacity returns); without one every eval runs in its home region
    and `watermark` backlogs are recorded as brownouts.  Returns
    (latencies, browned_regions, completed).  A router carrying a
    WanLatencyModel charges every cross-region hop its modeled
    (seeded, jittered) WAN delay before the eval reaches the remote
    queue — spillover is never free."""
    import collections
    comp = {r: collections.deque() for r in regions}
    last = {r: 0.0 for r in regions}
    lat, browned = [], set()

    def depth(r, t):
        dq = comp[r]
        while dq and dq[0] <= t:
            dq.popleft()
        return len(dq)

    def enqueue(r, t, t_arr):
        done = max(last[r], t) + svc
        last[r] = done
        comp[r].append(done)
        lat.append(done - t_arr)

    for t, home in arrivals:
        if router is None:
            if depth(home, t) >= watermark:
                browned.add(home)
            enqueue(home, t, t)
            continue
        for r in regions:
            router.region(r).note_ready(depth(r, t))
        for ev, r in router.drain_shed():
            enqueue(r, t + router.wan_delay(ev[1], r), ev[0])
        reg, _cause = router.route((t, home), home=home)
        if reg is not None:
            enqueue(reg, t + router.wan_delay(home, reg), t)
    # park-drain: anything the router shed completes once capacity
    # returns (never dropped)
    t = max(last.values())
    for _ in range(100_000):
        if router is None or not router.shed_depth():
            break
        t += svc
        for r in regions:
            router.region(r).note_ready(depth(r, t))
        for ev, r in router.drain_shed():
            enqueue(r, t + router.wan_delay(ev[1], r), ev[0])
    return lat, browned, len(lat)


def run_multiregion(n_devices=8, n_regions=4, n_nodes=None, n_evals=16,
                    count=64, evals_per_call=8, write_detail=True):
    """Multi-region WAN federation phase (ISSUE 13).

    Two legs.  (a) WAN exchange: CrossRegionResidentSolver places the
    same eval stream as a flat ShardedResidentSolver over the union
    fleet — placements must match exactly (the hierarchical candidate
    exchange is a transport optimisation, not a semantic change) —
    and wave_traffic's wan block reports the three-tier byte model
    with the `wan_cut_vs_flat <= 1/4` acceptance figure at bench
    scale.  (b) SLO spillover: a deterministic queue simulation
    parameterised by the measured device solve rate, driving skewed
    regional load (one hot region at ~1.4x its capacity) through
    three routing policies — region-isolated (stock semantics: the
    hot region browns out), SpilloverRouter (overflow to the
    cheapest sibling at SLO), and a balanced-load reference.  The
    acceptance bar: spillover's global p99 stays within 2x the
    balanced p99 while the isolated leg browns out, with zero evals
    lost and the shed-lane accounting intact.

    Self-provisions the virtual device platform like run_multichip;
    sizes default to 50k union nodes (NOMAD_TPU_MULTIREGION_NODES
    overrides).  The record merges into MULTICHIP_DETAIL.json under
    "multiregion"."""
    import importlib
    graft = importlib.import_module("__graft_entry__")
    n_devices, n_regions = graft._ensure_devices(n_devices, n_regions)
    import random

    import jax
    import numpy as np
    from nomad_tpu.parallel.federated import CrossRegionResidentSolver
    from nomad_tpu.parallel.sharded import ShardedResidentSolver
    from nomad_tpu.server.serving import SpilloverRouter, WanLatencyModel
    from nomad_tpu.solver.tensorize import Tensorizer
    from nomad_tpu.utils.compile_cache import cache_entries

    if n_nodes is None:
        n_nodes = int(os.environ.get("NOMAD_TPU_MULTIREGION_NODES",
                                     "50000"))
    per_region = n_nodes // n_regions
    nodes = make_nodes(per_region * n_regions)
    region_nodes = [nodes[r * per_region:(r + 1) * per_region]
                    for r in range(n_regions)]
    probe_job = make_job(2, 0, count)
    gp_need = len({Tensorizer.ask_signature(a)
                   for a in asks_for(probe_job)})
    gp = 1 << max(0, (gp_need - 1).bit_length())
    kp = 1 << max(0, (count - 1).bit_length())
    epc = min(evals_per_call, n_evals)
    NB = -(-n_evals // epc)
    out = {"phase": "multiregion", "n_devices": int(n_devices),
           "n_regions": int(n_regions), "skipped": False,
           "backend": jax.default_backend()}

    # ---- WAN leg: cross-region scheduling vs the flat union mesh ---
    cache0 = cache_entries()
    cr = CrossRegionResidentSolver(
        region_nodes, asks_for(probe_job), n_devices=n_devices,
        gp=gp, kp=kp, max_waves=18, pallas="off")
    jobs = [make_job(2, e, count) for e in range(n_evals)]
    batches = [cr.pack_batch(asks_for(j)) for j in jobs]
    assert all(pb is not None for pb in batches)
    t_wan = None
    for _round in range(2):                      # round 0 compiles
        cr.reset_usage()
        t0 = time.perf_counter()
        outs = [cr.solve_stream_async(batches[b * epc:(b + 1) * epc])
                for b in range(NB)]
        jax.block_until_ready(outs[-1])
        t_wan = time.perf_counter() - t0
    cache_rep = _cache_report(cache0)

    rs = ShardedResidentSolver(nodes, asks_for(probe_job),
                               n_devices=n_devices, gp=gp, kp=kp,
                               max_waves=18, pallas="off")
    bf = [rs.pack_batch(asks_for(j)) for j in jobs]
    t_flat = None
    for _round in range(2):
        rs.reset_usage()
        t0 = time.perf_counter()
        outs = [rs.solve_stream_async(bf[b * epc:(b + 1) * epc])
                for b in range(NB)]
        jax.block_until_ready(outs[-1])
        t_flat = time.perf_counter() - t0
    # placement parity spot check: the WAN exchange must be invisible
    cr.reset_usage()
    rs.reset_usage()
    c1, o1, _, st1 = cr.solve_stream(batches[:epc])
    c2, o2, _, st2 = rs.solve_stream(bf[:epc])
    parity = bool(np.array_equal(o1, o2)
                  and np.array_equal(st1, st2)
                  and np.array_equal(np.where(o1, c1, -1),
                                     np.where(o2, c2, -1)))
    wt = cr.wave_traffic(batches[:epc])
    wan = wt["wan"]
    measured = wt["measured"]
    out["wan"] = {
        "n_nodes": int(n_nodes),
        "np_padded": int(cr.template.avail.shape[0]),
        "shards_per_region": wan["shards_per_region"],
        "wan_resident_s": round(t_wan, 4),
        "flat_resident_s": round(t_flat, 4),
        "placements_match_flat": parity,
        "bytes_wan_per_wave": wan["bytes_wan_total_per_wave"],
        "flat_wan_per_wave": wan["flat_wan_total_per_wave"],
        "wan_cut_vs_flat": round(wan["wan_cut_vs_flat"], 4),
        "wan_within_quarter": bool(wan["wan_cut_vs_flat"] <= 0.25),
        "model": wan,
        "measured": measured,
        "compile_cache": cache_rep,
    }

    # ---- spillover leg: skewed load through three routing policies -
    # measured per-eval device rate parameterises the queue sim; the
    # p99 RATIOS are scale-free (all times are multiples of svc), so
    # the acceptance figure is deterministic under the fixed seed
    svc = max(t_wan / max(n_evals, 1), 1e-6)
    regions = [f"r{i}" for i in range(n_regions)]
    rng = random.Random(13)
    n_arr = 400
    lam = 2.0 / svc                      # total load = 50% of fleet
    t_a, arrivals = 0.0, []
    for _ in range(n_arr):
        t_a += rng.expovariate(lam)
        hot = rng.random() < 0.7         # ~1.4x the hot region's rate
        arrivals.append((t_a, regions[0] if hot
                         else regions[1 + rng.randrange(
                             n_regions - 1)]))
    balanced = [(t, regions[i % n_regions])
                for i, (t, _h) in enumerate(arrivals)]
    mp_small = 64                        # smoke-scale watermark
    lat_iso, browned, done_iso = _region_queue_sim(
        arrivals, regions, svc, watermark=int(0.75 * mp_small))

    # modeled WAN latency (ISSUE 14): every cross-region hop costs a
    # per-pair base (here 0.5 svc — the scale-free knob) with seeded
    # jitter; routing math subtracts the jitter-free expectation from
    # the SLO budget so remote capacity is never judged free
    wan_base = 0.5 * svc

    def _wan_model():
        return WanLatencyModel(default_s=wan_base, jitter=0.25)

    def _router():
        r = SpilloverRouter(
            regions={name: 1.0 + 0.1 * i
                     for i, name in enumerate(regions)},
            overrides={"slo_budget_s": 2.5 * svc, "spill_margin": 1.0,
                       "max_pending": mp_small},
            wan_model=_wan_model())
        for name in regions:
            for b in (1, 2, 4, 8, 16, 32, 64):
                r.note_solve(name, b, b * svc)
        return r

    router = _router()
    lat_sp, _b, done_sp = _region_queue_sim(arrivals, regions, svc,
                                            router=router)
    router_bal = _router()
    lat_bal, _b, done_bal = _region_queue_sim(balanced, regions, svc,
                                              router=router_bal)
    p99_iso = pct(sorted(lat_iso), 0.99)
    p99_sp = pct(sorted(lat_sp), 0.99)
    p99_bal = pct(sorted(lat_bal), 0.99)
    stats = router.stats()
    out["spillover"] = {
        "n_arrivals": n_arr,
        "svc_per_eval_s": round(svc, 6),
        "hot_region_share": 0.7,
        "isolated_browned_regions": sorted(browned),
        "p99_isolated_s": round(p99_iso, 4),
        "p99_spillover_s": round(p99_sp, 4),
        "p99_balanced_s": round(p99_bal, 4),
        "p99_vs_balanced": round(p99_sp / max(p99_bal, 1e-9), 3),
        "evals_lost": (n_arr - done_sp) + (n_arr - done_iso)
        + (n_arr - done_bal),
        "shed_lane_depth_end": router.shed_depth(),
        "routed": stats["routed"],
        "wan": {"base_s": round(wan_base, 6),
                "base_vs_svc": 0.5, "jitter": 0.25,
                **stats.get("wan", {})},
        "shed_accounting_intact": (
            stats["routed"]["shed"] == stats["routed"]["readmitted"]
            and router.shed_depth() == 0),
        "spill_ok": bool(p99_sp <= 2 * p99_bal and browned
                         and done_sp == n_arr),
    }
    out["ok"] = bool(out["wan"]["wan_within_quarter"] and parity
                     and out["spillover"]["spill_ok"]
                     and out["spillover"]["evals_lost"] == 0
                     and out["spillover"]["shed_accounting_intact"])
    if write_detail:
        path = os.path.join(REPO, "MULTICHIP_DETAIL.json")
        detail = {}
        if os.path.exists(path):
            try:
                with open(path) as f:
                    detail = json.load(f)
            except (OSError, json.JSONDecodeError):
                detail = {}
        detail["multiregion"] = out
        with open(path, "w") as f:
            json.dump(detail, f, indent=1)
    return out


# ------------------- chaos storm phase (ISSUE 14) -------------------

def run_chaos(n_devices=8, n_regions=4, write_detail=True, seed=14):
    """Chaos plane phase (ISSUE 14): a seeded compound fault storm —
    shard kills + region partitions + gossip flaps + stuck/slow/
    poisoned device solves — replayed through the real recovery hooks
    at config-3 load, with the invariant harness running continuously.

    Three sub-records:

      * ``watchdog`` — the acceptance failover arc: a stuck device
        solve (injected sleep past the deadline) answers from the
        bit-identical host twin with PLACEMENT-IDENTICAL results,
        quarantines the device, keeps answering from the twin while
        the backoff pends, and recovers to the device fast path on a
        clean probe — all visible in the mesh event log;
      * ``corruption`` — a delta-row corruption (device planes diverge
        from the raft-fed host template) is caught by the plane
        checksum invariant and healed by a clean re-apply;
      * ``storm`` — a fault-free leg vs the storm leg over identical
        eval streams: per-step latencies (p50/p99), zero lost evals,
        zero invariant violations, post-storm placements bit-identical
        to the fault-free reference, recovery times, and the
        watchdog-lane fast-path retention.

    Acceptance: zero violations, zero lost evals, storm p99 <= 3x the
    fault-free p99, and the watchdog failover demonstrated.  Merges
    into BENCH_DETAIL.json under "chaos"."""
    import importlib
    graft = importlib.import_module("__graft_entry__")
    n_devices, n_regions = graft._ensure_devices(n_devices, n_regions)
    import numpy as np
    from nomad_tpu import mock
    from nomad_tpu.chaos import (ChaosSupervisor, FaultPlan,
                                 InvariantHarness, global_injections)
    from nomad_tpu.parallel.federated import CrossRegionResidentSolver
    from nomad_tpu.parallel.sharded import ElasticMeshSupervisor
    from nomad_tpu.server.eval_broker import EvalBroker
    from nomad_tpu.server.serving import AdmissionController
    from nomad_tpu.solver.solve import _run_kernel
    from nomad_tpu.solver.tensorize import ClusterDelta, Tensorizer
    from nomad_tpu.solver.watchdog import global_watchdog
    from nomad_tpu.utils.metrics import global_metrics as _m
    from nomad_tpu.utils.tracing import global_mesh_events

    p3 = CONFIGS[3]
    n_nodes = int(os.environ.get("NOMAD_TPU_CHAOS_NODES",
                                 p3["n_nodes"]))
    resident = int(os.environ.get(
        "NOMAD_TPU_CHAOS_RESIDENT",
        p3["resident"] * n_nodes // p3["n_nodes"]))
    count = p3["count"]
    horizon = int(os.environ.get("NOMAD_TPU_CHAOS_HORIZON", "36"))
    per_region = n_nodes // n_regions
    nodes = make_nodes(per_region * n_regions)
    region_nodes = [nodes[r * per_region:(r + 1) * per_region]
                    for r in range(n_regions)]
    probe_job = make_job(3, 0, count)
    gp_need = len({Tensorizer.ask_signature(a)
                   for a in asks_for(probe_job)})
    gp = 1 << max(0, (gp_need - 1).bit_length())
    kp = 1 << max(0, (count - 1).bit_length())
    cr = CrossRegionResidentSolver(
        region_nodes, asks_for(probe_job), n_devices=n_devices,
        gp=gp, kp=kp, max_waves=18, pallas="off")
    used0 = resident_used0(cr.template, per_region * n_regions,
                           resident)
    msup = ElasticMeshSupervisor(cr.solver)
    msup.register_host("host-r1", 1)
    jobs = [make_job(3, e, count) for e in range(8)]
    batches = [cr.pack_batch(asks_for(j)) for j in jobs]
    # the watchdog device-dispatch lane: a standalone full pack (node
    # planes included — resident batches carry only the eval tensors)
    # over a modest node subset, so the host twin answers fast when
    # the watchdog fails over
    pb_wd = Tensorizer().pack(nodes[:256], asks_for(jobs[0]))
    import jax
    out = {"phase": "chaos", "seed": int(seed),
           "n_nodes": int(per_region * n_regions),
           "n_regions": int(n_regions), "resident": int(resident),
           "horizon": int(horizon),
           "backend": jax.default_backend()}

    # the storm schedule is generated up front (it is the experiment's
    # seed-addressable identity), which also lets the warmup below
    # compile every degraded-width variant the storm will actually
    # drive — the storm leg's p99 then measures fault HANDLING
    # (re-ship, failover, rebuild), not first-call compilation
    rates = {"shard_kill": 0.06, "region_kill": 0.06,
             "gossip_flap": 0.08, "stuck_solve": 0.05,
             "slow_solve": 0.08, "poison_solve": 0.05}
    plan = FaultPlan.generate(seed, horizon, rates,
                              shards=cr.solver.n_shards,
                              regions=cr.region_names,
                              members=["host-r1"])

    cr.reset_usage(used0=used0)
    cr.solve_stream([batches[0]])
    warm_kills = [("shard", 1)]         # the gossip-flap member's shard
    for ev in plan.events:
        if ev.kind == "shard_kill":
            warm_kills.append(
                ("shard", int(ev.target or 0) % cr.solver.n_shards))
        elif ev.kind == "region_kill":
            warm_kills.append(("region", ev.target))
    for wkind, wtgt in dict.fromkeys(warm_kills):
        if wkind == "shard":
            cr.solver.fail_shard(wtgt)
        else:
            cr.fail_region_shard(wtgt)
        cr.reset_usage(used0=used0)
        cr.solve_stream([batches[0]])
        cr.solver.recover()
        cr.reset_usage(used0=used0)
        cr.solve_stream([batches[0]])
    _run_kernel(pb_wd, host_mode="never")

    # ---- watchdog failover arc (the acceptance demo) ----
    deadline = float(os.environ.get("NOMAD_TPU_SOLVE_DEADLINE_S",
                                    "0.5"))
    global_watchdog.deadline_s = deadline
    global_watchdog.quarantined = False
    global_watchdog._failures = 0
    base_choice = np.asarray(
        _run_kernel(pb_wd, host_mode="never").choice)
    global_injections.arm("device_solve", "sleep", budget=1,
                          sleep_s=4.0 * deadline)
    t0 = time.perf_counter()
    stuck = np.asarray(_run_kernel(pb_wd, host_mode="never").choice)
    failover_s = time.perf_counter() - t0
    quarantined = bool(global_watchdog.quarantined)
    twin = np.asarray(_run_kernel(pb_wd, host_mode="never").choice)
    global_watchdog._probe_at = 0.0            # backoff elapsed
    probed = np.asarray(_run_kernel(pb_wd, host_mode="never").choice)
    out["watchdog"] = {
        "deadline_s": deadline,
        "failover_s": round(failover_s, 4),
        "failover_placements_identical": bool(
            np.array_equal(stuck, base_choice)),
        "quarantined_after_failover": quarantined,
        "quarantine_twin_identical": bool(
            np.array_equal(twin, base_choice)),
        "recovered_to_device": bool(not global_watchdog.quarantined),
        "probe_placements_identical": bool(
            np.array_equal(probed, base_choice)),
        "failover_in_event_log": bool(global_mesh_events.events(
            kind="watchdog.failover", limit=4096)),
        "recovery_in_event_log": bool(global_mesh_events.events(
            kind="watchdog.recovered", limit=4096)),
    }
    out["watchdog"]["ok"] = all(
        v for k, v in out["watchdog"].items()
        if isinstance(v, bool))

    # ---- delta-row corruption: detected, then healed ----
    hc = InvariantHarness()
    clean_before = hc.check_plane_checksums(cr.solver)
    victim = nodes[7]
    victim.node_resources.cpu += 1
    victim.compute_class()
    d = ClusterDelta()
    d.upsert_nodes.append(victim)
    global_injections.arm("delta_row", "mutate", budget=1, rows=2)
    corr_path = cr.apply_delta(d)
    detected = not hc.check_plane_checksums(cr.solver)
    d2 = ClusterDelta()
    d2.upsert_nodes.append(victim)         # clean re-apply heals
    cr.apply_delta(d2)
    healed = InvariantHarness().check_plane_checksums(cr.solver)
    out["corruption"] = {"apply_path": corr_path,
                         "clean_before": bool(clean_before),
                         "detected": bool(detected),
                         "healed_by_reapply": bool(healed)}

    # ---- fault-free leg vs the compound storm leg ----
    # each step serves SPS fleet batches + the watchdog lane + an
    # eval-broker burst: the per-step cost a client sees at config-3
    # load, against which a transition's one-time re-ship/failover
    # cost amortizes (exactly how a real serving tier absorbs it)
    SPS = 4                             # fleet solves per step

    def run_leg(supervisor):
        broker = EvalBroker(initial_nack_delay_s=0.01)
        broker.set_enabled(True)
        adm = AdmissionController(max_pending=4096,
                                  protect_priority=101,
                                  brownout_high=0.9,
                                  brownout_low=0.5,
                                  brownout_after_s=0.001,
                                  ns_rate=1e9, ns_burst=1e9)
        harness = InvariantHarness()
        dbg = os.environ.get("NOMAD_TPU_CHAOS_DEBUG")
        lat, recovery_s = [], []
        t_kill = None
        for step in range(horizon):
            t0 = time.perf_counter()
            if supervisor is not None:
                for e in supervisor.advance(step):
                    if e.kind in ("shard_kill", "region_kill"):
                        t_kill = time.perf_counter()
            t_adv = time.perf_counter()
            for i in range(SPS):
                ev = mock.eval_(job_id=f"job-{step}-{i}")
                harness.note_enqueued(ev.id)
                if adm.offer(ev, broker.ready_count()):
                    broker.enqueue(ev)
                else:
                    harness.note_outcome(ev.id, "shed")
            t_ev = time.perf_counter()
            for b in range(SPS):
                pb = batches[(step * SPS + b) % len(batches)]
                cr.reset_usage(used0=used0)
                choice, ok, _sc, _st = cr.solve_stream([pb])
            t_solve = time.perf_counter()
            res = _run_kernel(pb_wd, host_mode="never")
            t_lane = time.perf_counter()
            wd_choice = np.asarray(res.choice)
            for pi in range(min(4, pb_wd.n_place)):
                harness.note_placement(
                    f"s{step}-p{pi}", str(int(wd_choice[pi, 0])))
            while True:
                got, tok = broker.dequeue(["service"], 0.0)
                if got is None:
                    break
                broker.ack(got.id, tok)
                harness.note_outcome(got.id, "acked")
            if supervisor is not None and t_kill is not None \
                    and cr.mesh_state == "healthy":
                # the storm (or a gossip rejoin) recovered the mesh
                recovery_s.append(time.perf_counter() - t_kill)
                t_kill = None
            t_drain = time.perf_counter()
            lat.append(time.perf_counter() - t0)
            # the continuously-running invariant harness
            harness.check_eval_conservation(broker)
            harness.check_no_double_placement()
            harness.check_plane_checksums(cr.solver)
            harness.check_shed_accounting(admission=adm)
            if dbg:
                print(f"step {step:2d} total {lat[-1]:.3f} "
                      f"adv {t_adv - t0:.3f} "
                      f"evq {t_ev - t_adv:.3f} "
                      f"solve {t_solve - t_ev:.3f} "
                      f"lane {t_lane - t_solve:.3f} "
                      f"drain {t_drain - t_lane:.3f} "
                      f"chk {time.perf_counter() - t_drain:.3f}",
                      file=sys.stderr)
        if cr.mesh_state == "degraded":       # final quiesce
            t0 = time.perf_counter()
            cr.solver.recover()
            recovery_s.append(time.perf_counter()
                              - (t_kill or t0))
        harness.check_plane_checksums(cr.solver)
        cr.reset_usage(used0=used0)
        c, o, _s, st = cr.solve_stream([batches[0]])
        final = (np.where(o, c, -1).copy(), np.asarray(st).copy())
        return lat, harness, recovery_s, final

    c0 = _m.dump()["counters"]
    wd_host0 = (c0.get("watchdog.host_failover", 0)
                + c0.get("watchdog.host_quarantine", 0))
    lat_ff, h_ff, _rec, final_ff = run_leg(None)
    sup = ChaosSupervisor(plan, federated=cr, mesh_supervisor=msup,
                          injections=global_injections,
                          watchdog_deadline_s=deadline)
    lat_st, h_st, recovery_s, final_st = run_leg(sup)
    c1 = _m.dump()["counters"]
    wd_host1 = (c1.get("watchdog.host_failover", 0)
                + c1.get("watchdog.host_quarantine", 0))
    host_answers = wd_host1 - wd_host0
    p99_ff = pct(sorted(lat_ff), 0.99)
    p99_st = pct(sorted(lat_st), 0.99)
    rep = sup.report()
    out["storm"] = {
        "plan": rep,
        "evals_per_step": SPS,
        "solves_per_step": SPS,
        "p50_fault_free_s": round(pct(sorted(lat_ff), 0.50), 4),
        "p99_fault_free_s": round(p99_ff, 4),
        "p50_storm_s": round(pct(sorted(lat_st), 0.50), 4),
        "p99_storm_s": round(p99_st, 4),
        "p99_ratio": round(p99_st / max(p99_ff, 1e-9), 3),
        "evals_lost": 0 if (h_ff.ok and h_st.ok) else -1,
        "invariants_fault_free": h_ff.report(),
        "invariants_storm": h_st.report(),
        "recovery_s": [round(r, 4) for r in recovery_s],
        "step_lat_fault_free_s": [round(v, 3) for v in lat_ff],
        "step_lat_storm_s": [round(v, 3) for v in lat_st],
        "watchdog_host_answers": int(host_answers),
        "fast_path_retention": round(
            1.0 - host_answers / (2.0 * horizon), 4),
        "post_storm_placements_match_fault_free": bool(
            np.array_equal(final_st[0], final_ff[0])
            and np.array_equal(final_st[1], final_ff[1])),
        "chaos_events_logged": len(global_mesh_events.events(
            limit=4096, kind=None)),
    }
    global_injections.reset()
    global_watchdog.deadline_s = None
    out["ok"] = bool(
        out["watchdog"]["ok"]
        and out["corruption"]["detected"]
        and out["corruption"]["healed_by_reapply"]
        and h_ff.ok and h_st.ok
        and out["storm"]["p99_ratio"] <= 3.0
        and out["storm"]["post_storm_placements_match_fault_free"])
    if write_detail:
        path = os.path.join(REPO, "BENCH_DETAIL.json")
        detail = {}
        if os.path.exists(path):
            try:
                with open(path) as f:
                    detail = json.load(f)
            except (OSError, json.JSONDecodeError):
                detail = {}
        detail["chaos"] = out
        with open(path, "w") as f:
            json.dump(detail, f, indent=1)
    return out


def run_ours_latency(config, n_nodes, n_evals, count, resident):
    """Single-eval-per-call mode: what one interactive eval costs.

    The production worker picks the solve path by cluster/batch size
    (solver/host.py prefer_host — SURVEY §7.3's latency fallback): a
    small cluster solves with the numpy twin of the kernel in-process
    (identical placements, differential-tested), so a singleton eval
    never pays a device round trip; big clusters keep the device path.
    This benchmark makes the same pick."""
    import numpy as np
    from nomad_tpu.solver.host import HostResidentSolver, prefer_host
    from nomad_tpu.solver.resident import ResidentSolver, STATUS_RETRY

    nodes = make_nodes(n_nodes, devices=config == 4)
    from nomad_tpu.utils.compile_cache import cache_entries
    cache0 = cache_entries()
    t0 = time.perf_counter()
    probe_job = make_job(config, 0, count)
    gp_need = len(probe_job.task_groups)
    kp_need = count
    gp = 1 << max(0, (gp_need - 1).bit_length())
    kp = 1 << max(0, (kp_need - 1).bit_length())
    host = prefer_host(1 << max(0, (n_nodes - 1).bit_length()),
                       gp_need, kp_need)
    if host:
        # no compile-variant reuse to protect on host: exact-size pads
        rs = HostResidentSolver(nodes, asks_for(probe_job),
                                gp=gp_need, kp=kp_need)
    else:
        rs = ResidentSolver(nodes, asks_for(probe_job), gp=gp, kp=kp)
    rs.reset_usage(used0=resident_used0(rs.template, n_nodes, resident))
    jobs = [make_job(config, e, count) for e in range(n_evals)]
    warm = rs.pack_batch(asks_for(jobs[0]))
    rs.solve_stream([warm], seeds=[1])
    rs.reset_usage(used0=resident_used0(rs.template, n_nodes, resident))
    startup_s = time.perf_counter() - t0

    latencies = []
    placed = failed = retried = unresolved = 0
    n_calls = 0
    t_start = time.perf_counter()
    for e, job in enumerate(jobs):
        t_call = time.perf_counter()
        pack = getattr(rs, "pack_batch_cached", rs.pack_batch)
        pb = pack(asks_for(job))
        n_calls += 0 if host else 1     # host mode never leaves the CPU
        _, ok, _, status = rs.solve_stream([pb], seeds=[e + 1])
        placed += int(ok[0, :pb.n_place, 0].sum())
        failed += int((status[0, :pb.n_place] == 0).sum())
        unresolved += int((status[0, :pb.n_place] == STATUS_RETRY).sum())
        latencies.append(time.perf_counter() - t_call)
    elapsed = time.perf_counter() - t_start
    lat = latency_summary(latencies)

    return {
        "engine": ("nomad-tpu host-solver per-eval (latency mode)"
                   if host else
                   "nomad-tpu per-eval device calls (latency mode)"),
        "evals": n_evals, "placements": placed, "failed": failed,
        "retried": retried, "unresolved": unresolved,
        "n_device_calls": n_calls,
        "compile_cache": _cache_report(cache0),
        "elapsed_s": round(elapsed, 4),
        "startup_s": round(startup_s, 2),
        "evals_per_sec": round(n_evals / elapsed, 1),
        "placements_per_sec": round(placed / elapsed, 1),
        "p50_ms": lat["p50_ms"], "p99_ms": lat["p99_ms"],
        "nodes_scored_per_placement": n_nodes,
    }


def run_ours_federated(n_regions, n_nodes, n_evals, count, resident,
                       evals_per_call=128):
    """Config 5: FederatedResidentSolver — every region keeps its own
    node universe and usage tensors, but all regions' stream steps fuse
    into vmapped [R]-stacked device calls (parallel/federated.py): the
    whole federation pays ONE result-fetch round trip.  Steps dispatch
    pipelined (pack step b+1 while step b solves); on a TPU pod the
    region axis shards across chips with no collectives at all."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from nomad_tpu.parallel.federated import FederatedResidentSolver
    from nomad_tpu.solver.kernel import MERGED_GP_MAX
    from nomad_tpu.solver.resident import STATUS_RETRY

    epc = min(evals_per_call, n_evals)
    NB = -(-n_evals // epc)
    probe_job = make_job(5, 0, count)
    # scenario generation (cluster + jobs) happens before the startup
    # clock — parity with run_ours
    region_universe = make_nodes(n_nodes)
    all_jobs = [[make_job(5, r * n_evals + e, count)
                 for e in range(n_evals)] for r in range(n_regions)]
    from nomad_tpu.utils.compile_cache import cache_entries
    cache0 = cache_entries()
    t0 = time.perf_counter()
    # one shared universe across regions: the federated solver packs
    # it once (usage tensors stay per-region).  gp sized to the real
    # distinct-signature count (see run_ours) — config 5's merged
    # stream needs 1 row, not MERGED_GP_MAX
    from nomad_tpu.solver.tensorize import Tensorizer
    gp_need = len({Tensorizer.ask_signature(a)
                   for a in asks_for(probe_job)})
    fed = FederatedResidentSolver(
        [region_universe] * n_regions,
        asks_for(probe_job), gp=1 << max(0, (gp_need - 1).bit_length()),
        kp=1 << max(0, (count * epc - 1).bit_length()), max_waves=18)
    used0_region = resident_used0(fed.solvers[0].template, n_nodes,
                                  resident)
    used0 = np.stack([used0_region] * n_regions)

    # pipelined per-step dispatch (see run_ours): pack step b for all
    # regions, dispatch that one [R]-vmapped step as a chained call,
    # pack step b+1 while it solves; ONE concatenated fetch at the end
    import jax
    wasks, _wk = fed.merge_asks(0, sum(
        (asks_for(make_job(5, 9000 + e, count)) for e in range(epc)), []))
    warm = fed.pack_batch(0, wasks)
    warm.job_keys = None
    concat_jit = jax.jit(lambda *xs: jnp.concatenate(xs))
    wouts = [fed.solve_stream_async([[warm]] * n_regions,
                                    seeds=[[b + 1]] * n_regions)
             for b in range(NB)]
    np.asarray(concat_jit(*wouts))
    fed.reset_usage(used0=used0)
    startup_s = time.perf_counter() - t0

    t_start = time.perf_counter()
    batches = [[] for _ in range(n_regions)]
    outs = []
    pack_s = dispatch_s = 0.0
    for b in range(NB):
        i = b * epc
        step = []
        t_p = time.perf_counter()
        for r in range(n_regions):
            masks, mkeys = fed.merge_asks(r, sum(
                (asks_for(j) for j in all_jobs[r][i:i + epc]), []))
            pb = fed.pack_batch_cached(r, masks, job_keys=mkeys)
            batches[r].append(pb)
            step.append([pb])
        t_d = time.perf_counter()
        outs.append(fed.solve_stream_async(
            step, seeds=[[r * NB + b + 1] for r in range(n_regions)]))
        t_e = time.perf_counter()
        pack_s += t_d - t_p
        dispatch_s += t_e - t_d
    packed = np.asarray(concat_jit(*outs))            # ONE fetch
    elapsed = time.perf_counter() - t_start
    status = packed[:, :, :, -1].astype(np.int32)     # [NB, R, K]

    # steady-state delta waves: the same region-fused steps
    # re-dispatched — the step-level device cache ships nothing
    n_steady = min(4, NB)
    t_s = time.perf_counter()
    souts = [fed.solve_stream_async(
        [[batches[r][b]] for r in range(n_regions)],
        seeds=[[9000 + r * NB + b] for r in range(n_regions)])
        for b in range(n_steady)]
    t_sd = time.perf_counter()
    np.asarray(concat_jit(*souts) if n_steady > 1 else souts[0])
    main_pd = (pack_s + dispatch_s) / max(NB, 1)
    steady_pd = (t_sd - t_s) / n_steady
    steady = {
        "waves": n_steady,
        "steady_pack_dispatch_ms_per_wave": round(1000 * steady_pd, 3),
        "first_pass_pack_dispatch_ms_per_wave": round(1000 * main_pd, 3),
        "pack_dispatch_reduction": round(main_pd / max(steady_pd, 1e-9),
                                         1),
        "elapsed_s": round(time.perf_counter() - t_s, 4),
    }

    placed = failed = unresolved = 0
    for r in range(n_regions):
        for b, pb in enumerate(batches[r]):
            st = status[b, r, :pb.n_place]
            placed += int((st == 1).sum())
            failed += int((st == 0).sum())
            unresolved += int((st == STATUS_RETRY).sum())
    total_evals = n_regions * n_evals
    return {
        "engine": f"nomad-tpu federated stream x{n_regions} regions, "
                  "region-fused device calls",
        "evals": total_evals, "placements": placed, "failed": failed,
        "retried": 0, "unresolved": unresolved,
        "n_device_calls": 1,
        "breakdown_ms": {
            "pack": round(1000 * pack_s, 1),
            "dispatch": round(1000 * dispatch_s, 1),
        },
        "steady_state": steady,
        "compile_cache": _cache_report(cache0),
        "elapsed_s": round(elapsed, 4),
        "startup_s": round(startup_s, 2),
        "evals_per_sec": round(total_evals / elapsed, 1),
        "placements_per_sec": round(placed / elapsed, 1),
        # single fused call: every eval completes with the one fetch
        **latency_summary([elapsed]),
        "nodes_scored_per_placement": n_nodes,
    }


# ---------------- denominator: stock C++ engine ----------------------

def ensure_stock_engine():
    if (not os.path.exists(STOCK_BIN)
            or os.path.getmtime(STOCK_BIN) < os.path.getmtime(STOCK_SRC)):
        subprocess.run(["g++", "-O2", "-std=c++17", "-o", STOCK_BIN,
                        STOCK_SRC], check=True)


def run_stock(config, n_nodes, n_evals, count, resident, gen_seed=0):
    ensure_stock_engine()
    out = subprocess.run(
        [STOCK_BIN, str(config), str(n_nodes), str(n_evals), str(count),
         str(resident), str(gen_seed)],
        check=True, capture_output=True, text=True).stdout
    return json.loads(out)


# ---------------- configs ----------------

CONFIGS = {
    # n_evals sizes each steady-state workload to roughly 60-70% of the
    # cluster's REMAINING capacity: long enough that fixed costs
    # amortize on both engines, short of the pathological full-cluster
    # regime where every placement fails.  Configs 4 and 5 carry the
    # same resident-alloc load as the others (BASELINE measures loaded
    # 10K-node clusters, not empty ones); both engines see identical
    # generated clusters either way.
    1: dict(n_nodes=100, n_evals=12, count=100, resident=0),
    2: dict(n_nodes=10_000, n_evals=1536, count=64, resident=50_000),
    3: dict(n_nodes=10_000, n_evals=896, count=64, resident=100_000),
    4: dict(n_nodes=10_000, n_evals=1536, count=16, resident=50_000),
    5: dict(n_nodes=10_000, n_evals=768, count=64, resident=50_000),
}


def run_config(config):
    import gc
    p = CONFIGS[config]
    # best-of-3 on both engines — identical treatment on both sides
    if config == 1:
        runner = lambda: run_ours_latency(config, **p)  # noqa: E731
    elif config == 5:
        runner = lambda: run_ours_federated(4, **p)     # noqa: E731
    else:
        runner = lambda: run_ours(config, **p)          # noqa: E731

    def one_trial():
        gc.collect()          # drop prior trials' device buffers
        return runner()

    trials = [one_trial() for _ in range(3)]
    ours = min(trials, key=lambda r: r["elapsed_s"])
    # startup and elapsed are independent samples: trial 1 pays the
    # one-time device program load (cold attach), later trials restart
    # against the already-loaded program (the failover-relevant cost).
    # Record both.
    ours["startup_s"] = min(t["startup_s"] for t in trials)
    ours["startup_cold_s"] = max(t["startup_s"] for t in trials)
    stock = min((run_stock(config, **p) for _ in range(3)),
                key=lambda r: r["elapsed_s"])
    ratio_p = (ours["placements_per_sec"] / stock["placements_per_sec"]
               if stock["placements_per_sec"] else float("inf"))
    ratio_e = (ours["evals_per_sec"] / stock["evals_per_sec"]
               if stock["evals_per_sec"] else float("inf"))
    return {"config": config, "params": p, "ours": ours, "stock": stock,
            "ratio_placements": round(ratio_p, 3),
            "ratio_evals": round(ratio_e, 3)}


def run_quality_duel(config=3, n_nodes=512, count=64, load=1.15,
                     gen_seed=0):
    """Pack-to-capacity: same over-subscribed workload on both engines;
    the engine with better bin-packing places more before exhaustion.
    Stock ranks max(2, log2 N) sampled nodes per placement; the solve
    scores all N. Config 3's mixed ask sizes (400-850 cpu) make
    fragmentation matter."""
    # capacity estimate per config shape: cpu-bound for plain/mixed
    # asks, device-bound for config 4 (1 device/placement, 8 per
    # device-bearing node, every 2nd node)
    if config == 4:
        cap = (n_nodes // 2) * 8
    else:
        avg_ask = 625 if config == 3 else 400
        cap = int(n_nodes * (7500 / avg_ask))
    n_evals = max(1, int(cap * load) // count)
    # quality mode: one eval per call, exact deterministic scoring (the
    # production single-eval path) - no throughput-mode jitter/offsets
    ours = run_ours(config, n_nodes=n_nodes, n_evals=n_evals,
                    count=count, resident=0, evals_per_call=1,
                    exact=True, gen_seed=gen_seed)
    stock = run_stock(config, n_nodes=n_nodes, n_evals=n_evals,
                      count=count, resident=0, gen_seed=gen_seed)
    return {
        "config": config, "load": load, "gen_seed": gen_seed,
        "workload_placements": n_evals * count,
        "capacity_estimate": cap,
        "ours_placed": ours["placements"],
        "stock_placed": stock["placements"],
        "placed_ratio": round(
            ours["placements"] / max(stock["placements"], 1), 4),
    }


def run_quality_sweep(seeds=(0, 1, 2, 3, 4)):
    """Multi-seed, multi-shape, multi-load pack-to-capacity sweep
    (VERDICT r4 item 3: one seed/one config is a tie, not a win).
    Returns per-duel records + mean/min placed_ratio."""
    duels = []
    for config in (2, 3, 4):
        for load in (0.95, 1.15):
            for seed in seeds:
                duels.append(run_quality_duel(
                    config=config, load=load, gen_seed=seed))
                sys.stderr.write(
                    f"quality duel config={config} load={load} "
                    f"seed={seed}: {duels[-1]['placed_ratio']}\n")
    ratios = [d["placed_ratio"] for d in duels]
    return {
        "duels": duels,
        "n": len(duels),
        "mean_placed_ratio": round(sum(ratios) / len(ratios), 4),
        "min_placed_ratio": min(ratios),
        "max_placed_ratio": max(ratios),
    }


# ---------------- overcommit: in-kernel preemption (ISSUE 7) --------

def _oc_fill_job(i, rng):
    """A low-priority background job for the overcommit fill tier."""
    from nomad_tpu import mock
    job = mock.job(priority=int(rng.choice([5, 10, 20, 30, 45])))
    job.id = f"fill-{i}"
    job.name = job.id
    job.datacenters = [f"dc{d}" for d in range(4)]
    job.constraints = []
    tg = job.task_groups[0]
    tg.constraints = []
    tg.count = 16
    t = tg.tasks[0]
    t.resources.networks = []
    t.resources.cpu = int(rng.choice([400, 700, 900, 1200]))
    t.resources.memory_mb = t.resources.cpu
    tg.ephemeral_disk.size_mb = 100
    tg.networks = []
    return job


def _oc_eligible(config, nodes):
    """Nodes the config's HIGH-priority job shape can land on — the
    load multiple is defined over this subset's capacity (config 3
    excludes its constraint-filtered nodes, config 4 is device-bound)."""
    if config == 3:
        return [n for n in nodes if n.attributes["rack"] != "r63"
                and n.attributes["zone"] >= "z1"]
    if config == 4:
        return [n for n in nodes if n.node_resources.devices]
    return nodes


def _overcommit_leg(config, n_nodes, load, evict_e, gen_seed=0,
                    fill=0.8, count=16):
    """One scheduler-level overcommit leg: fill the cluster with
    low-priority running allocs to ~`fill` of cpu capacity, then drive
    priority-70 jobs through the REAL scheduler stack (Harness +
    store-attached resident Solver, preemption enabled) until total
    demand reaches `load` x eligible capacity.

    `evict_e` > 0 packs the evictable-alloc planes, so eviction sets
    are selected by the in-kernel preemption waves; `evict_e` = 0
    disables the planes and every exhausted placement takes the
    host-side preemption walk (`_try_preemption`) — the pre-ISSUE-7
    fallback this phase compares against.  Same store, same scheduler,
    same solve path otherwise."""
    from nomad_tpu import mock, structs as _st
    from nomad_tpu.scheduler.harness import Harness
    from nomad_tpu.solver.solve import Solver
    from nomad_tpu.state.store import SchedulerConfiguration
    from nomad_tpu.utils.metrics import global_metrics
    import numpy as np

    prev = os.environ.get("NOMAD_TPU_EVICT_E")
    os.environ["NOMAD_TPU_EVICT_E"] = str(evict_e)
    try:
        rng = np.random.default_rng(gen_seed * 31 + config)
        h = Harness()
        h.store.set_scheduler_config(
            h.next_index(),
            SchedulerConfiguration(preemption_service=True))
        nodes = make_nodes(n_nodes, devices=(config == 4),
                           gen_seed=gen_seed)
        for n in nodes:
            h.store.upsert_node(h.next_index(), n)
        h.solver = Solver(store=h.store, resident_min_nodes=1)
        elig = _oc_eligible(config, nodes)
        elig_ids = {n.id for n in elig}
        cap_cpu = float(sum(n.node_resources.cpu for n in elig))
        total_cpu = float(sum(n.node_resources.cpu for n in nodes))

        # ---- fill tier: bin-packed low-priority allocs, marked RUNNING
        filled = 0.0
        fill_elig = 0.0
        misses = 0
        i = 0
        while filled < fill * total_cpu and misses < 3:
            job = _oc_fill_job(i, rng)
            h.store.upsert_job(h.next_index(), job)
            h.process("service", mock.eval_(
                job_id=job.id,
                triggered_by=_st.EVAL_TRIGGER_JOB_REGISTER))
            allocs = h.store.allocs_by_job("default", job.id)
            for a in allocs:
                a.client_status = _st.ALLOC_CLIENT_RUNNING
            if allocs:
                h.store.upsert_allocs(h.next_index(), allocs)
                cpu = job.task_groups[0].tasks[0].resources.cpu
                filled += cpu * len(allocs)
                fill_elig += cpu * sum(a.node_id in elig_ids
                                       for a in allocs)
                misses = 0
            else:
                misses += 1
            i += 1

        # ---- high tier: measured sweep to load x eligible capacity
        per_place = 625 if config == 3 else 400
        high_cpu = max(0.0, load * cap_cpu - fill_elig)
        n_evals = max(1, int(round(high_cpu / (per_place * count))))
        global_metrics.reset()
        plans0 = len(h.plans)
        lat = []
        t0 = time.perf_counter()
        for e in range(n_evals):
            job = make_job(config if config != 5 else 2, e, count,
                           gen_seed)
            job.id = f"hi-{config}-{e}"
            job.name = job.id
            job.priority = 70
            if config == 5:
                # federation shape: each job pinned to one region(dc)
                job.datacenters = [f"dc{e % 4}"]
            h.store.upsert_job(h.next_index(), job)
            ts = time.perf_counter()
            h.process("service", mock.eval_(
                job_id=job.id, priority=70,
                triggered_by=_st.EVAL_TRIGGER_JOB_REGISTER))
            lat.append(time.perf_counter() - ts)
        wall = time.perf_counter() - t0
        evictions = placed = 0
        for p in h.plans[plans0:]:
            evictions += sum(len(v) for v in p.node_preemptions.values())
            placed += sum(len(v) for v in p.node_allocation.values())
        counters = global_metrics.dump().get("counters", {})
        kern = int(counters.get("scheduler.preempt.kernel", 0))
        fb = int(counters.get("scheduler.preempt.host_fallback", 0))
        return {
            "mode": "kernel" if evict_e > 0 else "host_walk",
            "config": config, "load": load, "n_nodes": n_nodes,
            "n_evals": n_evals, "count": count,
            "fill_frac": round(filled / total_cpu, 3),
            "wall_s": round(wall, 3),
            "evals_per_sec": round(n_evals / wall, 2),
            "placements": placed,
            "evictions": evictions,
            "evictions_per_sec": round(evictions / wall, 1),
            "preempt_kernel": kern,
            "preempt_host_fallback": fb,
            "fast_path_retention_pct": round(
                100.0 * kern / max(kern + fb, 1), 2),
            **latency_summary(lat),
        }
    finally:
        if prev is None:
            os.environ.pop("NOMAD_TPU_EVICT_E", None)
        else:
            os.environ["NOMAD_TPU_EVICT_E"] = prev


def _verify_twin_identity(gen_seed=0, n_nodes=64, count=16):
    """(place, evict) bit-identity of the device eviction pass vs the
    host twin on THIS phase's workload shape — a spot check riding the
    bench; the full pallas x shortlist x shard matrix is tier-1
    (tests/test_preempt_kernel.py)."""
    import numpy as np
    from nomad_tpu import mock
    from nomad_tpu.parallel.sharded import kernel_args
    from nomad_tpu.solver.host import host_solve_kernel
    from nomad_tpu.solver.kernel import solve_kernel
    from nomad_tpu.solver.tensorize import (Tensorizer,
                                            alloc_usage_vector)

    rng = np.random.default_rng(gen_seed + 7)
    nodes = make_nodes(n_nodes, gen_seed=gen_seed)
    for n in nodes:
        # tight nodes so the asks below genuinely need evictions
        n.node_resources.cpu = int(rng.choice([3000, 4000, 6000]))
        n.compute_class()
    abn = {}
    ci = 0
    for i, n in enumerate(nodes):
        lst = []
        for k in range(int(rng.integers(2, 6))):
            a = mock.alloc()
            a.id = f"low-{i}-{k}"
            a.node_id = n.id
            a.job.priority = int(rng.choice([5, 10, 20, 30, 45]))
            a.create_index = ci
            tr = a.allocated_resources.tasks["web"]
            tr.cpu = int(rng.choice([400, 700, 900, 1200]))
            tr.memory_mb, tr.networks = tr.cpu, []
            a.allocated_resources.shared.networks = []
            a.allocated_resources.shared.disk_mb = 0
            lst.append(a)
            ci += 1
        abn[n.id] = lst
    job = make_job(3, 0, count, gen_seed)
    job.priority = 70
    for tg in job.task_groups:
        tg.count = count
        tg.tasks[0].resources.cpu = 2000
        tg.tasks[0].resources.memory_mb = 2048
    pb = Tensorizer().pack(nodes, asks_for(job), abn, evict_e=8)
    used0 = np.zeros_like(pb.used0)
    for i, n in enumerate(nodes):
        for a in abn[n.id]:
            used0[i] += alloc_usage_vector(a)
    pb.used0 = used0
    ev_kw = dict(has_preempt=True, ev_res=pb.ev_res, ev_prio=pb.ev_prio,
                 ask_prio=pb.ask_prio)
    host = host_solve_kernel(*kernel_args(pb), **ev_kw)
    res = solve_kernel(*kernel_args(pb), has_distinct=False, **ev_kw)
    ok = np.asarray(res.choice_ok)
    same = (np.array_equal(ok, host.choice_ok)
            and np.array_equal(np.where(ok, np.asarray(res.choice), -1),
                               np.where(host.choice_ok, host.choice, -1))
            and np.array_equal(np.asarray(res.evict),
                               np.asarray(host.evict)))
    return {"n_nodes": n_nodes,
            "evict_pairs": int(np.asarray(host.evict).any(axis=1).sum()),
            "identical": bool(same)}


def run_overcommit(n_nodes=128, count=16, fill=0.8,
                   loads=(1.0, 1.15, 1.3, 1.5), gen_seed=0,
                   write_detail=True):
    """Overcommit phase (ISSUE 7 acceptance).

    Load sweep 1.0x-1.5x on the primary config (3) comparing the
    in-kernel preemption waves against the host-side preemption walk
    (`NOMAD_TPU_EVICT_E=0` — the pre-ISSUE-7 path), then the
    acceptance cell at load 1.15 on configs 3-5: zero host-side
    fallbacks (fast-path retention 100%), >= 1.3x wall-clock vs the
    host walk, evictions > 0, and a (place, evict) twin-identity spot
    check.  Scheduler-level end to end: real store, real
    GenericScheduler, store-attached resident Solver."""
    out = {"phase": "overcommit", "n_nodes": n_nodes, "count": count,
           "fill": fill, "sweep": [], "acceptance_configs": {}}

    def duel(config, load):
        k = _overcommit_leg(config, n_nodes, load, evict_e=8,
                            gen_seed=gen_seed, fill=fill, count=count)
        hw = _overcommit_leg(config, n_nodes, load, evict_e=0,
                             gen_seed=gen_seed, fill=fill, count=count)
        speed = round(hw["wall_s"] / max(k["wall_s"], 1e-9), 2)
        sys.stderr.write(
            f"overcommit config={config} load={load}: kernel "
            f"{k['wall_s']}s ({k['evictions']} ev, "
            f"retention {k['fast_path_retention_pct']}%) vs host walk "
            f"{hw['wall_s']}s -> {speed}x\n")
        return {"config": config, "load": load, "kernel": k,
                "host_walk": hw, "speedup_wall": speed}

    for load in loads:
        out["sweep"].append(duel(3, load))

    ok = True
    for config in (3, 4, 5):
        rec = (next(r for r in out["sweep"] if r["load"] == 1.15)
               if config == 3 and 1.15 in loads else duel(config, 1.15))
        k, hw = rec["kernel"], rec["host_walk"]
        acc = {
            "load": 1.15,
            "evictions": k["evictions"],
            "evictions_per_sec": k["evictions_per_sec"],
            "zero_host_fallbacks": k["preempt_host_fallback"] == 0,
            "fast_path_retention_pct": k["fast_path_retention_pct"],
            "speedup_vs_host_walk": rec["speedup_wall"],
            "speedup_ge_1_3": rec["speedup_wall"] >= 1.3,
            "p99_ms_kernel": k["p99_ms"],
            "p99_ms_host_walk": hw["p99_ms"],
        }
        out["acceptance_configs"][str(config)] = acc
        ok = ok and (acc["zero_host_fallbacks"] and acc["speedup_ge_1_3"]
                     and k["evictions"] > 0)
    out["twin_identity"] = _verify_twin_identity(gen_seed)
    ok = ok and out["twin_identity"]["identical"]
    out["ok"] = bool(ok)
    if write_detail:
        path = os.path.join(REPO, "BENCH_DETAIL.json")
        try:
            with open(path) as f:
                detail = json.load(f)
        except (OSError, json.JSONDecodeError):
            detail = {}
        detail["overcommit"] = out
        with open(path, "w") as f:
            json.dump(detail, f, indent=1)
    return out


def lint_summary():
    """nomadlint state for this run (analyzer version + finding
    counts), recorded in BENCH_DETAIL so every benchmark carries the
    lint state it was measured under."""
    try:
        from nomad_tpu.analysis import ANALYZER_VERSION, analyze, \
            pass_of
        t0 = time.perf_counter()
        rep = analyze()
        wall_s = round(time.perf_counter() - t0, 2)
        baselined_by_pass = {}
        for f in rep.suppressed:
            p = pass_of(f.rule)
            baselined_by_pass[p] = baselined_by_pass.get(p, 0) + 1
        out = {"version": ANALYZER_VERSION,
               "wall_s": wall_s,
               "unsuppressed": len(rep.findings),
               "errors": len(rep.errors),
               "warnings": len(rep.warnings),
               "baselined": len(rep.suppressed),
               "stale_baseline_keys": rep.stale_baseline_keys,
               "by_rule": rep.counts_by_rule(),
               "by_pass": rep.counts_by_pass(),
               "baselined_by_pass": dict(sorted(
                   baselined_by_pass.items()))}
    except Exception as e:          # never lose the run over lint
        out = {"error": str(e)}
    try:
        # scoring-spec provenance: which spec version (and term list)
        # every backend was verified against when this run was taken
        from nomad_tpu.solver import score_spec
        out["score_spec"] = {"version": score_spec.SPEC_VERSION,
                             "terms": list(score_spec.term_names())}
    except Exception:
        pass
    try:
        # flight-recorder shape for this run (ISSUE 10): the startup
        # line + BENCH_DETAIL record what the trace ring could hold
        from nomad_tpu.utils.tracing import global_tracer
        st = global_tracer.stats()
        out["trace_store"] = {"depth": st["depth_limit"],
                              "enabled": st["enabled"]}
    except Exception:
        pass
    return out


def run_analysis():
    """The phases that need the device but belong to no config: the
    applier saturation bench (the plan pipeline must not serialize on
    the consensus round trip, VERDICT r4 item 5), the device-only
    ceiling + roofline for the primary config, and the multi-seed /
    multi-shape / both-load quality sweep (30 duels: the quality claim
    must be systematic, not one lucky seed).  One child, because a
    chip belongs to one process at a time and the parent stays off
    JAX."""
    import importlib.util as _ilu
    _spec = _ilu.spec_from_file_location(
        "applier_bench", os.path.join(REPO, "bench", "applier_bench.py"))
    _ab = _ilu.module_from_spec(_spec)
    _spec.loader.exec_module(_ab)
    sweep = run_quality_sweep()
    return {
        "applier_pipeline": _ab.run_applier_bench(3.0),
        "device_ceiling": measure_device_ceiling(3),
        "quality_sweep": sweep,
        # the classic headline duel is the sweep's (config 3, 1.15,
        # seed 0) cell — reuse it rather than run a 31st duel
        "quality_pack_to_capacity": next(
            (d for d in sweep["duels"]
             if d["config"] == 3 and d["load"] == 1.15
             and d["gen_seed"] == 0), sweep["duels"][0]),
    }


#: phases that run alone in a child process: command-line flag ->
#: (function, key of its record in BENCH_DETAIL.json)
_CHILD_PHASES = {
    "--multichip": (run_multichip, "multichip"),
    "--multiregion": (run_multiregion, "multiregion"),
    "--chaos": (run_chaos, "chaos"),
    "--overcommit": (run_overcommit, "overcommit"),
    "--analysis": (run_analysis, None),
}


def _run_child(args, env=None):
    """Run one phase of this script in a child process and return the
    record it printed.  The parent never touches a JAX backend — a chip
    belongs to one process at a time, and a parent that held it would
    starve every child — so EVERY measurement runs in a child, and a
    child that exits non-zero or prints no record fails the whole
    bench: a hole in the results is not a result."""
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), *args],
        capture_output=True, text=True, env=env)
    rec = None
    for line in out.stdout.splitlines():
        if line.startswith("\x1e"):
            rec = json.loads(line[1:])
    if out.returncode != 0 or rec is None:
        sys.stderr.write(
            f"bench child {' '.join(args)} exited {out.returncode} "
            f"({'no record' if rec is None else 'record discarded'}):\n"
            f"{out.stdout[-1500:]}\n{out.stderr[-3000:]}\n")
        raise SystemExit(1)
    return rec


def main():
    from nomad_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if len(sys.argv) > 2 and sys.argv[1] == "--one":
        # child mode: run one config, print its record as JSON
        print("\x1e" + json.dumps(run_config(int(sys.argv[2]))))
        return
    if len(sys.argv) > 1 and sys.argv[1] in _CHILD_PHASES:
        # child mode: one phase alone in this process (each merges its
        # own record into BENCH_DETAIL.json / MULTICHIP_DETAIL.json)
        fn, _key = _CHILD_PHASES[sys.argv[1]]
        print("\x1e" + json.dumps(fn()))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--quality-sweep":
        out = run_quality_sweep()
        with open(os.path.join(REPO, "QUALITY_SWEEP.json"), "w") as f:
            json.dump(out, f, indent=1)
        print(json.dumps({k: out[k] for k in
                          ("n", "mean_placed_ratio", "min_placed_ratio",
                           "max_placed_ratio")}))
        return
    only = int(sys.argv[1]) if len(sys.argv) > 1 else None
    # lint state up front so BENCH_DETAIL records which invariants held
    # for this run (pure-AST pass, no device; never blocks the bench)
    lint = lint_summary()
    sys.stderr.write(
        f"nomadlint v{lint.get('version', '?')}: "
        f"{lint.get('unsuppressed', '?')} unsuppressed, "
        f"{lint.get('baselined', '?')} baselined"
        + (f" ({lint['error']})" if "error" in lint else "")
        + (f"; trace-store depth "
           f"{lint['trace_store']['depth']}"
           + ("" if lint['trace_store']['enabled'] else " (off)")
           if "trace_store" in lint else "") + "\n")
    # one child per config: isolates device state between configs
    # while the persistent XLA compile cache keeps per-config startup
    # warm
    results = [_run_child(["--one", str(c)]) for c in sorted(CONFIGS)
               if not only or c == only]
    if only is None:
        detail = {"configs": results, "lint": lint}
        # the mesh legs still run on an 8-device virtual CPU platform
        # (ROADMAP R7 replaces them with one four-chip host); multiregion
        # runs AFTER multichip so its record merges into the
        # MULTICHIP_DETAIL.json that phase just wrote
        mesh_env = dict(os.environ)
        mesh_env["JAX_PLATFORMS"] = "cpu"
        mesh_env["XLA_FLAGS"] = (
            mesh_env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()
        for flag in ("--multichip", "--multiregion"):
            detail[_CHILD_PHASES[flag][1]] = _run_child([flag],
                                                        env=mesh_env)
        detail["overcommit"] = _run_child(["--overcommit"])
        detail.update(_run_child(["--analysis"]))
        detail["notes"] = [
            "denominator: bench/stock_engine.cc — reference semantics "
            "(subsampled ranking, class-memoized feasibility, serial "
            "re-validating applier) in C++ at Go-comparable speed, fed "
            "the identical generated cluster/jobs",
            "the denominator is an UPPER BOUND on the reference's "
            "throughput: it keeps state in flat hash tables and skips "
            "the reference's memdb radix indexes, msgpack plan "
            "serialization, RPC hops and disk writes — real deployed "
            "schedulers run the same semantics considerably slower",
            "numerator timings include ask packing, transfer, solve and "
            "result fetch; one-time startup (node pack + device_put + "
            "XLA compile) reported separately as startup_s",
            "per-config ours.steady_state reports the DELTA-WAVE regime "
            "(ISSUE 2): the same eval population re-dispatched with a "
            "plan-apply usage changeset applied between waves — "
            "pack_dispatch_reduction compares first-pass vs steady "
            "per-wave pack+dispatch ms; ours.delta_counters carries "
            "delta_applies / repack_fallbacks / last_delta_ratio / "
            "bytes_dispatched_delta vs bytes_dispatched_full, and "
            "ours.compile_cache the persistent-XLA-cache hit/miss of "
            "this startup (warm_start = no new compiles persisted)",
            "numerator THROUGHPUT mode merges identical stateless asks "
            "at pack time (summed counts; distinct_hosts and stateful "
            "asks never merge) — the columnar payoff of coalescing "
            "evals; job-scoped soft scoring is then computed over the "
            "merged population while hard commit quotas stay exact. "
            "The quality duel runs EXACT mode (no merging, no jitter)",
        ]
        with open(os.path.join(REPO, "BENCH_DETAIL.json"), "w") as f:
            json.dump(detail, f, indent=1)
    primary = next((r for r in results if r["config"] == 3), results[0])
    # ALL five configs count: 1 is interactive latency (native in-
    # process solve), 2-5 are throughput streams — r4 verdict item 2
    ratios = [r["ratio_placements"] for r in results]
    geomean = (math.exp(sum(math.log(max(r, 1e-9)) for r in ratios)
                        / len(ratios)) if ratios else None)
    print(json.dumps({
        "metric": ("placements/sec @10K nodes, 100K resident allocs, "
                   "constraints+affinity+spread (BASELINE config 3); "
                   "vs_baseline = geomean placement-throughput ratio "
                   "over ALL FIVE configs (1 = interactive latency via "
                   "the native in-process solver, 2-5 = streamed "
                   "throughput) against the stock-semantics C++ "
                   "engine"),
        "value": primary["ours"]["placements_per_sec"],
        "unit": "placements/sec",
        "vs_baseline": round(geomean, 3) if geomean is not None else None,
    }))


if __name__ == "__main__":
    main()
