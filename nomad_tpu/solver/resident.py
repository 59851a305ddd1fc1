"""Resident solve: node tensors live on device, eval batches stream.

Every host-to-device transfer and every dispatch-then-fetch has a fixed
cost (a PCIe hop and a host sync) that a solve over a few asks cannot
amortize.  The reference never faces this — its scheduler runs
in-process (nomad/worker.go) — so the TPU-first design has to
restructure the *data flow*, not just the math:

  * pack the node side ONCE (capacity, attributes, device inventory) and
    `device_put` it a single time;
  * per eval batch, pack only the [G, ...] ask programs
    (Tensorizer.repack_asks) — no O(N) host walk, no O(N) transfer;
  * carry `used` / `dev_used` ON DEVICE between batches, so cluster
    usage never bounces through the host;
  * fuse MANY eval batches into one device call with `lax.scan`
    (solve_stream), amortizing the round trip over thousands of
    placements; each batch's placements see every earlier batch's
    RESOURCE commits (cpu/mem/disk/net + devices) through the carried
    usage.  Job-scoped scoring state — distinct_hosts blocking,
    anti-affinity collocation, spread usage — is seeded per batch, which
    is sound because the eval broker serializes evals per job
    (reference: nomad/eval_broker.go job-token dedup): one job can never
    appear in two batches of the same stream, and those dimensions never
    cross jobs.  solve_stream enforces that invariant;
  * fetch ONE packed [B, K, 2*TOP_K+1] result buffer (node indices,
    scores, and a per-placement STATUS_* outcome; `ok` is derivable
    because failed slots score NEG_INF).

Falls back to the general Solver path whenever an ask steps outside the
resident universe (repack_asks returns None).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..structs import Node
from .kernel import (MERGED_GP_MAX, NEG_INF, TOP_K, exact_dot,
                     solve_kernel)
from .tensorize import PackedBatch, PlacementAsk, Tensorizer

from jax import lax


def unpack_stream(out) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                np.ndarray]:
    """Decode a fetched stream payload — compact int16 (see
    pack_out_compact) or the f32 layout — into (choice, ok, score,
    status)."""
    out = np.asarray(out)                         # ONE fetched buffer
    if out.dtype == np.int16:
        choice = out[..., :TOP_K].astype(np.int32)
        u16 = np.ascontiguousarray(
            out[..., TOP_K:2 * TOP_K]).view(np.uint16)
        score = (u16.astype(np.uint32) << 16).view(np.float32)
        status = out[..., -1].astype(np.int32)
    else:
        choice = out[..., :TOP_K].astype(np.int32)
        score = out[..., TOP_K:2 * TOP_K]
        status = out[..., -1].astype(np.int32)
    ok = score > NEG_INF / 2
    return choice, ok, score, status


def _env_shortlist_c() -> int:
    """NOMAD_TPU_SHORTLIST_C: unset/'auto' -> 0 (auto), 'off'/-1 ->
    disabled, else an int handed to kernel.resolve_shortlist_c (which
    validates it against the problem shape at trace time)."""
    import os
    raw = os.environ.get("NOMAD_TPU_SHORTLIST_C", "").strip().lower()
    if raw in ("", "auto"):
        return 0
    if raw in ("off", "-1"):
        return -1
    try:
        return int(raw)
    except ValueError:
        raise ValueError(
            f"NOMAD_TPU_SHORTLIST_C={raw!r} invalid: use 'auto', 'off' "
            "or an integer shortlist width") from None


def _env_fused_lanes() -> int:
    """NOMAD_TPU_FUSED_LANES: unset/'1'/'serial' -> 1 (the serial
    scan — the bit-identical legacy fused path); an integer > 1 opts
    solve_stream into the lane-parallel chunked scan-of-vmap
    (ISSUE 20).  Callers that widen per round (the adaptive lane-width
    controller, fleet.LaneWidthController) pass `lanes=` per call
    instead."""
    import os
    raw = os.environ.get("NOMAD_TPU_FUSED_LANES", "").strip().lower()
    if raw in ("", "1", "serial"):
        return 1
    try:
        return max(1, int(raw))
    except ValueError:
        raise ValueError(
            f"NOMAD_TPU_FUSED_LANES={raw!r} invalid: pass an integer "
            "lane width (1 = serial scan)") from None


def pack_out_compact(choice, score, status):
    """Device-side result compaction: node indices as int16, scores
    bitcast through bfloat16, status as int16 — [..., 2*TOP_K+1] int16,
    HALF the fetch bytes of the f32 layout.  bf16 score
    precision (~3 significant digits) is plenty for explainability
    ranking, and `ok` derives from score > NEG_INF/2 which bf16
    preserves.  Requires Np < 32768 (int16 node indices)."""
    return jnp.concatenate(
        [choice.astype(jnp.int16),
         lax.bitcast_convert_type(score.astype(jnp.bfloat16), jnp.int16),
         status.astype(jnp.int16)[..., None]], axis=-1)

# per-placement outcome in the packed result's last column
STATUS_FAILED = 0      # infeasible / resources exhausted — terminal
STATUS_COMMITTED = 1   # slot-0 choice committed into carried usage
STATUS_RETRY = 2      # bounced by revalidation or wave budget — resubmit


def pack_batch_cached(solver, asks: Sequence[PlacementAsk],
                      job_keys: Optional[set] = None
                      ) -> Optional[PackedBatch]:
    """pack_batch with a whole-batch cache (shared by ResidentSolver
    and HostResidentSolver): asks carrying NO per-eval state (no
    penalties, existing allocs, blocked hosts, spread seeds, property
    limits) reuse the previously packed tensors for the same
    (spec signature, count) sequence — the steady-state stream where
    merge_asks collapses every chunk to the same few rows.  Nothing
    mutates a PackedBatch, so sharing is sound; job_keys (the stream
    guard) is refreshed per call.

    distinct_hosts asks are NEVER cached: their packed `distinct`
    column interns job/group IDENTITY, which the spec signature
    deliberately excludes — a cache hit could alias two different
    jobs' distinctness patterns (same reason merge_asks skips them)."""
    from ..scheduler import feasible as hostfeas
    from ..structs import CONSTRAINT_DISTINCT_HOSTS
    cacheable = all(
        not (a.penalty_nodes or a.existing_by_node
             or a.distinct_hosts_blocked or a.spread_seed
             or a.property_limits)
        and not any(c.operand == CONSTRAINT_DISTINCT_HOSTS
                    for c in hostfeas.merged_constraints(a.job, a.tg))
        for a in asks)
    if not cacheable:
        return solver.pack_batch(asks, job_keys=job_keys)
    sig = solver._tz.ask_signer()
    key = tuple((sig(a), a.count) for a in asks)
    pb = solver._eval_cache.get(key)
    if pb is None:
        pb = solver.pack_batch(asks, job_keys=job_keys)
        if pb is None:
            return None
        if len(solver._eval_cache) > 512:
            solver._eval_cache.clear()
        solver._eval_cache[key] = pb
    else:
        pb.job_keys = (job_keys if job_keys is not None else
                       {(a.job.namespace, a.job.id) for a in asks})
    return pb

def model_wave_bytes(Np: int, Gp: int, K: int, S: int, R: int,
                     has_spread: bool, mode: str, TK: int, C: int
                     ) -> Tuple[int, int, int]:
    """Two-tier per-wave HBM byte model: (bytes_wave1, bytes_rewave,
    fused_pass_count).  bytes_wave1 models a full-N pass (wave 1 and
    every shortlist-escape rescore); bytes_rewave a shortlist-resident
    contention wave over the carried [Gp, C] state.  Pure function of
    the solve shape so tests and the bench roofline share one model
    (ResidentSolver.wave_traffic feeds it the live configuration)."""
    plane = Gp * Np
    spread_planes = (2 * S * plane * 4) if has_spread else 0
    if mode == "off":
        # the unfused chain: ~6 elementwise [Gp, Np] f32 passes plus
        # the [Gp, Np, R] broadcast intermediates and the top-k read
        bytes_wave1 = (plane * 4 * 6 + plane * R * 4 * 2
                       + spread_planes + Np * R * 4 * 2
                       + K * 4 * 6)
        passes = 6
    else:
        # fused single pass: every plane read ONCE (feas/pen as
        # GROUP-bitpacked int32 words, one word per 32 groups per node
        # column; aff f32, jitter f32, coll f32 + spread statics), node
        # rows once, plus score write+read in "score" mode only
        reads = plane * (4 + 4 + 4) + 2 * (-(-Gp // 32) * Np * 4) \
            + spread_planes + Np * R * 4 * 3
        extra = (plane * 4 * 2 if mode == "score" else 0)
        bytes_wave1 = reads + extra + K * 4 * 6
        passes = 1
    if C > 0:
        # shortlist wave: carried [Gp, C] state read+written by the
        # loop carry (idx/feas/pen/aff/coll + spread vn/de), live
        # gathers of used/avail/reserved rows, the [Gp, TK] window,
        # the [Np] commit-mark plane and the K-sized commit vectors
        per_entry = (14 + (8 * S if has_spread else 0)) * 2 + 12 * R
        bytes_rewave = (Gp * C * per_entry + Np * 4
                        + Gp * TK * 12 + K * 4 * 2)
    else:
        bytes_rewave = bytes_wave1     # no shortlist: all waves full
    return int(bytes_wave1), int(bytes_rewave), passes


# ask-side solve_kernel args stacked per batch (see sharded._ARG_SPECS)
_ASK_ARGS = ("ask_res", "ask_desired", "distinct", "dc_ok", "host_ok",
             "coll0", "penalty", "c_op", "c_col", "c_rank", "a_op", "a_col",
             "a_rank", "a_weight", "a_host", "sp_col", "sp_weight",
             "sp_targeted", "sp_desired", "sp_implicit", "sp_used0",
             "dev_ask", "p_ask", "ask_prio")


def _solve_one(avail, reserved, valid, node_dc, attr_rank, dev_cap,
               used, dev_used, batch, n_place, seed=0, has_spread=True,
               group_count_hint=0, max_waves=0, wave_mode="scan",
               has_distinct=True, has_devices=True, stack_commit=False,
               pallas_mode="off", shortlist_c=0, mesh_axis=None,
               mesh_shards=0, has_preempt=False, ev_res=None,
               ev_prio=None, mesh_hosts=0, mesh_nt=0, tile_np=0,
               node_gid=None, owner_map=None, slot_map=None,
               mesh_regions=0, lane_axis=None):
    # host_ok / penalty may arrive BITPACKED from _stack_args (uint32
    # lanes, 1/8th the transport bytes of the dense bool planes);
    # unpack on device — dtype is static, so either form compiles once
    from .masks import unpack_bool_u32
    Np = avail.shape[0]
    host_ok = batch["host_ok"]
    if host_ok.dtype == jnp.uint32:
        host_ok = unpack_bool_u32(host_ok, Np)
    penalty = batch["penalty"]
    if penalty.dtype == jnp.uint32:
        penalty = unpack_bool_u32(penalty, Np)
    ev_kw = {}
    if has_preempt:
        # the stream caller gated distinct batches off already (the
        # eviction pass statically refuses distinct_hosts batches)
        has_distinct = False
        ev_kw = dict(has_preempt=True, ev_res=ev_res, ev_prio=ev_prio,
                     ask_prio=batch["ask_prio"])
    return solve_kernel(
        avail, reserved, used, valid, node_dc, attr_rank,
        batch["ask_res"], batch["ask_desired"], batch["distinct"],
        batch["dc_ok"], host_ok, batch["coll0"],
        penalty, batch["c_op"], batch["c_col"],
        batch["c_rank"], batch["a_op"], batch["a_col"],
        batch["a_rank"], batch["a_weight"], batch["a_host"],
        batch["sp_col"], batch["sp_weight"], batch["sp_targeted"],
        batch["sp_desired"], batch["sp_implicit"], batch["sp_used0"],
        dev_cap, dev_used, batch["dev_ask"], batch["p_ask"], n_place,
        seed, has_spread=has_spread, group_count_hint=group_count_hint,
        max_waves=max_waves, wave_mode=wave_mode,
        has_distinct=has_distinct, has_devices=has_devices,
        stack_commit=stack_commit, pallas_mode=pallas_mode,
        shortlist_c=shortlist_c, mesh_axis=mesh_axis,
        mesh_shards=mesh_shards, mesh_hosts=mesh_hosts,
        mesh_nt=mesh_nt, tile_np=tile_np, node_gid=node_gid,
        owner_map=owner_map, slot_map=slot_map,
        mesh_regions=mesh_regions, lane_axis=lane_axis, **ev_kw)


@functools.partial(jax.jit,
                   static_argnames=("has_spread", "group_count_hint",
                                    "max_waves", "wave_mode",
                                    "has_distinct", "has_devices",
                                    "stack_commit", "compact",
                                    "pallas_mode", "shortlist_c",
                                    "has_preempt"))
def _stream_kernel(avail, reserved, valid, node_dc, attr_rank, dev_cap,
                   used0, dev_used0, stacked, n_places, seeds,
                   ev_res=None, ev_prio=None,
                   has_spread=True, group_count_hint=0, max_waves=0,
                   wave_mode="scan", has_distinct=True,
                   has_devices=True, stack_commit=False, compact=True,
                   pallas_mode="off", shortlist_c=0,
                   has_preempt=False):
    """lax.scan solve_kernel over a leading batch axis of ask tensors,
    threading resource usage from batch to batch on device.  Also
    returns the per-batch wave and full-rescore counts [B] — the
    instrumentation the two-tier HBM byte model multiplies against
    (bytes_wave1 x rescore + bytes_rewave x shortlist waves) — and the
    per-batch [K, E] eviction-slot masks of the in-kernel preemption
    pass (zeros [K, 1] when has_preempt is off)."""

    def step(carry, xs):
        used, dev_used = carry
        batch, n_place, seed = xs
        res = _solve_one(avail, reserved, valid, node_dc, attr_rank,
                         dev_cap, used, dev_used, batch, n_place, seed,
                         has_spread, group_count_hint, max_waves,
                         wave_mode, has_distinct, has_devices,
                         stack_commit, pallas_mode, shortlist_c,
                         has_preempt=has_preempt, ev_res=ev_res,
                         ev_prio=ev_prio)
        status = jnp.where(res.choice_ok[:, 0], STATUS_COMMITTED,
                           jnp.where(res.unfinished, STATUS_RETRY,
                                     STATUS_FAILED))
        if compact:
            packed = pack_out_compact(res.choice, res.score, status)
        else:
            packed = jnp.concatenate(
                [res.choice.astype(jnp.float32), res.score,
                 status.astype(jnp.float32)[:, None]], axis=-1)
        evict = (res.evict if has_preempt
                 else jnp.zeros((res.choice.shape[0], 1), bool))
        return ((res.used_final, res.dev_used_final),
                (packed, evict, res.n_waves, res.n_rescore))

    (used_f, dev_used_f), (out, evict, waves, rescores) = jax.lax.scan(
        step, (used0, dev_used0), (stacked, n_places, seeds))
    return used_f, dev_used_f, out, evict, waves, rescores


@functools.partial(jax.jit,
                   static_argnames=("lanes", "has_spread",
                                    "group_count_hint", "max_waves",
                                    "wave_mode", "has_distinct",
                                    "has_devices", "stack_commit",
                                    "compact", "pallas_mode",
                                    "shortlist_c"))
def _lane_stream_kernel(avail, reserved, valid, node_dc, attr_rank,
                        dev_cap, used0, dev_used0, stacked, n_places,
                        seeds, lanes=2, has_spread=True,
                        group_count_hint=0, max_waves=0,
                        wave_mode="while", has_distinct=True,
                        has_devices=True, stack_commit=False,
                        compact=True, pallas_mode="off", shortlist_c=0):
    """Chunked scan-of-vmap fused stream (ISSUE 20): the serial scan of
    `_stream_kernel` but L batches per scan step, each step `vmap`ing
    the solve over its L lanes against the CARRIED usage snapshot and
    then revalidating all L lanes' slot-0 commits in one in-kernel pass
    — a cumulative same-node credit in cross-lane placement order
    (lane-major: lane l's placement k revalidates at rank l*K + k).
    Serial depth drops from B to B/L; placements a sibling lane beat to
    a node bounce to STATUS_RETRY with every score slot nulled, so the
    retry stream clears them.

    The lanes keep the caller's shortlist: `lane_axis` makes the
    carried/full wave cond lane-UNIFORM (a psum over the vmap axis is
    unbatched, so the cond stays a real branch — see kernel.py); a
    plain vmap would turn the cond into a select that runs both
    branches on every lane.

    B must be a multiple of `lanes` (the host pads with n_place=0 rows).
    Preemption streams stay on the serial kernel: cross-lane
    revalidation of EVICTION credits (usage that goes DOWN) has no
    one-round conservative form.  Returns (used, dev_used, out [B,...],
    waves [B], rescores [B], bounced [B], committed [B])."""
    L = lanes
    B = n_places.shape[0]
    n_chunks = B // L
    st_c = jax.tree_util.tree_map(
        lambda v: v.reshape((n_chunks, L) + v.shape[1:]), dict(stacked))
    np_c = n_places.reshape(n_chunks, L)
    seed_c = seeds.reshape(n_chunks, L)
    K = stacked["p_ask"].shape[1]
    ks = jnp.arange(K)
    lk = jnp.arange(L * K)

    def chunk_step(carry, xs):
        used, dev_used = carry
        batch, n_place, seed = xs
        res = jax.vmap(
            lambda b, n, s: _solve_one(
                avail, reserved, valid, node_dc, attr_rank, dev_cap,
                used, dev_used, b, n, s, has_spread, group_count_hint,
                max_waves, wave_mode, has_distinct, has_devices,
                stack_commit, pallas_mode, shortlist_c,
                lane_axis="lanes"),
            axis_name="lanes")(batch, n_place, seed)
        # ---- cross-lane revalidation (the serial plan applier) ----
        # Flatten lane-major and replay apply_batch's arithmetic over
        # the whole chunk: cumulative same-node credit in (lane,
        # placement) order, conservative one-round semantics (a bounced
        # placement's load still counts toward later same-node rows, so
        # bounces can cascade — every one is STATUS_RETRY, never lost).
        # Intra-lane placements re-earn their own solve's commits: the
        # lane charged them against the same snapshot in the same
        # order, so their cumulative fit re-checks true.
        res_l = jnp.take_along_axis(
            batch["ask_res"],
            batch["p_ask"][:, :, None].astype(jnp.int32), axis=1)
        dev_l = jnp.take_along_axis(
            batch["dev_ask"],
            batch["p_ask"][:, :, None].astype(jnp.int32), axis=1)
        res_k = res_l.reshape(L * K, -1)
        dev_k = dev_l.reshape(L * K, -1)
        choice = res.choice.reshape(L * K, TOP_K)
        score = res.score.reshape(L * K, TOP_K)
        unfin = res.unfinished.reshape(L * K)
        okf = (res.choice_ok[:, :, 0]
               & (ks[None, :] < n_place[:, None])).reshape(L * K)
        cand = choice[:, 0]
        earlier = lk[None, :] < lk[:, None]
        same = ((cand[None, :] == cand[:, None]) & okf[None, :]
                & okf[:, None] & earlier)
        prior = exact_dot(same.astype(jnp.float32),
                          res_k * okf[:, None])
        prior_dev = exact_dot(same.astype(jnp.float32),
                              dev_k * okf[:, None])
        fits = ((used[cand] + prior + res_k) <= avail[cand]).all(-1)
        dev_fits = ((dev_used[cand] + prior_dev + dev_k)
                    <= dev_cap[cand]).all(-1)
        commit = okf & fits & dev_fits
        cm = commit[:, None]
        used = used.at[cand].add(res_k * cm)
        dev_used = dev_used.at[cand].add(dev_k * cm)
        # bounced placements lose ALL slots (their fall-through scores
        # were solved against a stale snapshot and were never charged)
        score = jnp.where(cm, score, NEG_INF)
        status = jnp.where(commit, STATUS_COMMITTED,
                           jnp.where(okf | unfin, STATUS_RETRY,
                                     STATUS_FAILED))
        score_l = score.reshape(L, K, TOP_K)
        status_l = status.reshape(L, K)
        if compact:
            packed = jax.vmap(pack_out_compact)(res.choice, score_l,
                                                status_l)
        else:
            packed = jnp.concatenate(
                [res.choice.astype(jnp.float32), score_l,
                 status_l.astype(jnp.float32)[..., None]], axis=-1)
        bounced = (okf & ~commit).reshape(L, K).sum(axis=1)
        committed = commit.reshape(L, K).astype(jnp.int32).sum(axis=1)
        return ((used, dev_used),
                (packed, res.n_waves, res.n_rescore,
                 bounced.astype(jnp.int32), committed))

    (used_f, dev_used_f), (out, waves, rescores, bounced, committed) = \
        jax.lax.scan(chunk_step, (used0, dev_used0),
                     (st_c, np_c, seed_c))

    def _flat(a):
        return a.reshape((B,) + a.shape[2:])

    return (used_f, dev_used_f, _flat(out), _flat(waves),
            _flat(rescores), _flat(bounced), _flat(committed))


class ResidentSolver:
    """Streaming placement engine for one node snapshot.

    Build once per (node set, attribute/driver universe); then
    `solve_stream` processes eval batches with device-resident state.
    The probe asks passed to the constructor define the tensor universe
    (attr columns, constraint/affinity/spread slot counts, device
    patterns); real batches whose asks fit that universe take the fast
    path.
    """

    def __init__(self, nodes: Sequence[Node],
                 probe_asks: Sequence[PlacementAsk],
                 allocs_by_node: Optional[Dict[str, list]] = None,
                 gp: Optional[int] = None, kp: Optional[int] = None,
                 max_waves: int = 0, wave_mode: str = "scan",
                 stack_commit: bool = False, pallas: str = "auto",
                 delta_threshold: Optional[float] = None,
                 shortlist_c: Optional[int] = None,
                 evict_e: int = 0,
                 fused_lanes: Optional[int] = None):
        import os
        self.nodes = list(nodes)
        #: in-kernel preemption (ISSUE 7): > 0 packs top-E evictable-
        #: alloc planes from `allocs_by_node` and runs the eviction
        #: wave pass for groups with nothing placeable.  Stream-mode
        #: contract: the caller must feed each batch's evictions back
        #: as stop deltas (solve_stream_pipelined deltas=) before the
        #: next batch — usage carries on device, but the candidate
        #: planes only advance through apply_delta.  0 = off (default
        #: for the raw stream engine; the worker Solver enables it via
        #: tensorize.evict_width()).
        self.evict_e = int(evict_e)
        self.max_waves = max_waves        # 0 = kernel default
        self.wave_mode = wave_mode        # see kernel.py loop-shape note
        self.stack_commit = stack_commit  # serial-fidelity commits
        #: "auto" resolves per trace against shape + backend (pallas
        #: fused wave kernel on TPU / forced via NOMAD_TPU_PALLAS);
        #: "off"/"score"/"topk" pin it (tests, benchmarks)
        self.pallas = pallas
        #: shortlist width for contention waves: 0 auto-sizes (the
        #: candidate window rounded up a tile), -1 disables, explicit
        #: values are validated at trace time (kernel.py
        #: resolve_shortlist_c — invalid values RAISE, never clamp).
        #: NOMAD_TPU_SHORTLIST_C overrides when the ctor arg is None
        #: ("auto"/"off" accepted as spellings of 0/-1).
        self.shortlist_c = (
            shortlist_c if shortlist_c is not None
            else _env_shortlist_c())
        #: default lane width for solve_stream (ISSUE 20): 1 = the
        #: serial scan, bit-identical legacy behavior; L > 1 solves L
        #: batches per scan step (chunked scan-of-vmap) and revalidates
        #: their commits cross-lane, bouncing losers to STATUS_RETRY.
        #: NOMAD_TPU_FUSED_LANES overrides when the ctor arg is None;
        #: solve_stream_async(lanes=) overrides per call.
        self.fused_lanes = (int(fused_lanes) if fused_lanes is not None
                            else _env_fused_lanes())
        #: device-side revalidation counters of the last LANE-parallel
        #: stream (None after a serial stream) — fetch via
        #: lane_counters(), which the adaptive width controller feeds on
        self.last_lane_counters = None
        #: per-batch wave counts of the LAST dispatched stream (device
        #: array; fetch syncs — instrumentation consumers only)
        self.last_waves = None
        #: per-batch FULL-rescore wave counts of the last stream (the
        #: remainder up to last_waves ran shortlist-resident)
        self.last_rescore_waves = None
        #: delta waves touching more than this fraction of real node
        #: slots fall back to a full repack (one contiguous re-put beats
        #: a near-total scatter); NOMAD_TPU_DELTA_THRESHOLD overrides
        self.delta_threshold = (
            delta_threshold if delta_threshold is not None
            else float(os.environ.get("NOMAD_TPU_DELTA_THRESHOLD",
                                      "0.25")))
        #: resident-delta observability (ISSUE 2 satellite): consumed by
        #: wave_traffic / BENCH_DETAIL
        self.delta_counters = {
            "delta_applies": 0, "repack_fallbacks": 0,
            "last_delta_ratio": 0.0,
            "bytes_dispatched_delta": 0, "bytes_dispatched_full": 0,
            # cumulative ask-plane bytes the stream dispatches shipped
            # (ISSUE 20 satellite; per-round in last_dispatch_bytes)
            "bytes_dispatched_ask": 0, "ask_dispatches": 0,
        }
        #: pow2-bucketed staging buffers for the B>1 stacked ask planes
        #: (ISSUE 20 satellite — see _staged_stack)
        self._stage_cache: Dict = {}
        #: B>1 repeated-stream device cache (ISSUE 20 satellite): the
        #: stacked+device-put ask dict keyed on the identity tuple of
        #: the stream's batches — see _stack_args
        self._stream_stack_cache: Dict = {}
        #: bumps on every node-shape change; device-side stacked-batch
        #: caches are keyed on it so a stale ask plane is never reused
        self._node_epoch = 0
        #: bumps whenever the EVICTION planes advance (alloc place/stop
        #: deltas replay ev rows WITHOUT touching the node shape, so
        #: the node epoch alone cannot invalidate ev-dependent caches —
        #: ISSUE 8 satellite; see federated._stack_args)
        self._ev_epoch = 0
        #: host bytes the LAST dispatch actually shipped (0 on a
        #: device-cached re-dispatch)
        self.last_dispatch_bytes = 0
        #: wall-clock of the last SYNCHRONOUS stream solve (solve_stream
        #: / solve_stream_pipelined, dispatch through fetch) keyed by
        #: batch count — the serving tier's EWMA solve-time model feeds
        #: from this (server/serving.py EwmaSolveModel.observe)
        self.last_solve_stats = None
        #: [B, K, E] eviction-slot masks of the last dispatched stream
        #: (device array; list when pipelined) — None until a preempt-
        #: enabled stream ran
        self.last_evict = None
        self._probe_asks = list(probe_asks)
        self._tz = Tensorizer()
        self.template = self._tz.pack(nodes, probe_asks, allocs_by_node,
                                      evict_e=self.evict_e)
        self.node_index = {n.id: i for i, n in enumerate(self.nodes)}
        self.gp = gp or self.template.ask_res.shape[0]
        self.kp = kp or self.template.p_ask.shape[0]
        self._drv_cache: Dict[str, np.ndarray] = {}
        self._row_cache: Dict = {}    # ask_signature -> packed spec row
        self._eval_cache: Dict = {}       # see pack_batch_cached
        # device-resident constants for the [G, N] ask-side arrays that
        # are usually all-zero (fresh jobs) or at their universe default
        # (host_ok): shipping them dense per call costs ~100MB/s-class
        # transports far more than the solve itself
        self._const_cache: Dict[Tuple[str, int], object] = {}
        self._put_node_side()

    #: subclass hook (parallel.sharded): bitpacking bool ask planes
    #: would split 32 node columns per uint32 lane, which a node-axis
    #: NamedSharding cannot partition cleanly — the mesh solver ships
    #: them dense instead
    _pack_bool_planes = True

    def _put_node(self, name: str, arr):
        """Device placement for one node-side tensor (subclass hook:
        the mesh-resident solver pins a node-axis NamedSharding).

        Always COPIES first: CPU device_put can alias the numpy buffer
        zero-copy, and apply_delta later mutates the template arrays IN
        PLACE host-side (apply_node_delta_host) — through an alias the
        device carry would see both the host `+=` and the device
        scatter-add, double-charging usage depending on nothing more
        than heap alignment."""
        return jax.device_put(np.array(arr))

    def _put_ask(self, name: str, arr):
        """Device placement for one stacked [B, ...] ask tensor
        (subclass hook, as _put_node)."""
        return jax.device_put(arr)

    def _put_node_side(self) -> None:
        """Ship the full node-side tensors to device (initial build and
        the repack-fallback path) and rebuild everything derived from
        the node axis."""
        t = self.template
        self._dev_node = {
            "avail": self._put_node("avail", t.avail),
            "reserved": self._put_node("reserved", t.reserved),
            "valid": self._put_node("valid", t.valid),
            "node_dc": self._put_node("node_dc", t.node_dc),
            "attr_rank": self._put_node("attr_rank", t.attr_rank),
            "dev_cap": self._put_node("dev_cap", t.dev_cap),
        }
        if t.ev_prio is not None:
            # evictable-alloc planes live in HBM next to the other
            # node-axis planes (delta-maintained through apply_delta)
            self._dev_node["ev_prio"] = self._put_node("ev_prio",
                                                       t.ev_prio)
            self._dev_node["ev_res"] = self._put_node("ev_res", t.ev_res)
        self._used = self._put_node("used", t.used0)
        self._dev_used = self._put_node("dev_used", t.dev_used0)
        # compact int16 result payload needs int16-expressible node ids
        self._compact = t.avail.shape[0] < 32768
        self._default_host_ok = np.zeros((self.gp, t.avail.shape[0]),
                                         bool)
        self._default_host_ok[:, :t.n_real] = True
        self.delta_counters["bytes_dispatched_full"] += int(
            t.avail.nbytes + t.reserved.nbytes + t.valid.nbytes
            + t.node_dc.nbytes + t.attr_rank.nbytes + t.dev_cap.nbytes
            + t.used0.nbytes + t.dev_used0.nbytes
            + (t.ev_prio.nbytes + t.ev_res.nbytes
               if t.ev_prio is not None else 0))

    def _delta_set(self, arr, idx, rows):
        """Row-scatter 'set' into resident node state (subclass hook:
        the mesh solver routes rows to the owning shard — the plain
        jit scatter is only partition-safe on one device)."""
        from .kernel import delta_scatter_set
        return delta_scatter_set(arr, idx, rows)

    def _delta_add(self, arr, idx, rows):
        """Row-scatter 'add' into carried usage (subclass hook, as
        _delta_set)."""
        from .kernel import delta_scatter_add
        return delta_scatter_add(arr, idx, rows)

    # ------------------------------------------------- delta lifecycle
    def apply_delta(self, delta) -> str:
        """Apply a ClusterDelta to the device-resident cluster state.

        The incremental path (returns "delta") scatters only the touched
        rows into the HBM-resident avail/reserved/valid/attr/dev arrays
        and the carried usage, via donate-buffer kernels — no [Np, ...]
        re-tensorization, no full re-put.  Falls back to a full repack
        (returns "repack") when the delta steps outside the interned
        universe (new dc / attr value / device pattern — the
        interning-table invalidation), overflows the padded node axis,
        or touches more than `delta_threshold` of the real node slots.
        """
        from .tensorize import apply_node_delta_host
        if delta.empty():
            return "delta"
        nd = self._tz.delta_pack(self.template, self.node_index, delta)
        if nd is not None:
            ratio = nd.ratio(self.template.n_real)
            self.delta_counters["last_delta_ratio"] = round(ratio, 6)
        if nd is None or nd.ratio(self.template.n_real) \
                > self.delta_threshold:
            self.repack(delta)
            return "repack"
        n_real_before = self.template.n_real
        apply_node_delta_host(self.template, nd, self.nodes,
                              self.node_index)
        # pow2-pad the scatter payloads so steady-state delta waves
        # (whose row counts vary wave to wave) reuse a handful of
        # compiled scatter variants instead of retracing per shape:
        # "set" pads by repeating row 0 (duplicate identical writes),
        # "add" pads with zero rows at slot 0 (no-op adds)
        def _pad(idx, rows, repeat_first):
            M = idx.size
            P = 8
            while P < M:
                P *= 2
            if P == M:
                return idx, rows
            if repeat_first:
                pad_i = np.full(P - M, idx[0], idx.dtype)
                pads = [np.repeat(r[:1], P - M, axis=0) for r in rows]
            else:
                pad_i = np.zeros(P - M, idx.dtype)
                pads = [np.zeros((P - M,) + r.shape[1:], r.dtype)
                        for r in rows]
            return (np.concatenate([idx, pad_i]),
                    [np.concatenate([r, p]) for r, p in zip(rows, pads)])

        if nd.touches_nodes():
            from ..chaos.injection import global_injections
            inj = global_injections.get("delta_row")
            if inj is not None:
                # chaos site "delta_row" (ISSUE 14): corrupt the
                # device-bound scatter rows AFTER the host template took
                # the clean apply — the planes diverge silently until a
                # checksum audit (check_plane_checksums) catches it
                inj.fire()
                k = min(int(inj.args.get("rows", 1)), nd.avail.shape[0])
                nd.avail = nd.avail.copy()
                nd.avail[:k] += 1.0
            dn = self._dev_node
            idx, (r_avail, r_res, r_valid, r_dc, r_attr, r_dev) = _pad(
                nd.idx, [nd.avail, nd.reserved, nd.valid,
                         nd.node_dc.astype(np.asarray(
                             dn["node_dc"]).dtype), nd.attr_rank,
                         nd.dev_cap], repeat_first=True)
            dn["avail"] = self._delta_set(dn["avail"], idx, r_avail)
            dn["reserved"] = self._delta_set(dn["reserved"], idx,
                                             r_res)
            dn["valid"] = self._delta_set(dn["valid"], idx, r_valid)
            dn["node_dc"] = self._delta_set(dn["node_dc"], idx, r_dc)
            dn["attr_rank"] = self._delta_set(dn["attr_rank"], idx,
                                              r_attr)
            dn["dev_cap"] = self._delta_set(dn["dev_cap"], idx, r_dev)
            # node-shape changes invalidate every cached host mask and
            # packed batch (driver/volume feasibility, host_ok widths)
            self._node_epoch += 1
            self._row_cache.clear()
            self._drv_cache.clear()
            self._eval_cache.clear()
            if self.template.n_real != n_real_before:
                self._default_host_ok = np.zeros(
                    (self.gp, self.template.avail.shape[0]), bool)
                self._default_host_ok[:, :self.template.n_real] = True
                self._const_cache = {
                    k: v for k, v in self._const_cache.items()
                    if k[0] != "host_ok"}
        if nd.u_idx.size:
            u_idx, (u_res, u_dev) = _pad(nd.u_idx, [nd.u_res, nd.u_dev],
                                         repeat_first=False)
            self._used = self._delta_add(self._used, u_idx, u_res)
            self._dev_used = self._delta_add(self._dev_used, u_idx,
                                             u_dev)
        if self.template.ev_lists is not None:
            # eviction-plane rows the host apply just recomputed
            # (_apply_evict_delta) scatter like every other node plane
            ev_slots = sorted({s for s, _ in nd.alloc_place}
                              | {s for s, _ in nd.alloc_stop})
            ev_slots = [s for s in ev_slots
                        if s < self.template.ev_prio.shape[0]]
            if ev_slots:
                self._ev_epoch += 1
                t = self.template
                e_idx, (e_prio, e_res) = _pad(
                    np.asarray(ev_slots, np.int32),
                    [t.ev_prio[ev_slots], t.ev_res[ev_slots]],
                    repeat_first=True)
                dn = self._dev_node
                dn["ev_prio"] = self._delta_set(dn["ev_prio"], e_idx,
                                                e_prio)
                dn["ev_res"] = self._delta_set(dn["ev_res"], e_idx,
                                               e_res)
        self.delta_counters["delta_applies"] += 1
        self.delta_counters["bytes_dispatched_delta"] += nd.nbytes()
        return "delta"

    def repack(self, delta=None) -> None:
        """Full-repack fallback: rebuild the node-side template from the
        current node set (delta applied host-side first, removed nodes
        compacted away) and re-put it whole.  Carried usage transfers by
        node id; usage deltas in `delta` are folded in host-side."""
        from .tensorize import alloc_usage_vector
        used, dev_used = self.usage()        # one sync
        old_ids = list(self.template.node_ids)
        by_id = {n.id: n for n in self.nodes}
        removed = set()
        if delta is not None:
            for n in delta.upsert_nodes:
                by_id[n.id] = n
            removed = set(delta.remove_node_ids)
        # keep join order, compact tombstones away; an upsert in the
        # triggering delta revives a previously-removed slot
        upserted = ({n.id for n in delta.upsert_nodes}
                    if delta is not None else set())
        new_nodes = []
        seen = set()
        for i, nid in enumerate(old_ids):
            if nid in removed:
                continue
            if not self.template.valid[i] and nid not in upserted:
                continue              # old tombstone stays dead
            new_nodes.append(by_id[nid])
            seen.add(nid)
        if delta is not None:
            for n in delta.upsert_nodes:
                if n.id not in seen and n.id not in removed:
                    new_nodes.append(n)
                    seen.add(n.id)
        old_ev_lists = (None if self.template.ev_lists is None else
                        {nid: self.template.ev_lists[i]
                         for i, nid in enumerate(old_ids)
                         if i < len(self.template.ev_lists)})
        self.nodes = new_nodes
        self.template = self._tz.pack(self.nodes, self._probe_asks,
                                      evict_e=self.evict_e)
        self.node_index = {n.id: i for i, n in enumerate(self.nodes)}
        # carry usage across by node id (slots moved in the compaction)
        t = self.template
        if t.ev_lists is not None and old_ev_lists is not None:
            # eviction candidates carry by node id too
            from .tensorize import _evict_row
            E = t.ev_prio.shape[1]
            for j, nid in enumerate(t.node_ids):
                cands = old_ev_lists.get(nid)
                if cands:
                    t.ev_lists[j] = list(cands)
                    t.ev_prio[j], t.ev_res[j], t.ev_ids[j] = _evict_row(
                        cands, E)
        for i, nid in enumerate(old_ids):
            j = self.node_index.get(nid)
            if j is not None:
                t.used0[j] = used[i]
                t.dev_used0[j] = dev_used[i]
        if delta is not None:
            for nid, alloc in delta.place:
                j = self.node_index.get(nid)
                if j is not None:
                    t.used0[j] += alloc_usage_vector(alloc)
            for nid, alloc in delta.stop:
                j = self.node_index.get(nid)
                if j is not None:
                    t.used0[j] -= alloc_usage_vector(alloc)
            if t.ev_lists is not None:
                from .tensorize import apply_evict_ops
                slot_ops = lambda grp: [  # noqa: E731
                    (j, a) for nid, a in grp
                    for j in (self.node_index.get(nid),)
                    if j is not None]
                apply_evict_ops(t, slot_ops(delta.stop),
                                slot_ops(delta.place))
        self._node_epoch += 1
        self._ev_epoch += 1
        self._row_cache.clear()
        self._drv_cache.clear()
        self._eval_cache.clear()
        self._const_cache.clear()
        self.delta_counters["repack_fallbacks"] += 1
        self._put_node_side()

    def pack_batch(self, asks: Sequence[PlacementAsk],
                   job_keys: Optional[set] = None
                   ) -> Optional[PackedBatch]:
        """Ask-side-only pack against the resident universe. job_keys
        overrides the same-job stream guard's key set — merge_asks
        callers pass the PRE-merge keys so absorbed jobs still count."""
        pb = self._tz.repack_asks(self.nodes, asks, self.template,
                                  gp=self.gp, kp=self.kp,
                                  drv_cache=self._drv_cache,
                                  row_cache=self._row_cache)
        if pb is not None:
            pb.job_keys = (job_keys if job_keys is not None else
                           {(a.job.namespace, a.job.id) for a in asks})
        return pb

    def pack_batch_cached(self, asks: Sequence[PlacementAsk],
                          job_keys: Optional[set] = None
                          ) -> Optional[PackedBatch]:
        return pack_batch_cached(self, asks, job_keys)

    def merge_asks(self, asks: Sequence[PlacementAsk]
                   ) -> Tuple[List[PlacementAsk], set]:
        """Throughput-mode ask dedup: asks with the SAME spec signature
        and no per-eval state collapse into one group row with the
        summed count, shrinking the [G, N] wave work by the workload's
        duplication factor — the columnar payoff of coalescing evals.
        Job-scoped soft scoring (anti-affinity, spread progress) is then
        computed over the merged population rather than per job; the
        hard commit quotas stay exact, and distinct_hosts (at ANY level,
        incl. per-task) / stateful asks never merge. Returns (merged
        asks, job keys of EVERY original ask — pass to pack_batch so the
        stream guard still sees absorbed jobs). Exact-mode callers
        (tests, quality comparisons) skip this entirely."""
        import dataclasses
        from ..scheduler import feasible as hostfeas
        from ..structs import CONSTRAINT_DISTINCT_HOSTS
        signer = self._tz.ask_signer()
        first: Dict = {}
        counts: Dict = {}
        out: List[PlacementAsk] = []
        order: List = []
        keys = {(a.job.namespace, a.job.id) for a in asks}
        for a in asks:
            stateful = (a.penalty_nodes or a.existing_by_node
                        or a.distinct_hosts_blocked or a.spread_seed
                        or a.property_limits)
            distinct = any(
                c.operand == CONSTRAINT_DISTINCT_HOSTS
                for c in hostfeas.merged_constraints(a.job, a.tg))
            if stateful or distinct:
                out.append(a)
                continue
            sig = signer(a)
            if sig in counts:
                counts[sig] += a.count
            else:
                first[sig] = a
                counts[sig] = a.count
                order.append(sig)
        merged = [
            (first[sig] if counts[sig] == first[sig].count
             else dataclasses.replace(first[sig], count=counts[sig]))
            for sig in order]
        return merged + out, keys

    def solve_stream(self, batches: Sequence[PackedBatch],
                     seeds: Optional[Sequence[int]] = None
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                np.ndarray]:
        """Solve B ask batches in ONE device call.

        Returns (choice [B, K, TOP_K] int, ok [B, K, TOP_K] bool,
        score [B, K, TOP_K] float, status [B, K] int — STATUS_*).
        Resource usage carries on device: a later batch sees every
        earlier batch's placements, and the carried usage persists for
        the next solve_stream call.  STATUS_RETRY placements (wave
        budget ran out) should be resubmitted in a later stream.

        A job may appear in at most ONE batch per stream (the broker's
        per-job eval serialization): job-scoped scoring state is seeded
        per batch and does not carry.

        `seeds`: optional per-batch tie-break seeds (see the kernel's
        jitter note). None keeps exact deterministic scoring; passing
        distinct seeds fans identical asks across equal-scoring nodes,
        which converges contended batches in fewer waves.
        """
        import time as _t
        t0 = _t.perf_counter()
        out = self._unpack(self.solve_stream_async(batches, seeds))
        self.last_solve_stats = {"n_batches": len(batches),
                                 "wall_s": _t.perf_counter() - t0}
        return out

    def solve_stream_async(self, batches: Sequence[PackedBatch],
                           seeds: Optional[Sequence[int]] = None,
                           lanes: Optional[int] = None):
        """Dispatch a stream WITHOUT fetching: returns the device-side
        packed result (pass to finish_stream to unpack).  Lets callers
        pipeline independent streams (e.g. one per region/solver) so
        their transport round trips overlap — JAX dispatch is async, and
        the carried usage updates device-side immediately.

        `lanes` overrides the solver's `fused_lanes` width for this
        call: > 1 routes multi-batch streams to the lane-parallel
        chunked scan-of-vmap (ISSUE 20) — L batches solve per scan
        step against the carried snapshot and revalidate cross-lane,
        bouncing conflicts to STATUS_RETRY.  1 (the default) is the
        serial scan, bit-identical to every earlier release.
        Preemption streams always stay serial (the eviction pass has
        no cross-lane revalidation form)."""
        self._check_stream_jobs(batches)
        self._check_batch_axis(batches)
        has_distinct = self._has_distinct(batches)
        preempt = self._preempt_on(has_distinct)
        L = int(self.fused_lanes if lanes is None else lanes)
        if L > 1 and len(batches) > 1 and not preempt:
            return self._solve_lanes(batches, seeds, L, has_distinct)
        self.last_lane_counters = None
        stacked = self._stack_args(batches)
        n_places = np.asarray([pb.n_place for pb in batches], np.int32)
        seed_arr = (np.zeros(len(batches), np.int32) if seeds is None
                    else np.asarray(list(seeds), np.int32))
        (self._used, self._dev_used, out, self.last_evict,
         self.last_waves, self.last_rescore_waves) = _stream_kernel(
            self._dev_node["avail"], self._dev_node["reserved"],
            self._dev_node["valid"], self._dev_node["node_dc"],
            self._dev_node["attr_rank"], self._dev_node["dev_cap"],
            self._used, self._dev_used, stacked, n_places, seed_arr,
            ev_res=self._dev_node.get("ev_res"),
            ev_prio=self._dev_node.get("ev_prio"),
            has_spread=self._has_spread(batches),
            group_count_hint=self._group_count_hint(batches),
            max_waves=self.max_waves, wave_mode=self.wave_mode,
            has_distinct=has_distinct,
            has_devices=self._has_devices(batches),
            stack_commit=self.stack_commit, compact=self._compact,
            pallas_mode=self.pallas, shortlist_c=self.shortlist_c,
            has_preempt=preempt)
        return out

    def _solve_lanes(self, batches: Sequence[PackedBatch], seeds,
                     L: int, has_distinct: bool):
        """Lane-parallel stream dispatch (ISSUE 20): pad B up to a
        multiple of L with zero-place rows (repeating the last batch's
        planes — nothing solves, nothing commits, the padding never
        leaves the device) and run the chunked scan-of-vmap kernel.
        Revalidation counters stay device-side until lane_counters()."""
        B = len(batches)
        pad = (-B) % L
        pbs = list(batches) + [batches[-1]] * pad
        stacked = self._stack_args(pbs)
        n_places = np.asarray(
            [pb.n_place for pb in batches] + [0] * pad, np.int32)
        seed_list = ([0] * B if seeds is None else list(seeds))
        seed_arr = np.asarray(seed_list + [0] * pad, np.int32)
        (self._used, self._dev_used, out, waves, rescores, bounced,
         committed) = _lane_stream_kernel(
            self._dev_node["avail"], self._dev_node["reserved"],
            self._dev_node["valid"], self._dev_node["node_dc"],
            self._dev_node["attr_rank"], self._dev_node["dev_cap"],
            self._used, self._dev_used, stacked, n_places, seed_arr,
            lanes=L, has_spread=self._has_spread(batches),
            group_count_hint=self._group_count_hint(batches),
            max_waves=self.max_waves,
            # "while" drains when EVERY lane converges; the scan
            # shape's per-wave skip cond is per-lane (batched) and
            # would pay the whole wave budget under the vmap
            wave_mode="while",
            has_distinct=has_distinct,
            has_devices=self._has_devices(batches),
            stack_commit=self.stack_commit, compact=self._compact,
            pallas_mode=self.pallas, shortlist_c=self.shortlist_c)
        if pad:
            out, waves, rescores = out[:B], waves[:B], rescores[:B]
            bounced, committed = bounced[:B], committed[:B]
        self.last_evict = None
        self.last_waves = waves
        self.last_rescore_waves = rescores
        self.last_lane_counters = {
            "lanes": L, "chunks": (B + pad) // L,
            "bounced": bounced, "committed": committed}
        return out

    def lane_counters(self) -> Optional[Dict]:
        """Fetch (one sync) the last lane-parallel stream's
        revalidation counters: bounced/committed placement totals and
        the bounce rate the adaptive lane-width controller feeds on
        (fleet.LaneWidthController.note_round).  None after a serial
        stream."""
        lc = self.last_lane_counters
        if lc is None:
            return None
        bounced = int(np.asarray(lc["bounced"]).sum())
        committed = int(np.asarray(lc["committed"]).sum())
        total = bounced + committed
        return {"lanes": int(lc["lanes"]), "chunks": int(lc["chunks"]),
                "bounced": bounced, "committed": committed,
                "bounce_rate": (bounced / total) if total else 0.0}

    def _preempt_on(self, has_distinct: bool) -> bool:
        """Eviction waves run only when the planes are resident and
        the stream has no distinct_hosts groups (the pass statically
        refuses them — those batches keep the host-side walk)."""
        return ("ev_prio" in self._dev_node) and not has_distinct

    def finish_stream(self, out) -> Tuple[np.ndarray, np.ndarray,
                                          np.ndarray, np.ndarray]:
        return self._unpack(out)

    def solve_stream_pipelined(self, chunks, seeds=None, pack=None,
                               deltas=None
                               ) -> Tuple[np.ndarray, np.ndarray,
                                          np.ndarray, np.ndarray]:
        """True double-buffered wave pipeline.

        Every wave runs three overlapped stages: the DEVICE applies wave
        b's usage-commit delta (scatter into the resident state) and
        solves wave b, while the HOST packs wave b+1 — every dispatch is
        async and the carried usage chains the calls on device, so each
        wave's host-side packing rides entirely under the previous
        wave's delta-apply + solve; ONE concatenated fetch then pays the
        transport round trip once for the whole stream (the fused-call
        schedule pays the same single round trip but serializes ALL
        packing before the first wave can start).

        `chunks`: sequence of PackedBatch, or of ask-lists packed via
        `pack` (default pack_batch_cached).  `deltas`: optional per-wave
        ClusterDelta (or None entries) applied through apply_delta
        BEFORE that wave's solve — the plan-apply feedback path; a delta
        that forces a full repack is still honored, it just pays the
        re-put.  Returns the solve_stream tuple (choice [B,K,TOP_K], ok,
        score, status); per-phase timings land in
        self.last_pipeline_stats (incl. delta_apply_s and the bytes
        each dispatch actually shipped) and per-call wave counts in
        self.last_waves (list of device scalars).
        """
        import time
        chunks = list(chunks)
        if not chunks:
            raise ValueError("solve_stream_pipelined needs >= 1 chunk")
        outs, waves, rescores, evicts = [], [], [], []
        pack_s = dispatch_s = delta_s = 0.0
        bytes_shipped = 0

        def _pack(chunk):
            if isinstance(chunk, PackedBatch):
                return chunk
            pb = (pack or self.pack_batch_cached)(chunk)
            if pb is None:
                raise ValueError(
                    "pipelined chunk fell outside the resident universe")
            return pb

        t0 = time.perf_counter()
        pb_next = _pack(chunks[0])
        pack_s += time.perf_counter() - t0
        for b in range(len(chunks)):
            pb = pb_next
            if deltas is not None and b < len(deltas) \
                    and deltas[b] is not None:
                t0 = time.perf_counter()
                self.apply_delta(deltas[b])
                delta_s += time.perf_counter() - t0
            t0 = time.perf_counter()
            outs.append(self.solve_stream_async(
                [pb], seeds=None if seeds is None else [seeds[b]]))
            waves.append(self.last_waves)
            rescores.append(self.last_rescore_waves)
            evicts.append(self.last_evict)
            bytes_shipped += self.last_dispatch_bytes
            t1 = time.perf_counter()
            dispatch_s += t1 - t0
            if b + 1 < len(chunks):
                # host packs wave b+1 while the device is still applying
                # wave b's delta and solving wave b (async dispatches)
                pb_next = _pack(chunks[b + 1])
                pack_s += time.perf_counter() - t1
        t3 = time.perf_counter()
        packed = np.asarray(outs[0] if len(outs) == 1
                            else self._concat_jit(*outs))
        fetch_s = time.perf_counter() - t3
        self.last_waves = waves
        self.last_rescore_waves = rescores
        self.last_evict = evicts
        self.last_pipeline_stats = {
            "pack_s": pack_s, "dispatch_s": dispatch_s,
            "delta_apply_s": delta_s,
            "fetch_s": fetch_s, "n_dispatches": len(outs),
            "bytes_dispatched": bytes_shipped}
        self.last_solve_stats = {
            "n_batches": len(chunks),
            "wall_s": pack_s + dispatch_s + delta_s + fetch_s}
        return self._unpack(packed)

    @functools.cached_property
    def _concat_jit(self):
        return jax.jit(lambda *xs: jnp.concatenate(xs))

    def wave_traffic(self, batches: Sequence[PackedBatch]) -> Dict:
        """Two-tier per-wave HBM byte model for the CURRENT solve
        configuration (ISSUE 4).

        `bytes_wave1` models a FULL-N pass (the first wave, and every
        rescore-escape wave); `bytes_rewave` models a shortlist-
        resident contention wave — the carried [Gp, C] state plus the
        <= C live gathers, typically 10-100x below the full pass.
        Combined with the measured per-batch counters (last_waves /
        last_rescore_waves) the total is
        ``bytes_wave1 x rescore_waves + bytes_rewave x shortlist
        waves`` — the achieved-GB/s numerator of the roofline report.
        `bytes_per_wave` stays as the full-pass alias for older
        consumers.  Measured counters ride along under "measured" when
        a stream has been dispatched."""
        from . import pallas_kernel as _pk
        from .kernel import resolve_shortlist_c, window_tk
        t = self.template
        Np, R = t.avail.shape
        Gp = max(pb.ask_res.shape[0] for pb in batches)
        K = max(pb.p_ask.shape[0] for pb in batches)
        S = t.sp_desired.shape[1]
        has_spread = self._has_spread(batches)
        TK = window_tk(Gp, K, Np, self._group_count_hint(batches))
        C = (0 if self._has_distinct(batches)
             else resolve_shortlist_c(Np, TK, self.shortlist_c))
        mode = self.pallas
        if mode == "auto":
            V = t.sp_desired.shape[2]
            mode = _pk.resolve_mode(Np, Gp, TK, V, has_spread)
        bytes_wave1, bytes_rewave, passes = model_wave_bytes(
            Np, Gp, K, S, R, has_spread, mode, TK, C)
        out = {"mode": mode, "tile": _pk.pick_tile(Np, Gp),
               "bytes_per_wave": int(bytes_wave1),
               "bytes_wave1": int(bytes_wave1),
               "bytes_rewave": int(bytes_rewave),
               "shortlist_c": int(C),
               "fused_pass_count": passes,
               # resident-delta traffic counters (ISSUE 2): how much
               # node-state each lifecycle path actually dispatched
               "delta": dict(self.delta_counters)}
        m = self.measured_wave_counters()
        if m is not None:
            m["modeled_bytes_total"] = int(
                bytes_wave1 * m["rescore_waves"]
                + bytes_rewave * m["shortlist_waves"])
            out["measured"] = m
        return out

    def trace_attrs(self, batches: Optional[Sequence[PackedBatch]] = None
                    ) -> Dict:
        """Flight-recorder attributes for the last dispatched stream
        (ISSUE 10): the measured wave/rescore/shortlist counters, the
        eviction-commit count, the resident-delta counters and — when
        the solved batches are passed — the full two-tier byte model
        (ICI/DCN tiers included on the mesh solvers, which override
        wave_traffic).  This is the structured form the solve span
        carries instead of the bench-only JSON."""
        attrs: Dict = {"delta": dict(self.delta_counters)}
        m = self.measured_wave_counters()
        if m is not None:
            attrs.update(m)
        ev = self.last_evict
        if ev is not None:
            evs = ev if isinstance(ev, list) else [ev]
            attrs["evict_commits"] = int(sum(
                int(np.asarray(e).any(axis=-1).sum())
                for e in evs if e is not None))
        if self.last_solve_stats is not None:
            attrs["solve"] = dict(self.last_solve_stats)
        if batches:
            try:
                wt = self.wave_traffic(batches)
            except Exception:   # the model must never fail a trace
                wt = None
            if wt is not None:
                attrs["wave_traffic"] = {
                    k: v for k, v in wt.items() if k != "delta"}
        return attrs

    def measured_wave_counters(self) -> Optional[Dict]:
        """Waves / full-rescore waves of the LAST dispatched stream(s)
        (fetch syncs).  shortlist_waves is the remainder — the waves
        that ran shortlist-resident."""
        if self.last_waves is None:
            return None
        def _tot(x):
            if isinstance(x, list):
                return int(sum(int(np.asarray(w).sum()) for w in x))
            return int(np.asarray(x).sum())
        waves = _tot(self.last_waves)
        resc = (_tot(self.last_rescore_waves)
                if self.last_rescore_waves is not None else waves)
        return {"waves_total": waves, "rescore_waves": resc,
                "shortlist_waves": waves - resc}

    def health_counters(self):
        """Fleet health reduction over the RESIDENT planes (ISSUE 15):
        one kernel dispatch + one fetch, no repack, no host walk.
        Returns a telemetry.HealthCounters bit-identical to the numpy
        twin over the same template/usage mirrors."""
        from ..telemetry.health import device_health_counters
        return device_health_counters(self)

    @staticmethod
    def _has_spread(batches: Sequence[PackedBatch]) -> bool:
        return bool(any((pb.sp_col[:, 0] >= 0).any() for pb in batches))

    @staticmethod
    def _has_distinct(batches: Sequence[PackedBatch]) -> bool:
        return bool(any((pb.distinct >= 0).any() for pb in batches))

    @staticmethod
    def _has_devices(batches: Sequence[PackedBatch]) -> bool:
        return bool(any(pb.dev_ask.any() for pb in batches))

    @staticmethod
    def _group_count_hint(batches: Sequence[PackedBatch],
                          floor: int = 6) -> int:
        """Pow2-rounded largest per-group placement count across the
        stream (sizes the kernel's wave width; pow2 rounding bounds the
        number of distinct compiled variants).  `floor` is the pow2
        exponent floor: 6 (=64) for the device path so drain/retry
        batches share one compiled bucket; the host path passes 3 —
        no compile, so the window can track real demand."""
        m = 1
        for pb in batches:
            if pb.n_place:
                cm = pb.__dict__.get("_count_max")
                if cm is None:
                    cm = int(np.bincount(pb.p_ask[:pb.n_place]).max())
                    pb.__dict__["_count_max"] = cm
                m = max(m, cm)
        # floor at 64: one compiled variant covers all small counts
        # (reduced drain/retry batches would otherwise each compile
        # their own bucket). The ceiling mirrors the kernel's wave-width
        # clamp (W = min(2*hint, w_cap)) — larger hints would compile
        # byte-identical programs.
        from .kernel import _MERGED_W_CAP, _WIDE_W_CAP
        gp = max((pb.ask_res.shape[0] for pb in batches), default=0)
        cap = (_MERGED_W_CAP if gp <= MERGED_GP_MAX else _WIDE_W_CAP) // 2
        return min(1 << max(floor, (m - 1).bit_length()), cap)

    @staticmethod
    def _unpack(out) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                              np.ndarray]:
        return unpack_stream(out)

    def _stack_args(self, batches: Sequence[PackedBatch]):
        """Stack ask tensors on a leading batch axis, substituting
        cached device-resident constants for the big [G, N] arrays when
        every batch carries the default value (all-zero coll0 / penalty
        / a_host, universe-default host_ok) — the common fresh-job case.
        A host-side compare is cheaper than shipping the dense zeros.

        Single-batch dispatches (the pipelined steady-state schedule)
        additionally cache the fully device-put stacked dict ON the
        PackedBatch, keyed by the node epoch: a re-dispatched batch —
        the blocked-eval retry / drain re-eval / same-jobs steady state
        — ships ZERO ask bytes.  last_dispatch_bytes records what each
        call actually moved (the delta-vs-full traffic counters)."""
        B = len(batches)
        if B == 1:
            cached = batches[0].__dict__.get("_dev_stacked")
            if cached is not None and cached[0] == self._node_epoch:
                self.last_dispatch_bytes = 0
                return cached[1]
        else:
            # B>1 twin of the single-batch step cache (ISSUE 20
            # satellite): a steady-state stream re-dispatching the SAME
            # batch objects — the lane sweep's per-family packed memo,
            # the retry drain — ships zero ask bytes.  Keyed on batch
            # identity; the entry holds strong refs to the batches so
            # the ids cannot be recycled while cached.
            skey = tuple(id(pb) for pb in batches)
            cached = self._stream_stack_cache.get(skey)
            if cached is not None and cached[0] == self._node_epoch:
                self.last_dispatch_bytes = 0
                return cached[2]
        stacked = {}
        shipped = 0
        t = self.template
        # identity fast path: repack_asks hands out one shared read-only
        # plane per default [G, N] argument — recognizing it skips both
        # the O(G*N) .any()/array_equal scans and the host stack
        def _all_shared(mats, name):
            shared = self._tz._planes.get(
                (name, self.gp, t.avail.shape[0], t.n_real))
            return shared is not None and all(m is shared for m in mats)
        for name in _ASK_ARGS:
            mats = [getattr(pb, name) for pb in batches]
            if name in ("coll0", "penalty", "a_host") and (
                    _all_shared(mats, name)
                    or not any(m.any() for m in mats)):
                key = (name, B)
                if key not in self._const_cache:
                    self._const_cache[key] = self._put_ask(
                        name,
                        np.zeros((B,) + mats[0].shape, mats[0].dtype))
                stacked[name] = self._const_cache[key]
                continue
            if name == "host_ok" and (
                    _all_shared(mats, name)
                    or all(np.array_equal(m, self._default_host_ok)
                           for m in mats)):
                key = (name, B)
                if key not in self._const_cache:
                    self._const_cache[key] = self._put_ask(
                        name, np.broadcast_to(
                            self._default_host_ok,
                            (B,) + self._default_host_ok.shape).copy())
                stacked[name] = self._const_cache[key]
                continue
            arr = (self._staged_stack(name, mats) if B > 1
                   else np.stack(mats))
            if name in ("host_ok", "penalty") and self._pack_bool_planes:
                # ship the bool planes bitpacked (uint32 lanes, 8x
                # fewer transport bytes); _solve_one unpacks on device
                from .masks import np_pack_bool_u32
                arr = np_pack_bool_u32(arr)
            shipped += arr.nbytes
            stacked[name] = arr
        self.last_dispatch_bytes = shipped
        self.delta_counters["bytes_dispatched_ask"] += shipped
        self.delta_counters["ask_dispatches"] += 1
        if B == 1:
            dev = {k: (self._put_ask(k, v) if isinstance(v, np.ndarray)
                       else v) for k, v in stacked.items()}
            batches[0].__dict__["_dev_stacked"] = (self._node_epoch, dev)
            return dev
        # device-put through a COPY: the staged planes are views into
        # the rotating staging ring, and CPU device_put may alias that
        # memory zero-copy — a later round refilling the ring would
        # corrupt the cached device arrays through the alias
        dev = {k: (self._put_ask(k, np.array(v))
                   if isinstance(v, np.ndarray) else v)
               for k, v in stacked.items()}
        if len(self._stream_stack_cache) >= 4:
            self._stream_stack_cache.pop(
                next(iter(self._stream_stack_cache)))
        self._stream_stack_cache[skey] = (self._node_epoch,
                                          tuple(batches), dev)
        return dev

    def _check_batch_axis(self, batches: Sequence[PackedBatch]) -> None:
        """A full repack can change the padded node axis; batches packed
        before it carry [G, Np_old] planes and must be re-packed."""
        Np = self.template.avail.shape[0]
        for pb in batches:
            if pb.host_ok.shape[1] != Np:
                raise ValueError(
                    "PackedBatch predates a full repack (node axis "
                    f"{pb.host_ok.shape[1]} != {Np}); re-pack its asks")

    @staticmethod
    def _check_stream_jobs(batches: Sequence[PackedBatch]) -> None:
        seen: set = set()
        for pb in batches:
            keys = getattr(pb, "job_keys", None)
            if keys:
                overlap = seen & keys
                if overlap:
                    raise ValueError(
                        f"job {overlap} appears in multiple batches of "
                        "one stream; job-scoped state (distinct_hosts, "
                        "anti-affinity, spread) would not be visible "
                        "across them")
                seen |= keys

    def _staged_stack(self, name: str, mats) -> np.ndarray:
        """Pow2-bucketed preallocated staging for the fused path's
        B>1 stacked ask planes (ISSUE 20 satellite): `np.stack`
        allocates a fresh [B, ...] block per arg per round, which at
        128-member rounds is the dispatch stage's single biggest host
        cost — these buffers are keyed (arg, pow2(B), row shape) and
        reused round over round, copying rows in place.  TWO buffers
        rotate per key: CPU `device_put` may alias the host memory
        zero-copy and the coordinator keeps exactly one round in
        flight, so the previous round's dispatch can still be reading
        buffer A while this round fills buffer B."""
        B = len(mats)
        bucket = 1 << max(0, (B - 1).bit_length())
        key = (name, bucket, mats[0].shape, mats[0].dtype.str)
        ring = self._stage_cache.get(key)
        if ring is None:
            ring = [np.empty((bucket,) + mats[0].shape, mats[0].dtype),
                    np.empty((bucket,) + mats[0].shape, mats[0].dtype),
                    0]
            self._stage_cache[key] = ring
        buf = ring[ring[2]]
        ring[2] ^= 1
        for i, m in enumerate(mats):
            buf[i] = m
        return buf[:B]

    # ------------------------------------------------ retrace guard
    @staticmethod
    def compile_count() -> int:
        """Total compiled variants across the resident dispatch
        kernels (the jit compile-cache probe behind the retrace-count
        regression guard, nomadlint JIT203's runtime twin): steady-state
        streams over a fixed node/ask universe must not grow this —
        every new entry is a silent recompile eating the PR 1/2 wins."""
        return sum(fn._cache_size() for fn in
                   (_stream_kernel, _lane_stream_kernel))

    def usage(self) -> Tuple[np.ndarray, np.ndarray]:
        """Fetch the carried device usage (one sync — call sparingly)."""
        return np.asarray(self._used), np.asarray(self._dev_used)

    def plane_checksum(self) -> int:
        """Fingerprint the DEVICE-resident node planes (one fetch —
        call at quiesce points only).  Must equal
        tensorize.template_checksum(self.template) whenever the mesh
        is healthy: the delta-scatter path, a repack, and an elastic
        recover all have to land the device planes bit-identical to
        the raft-fed host template (ISSUE 14 invariant harness)."""
        from .tensorize import plane_crc
        t = self.template
        dn = self._dev_node
        meta = f"{t.n_real}:{','.join(t.node_ids)}".encode()
        return plane_crc(dn["avail"], dn["reserved"], dn["valid"],
                         dn["node_dc"], dn["attr_rank"], dn["dev_cap"],
                         ev_prio=dn.get("ev_prio"),
                         ev_res=dn.get("ev_res"), meta=meta)

    def reset_usage(self, used0: Optional[np.ndarray] = None,
                    dev_used0: Optional[np.ndarray] = None) -> None:
        t = self.template
        self._used = self._put_node(
            "used", t.used0 if used0 is None else used0)
        self._dev_used = self._put_node(
            "dev_used", t.dev_used0 if dev_used0 is None else dev_used0)
