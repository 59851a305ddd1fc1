"""Multi-chip solve: shard the node axis over a TPU mesh.

The scaling-book recipe (SURVEY §2.6): pick a mesh, annotate input
shardings, and let XLA/GSPMD insert the collectives. The node axis is our
"long sequence" (SURVEY §5.7) — feasibility masking and scoring partition
cleanly along it; the per-step masked top-k and the winner-commit scatter
become cross-shard collectives (reduce over ICI) that XLA derives from
the shardings, replacing hand-written NCCL/MPI in the reference's world.

Two levels:
  * `sharded_solve_args`  — one region's solve, node axis sharded.
  * `federated_solve_args` — BASELINE config 5: a leading region axis
    (independent solves, the federation analog of nomad/serf.go regions)
    vmapped and sharded over the mesh's "region" axis; node axis sharded
    within each region's device row.
"""
from __future__ import annotations

import functools
import inspect
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..solver.kernel import solve_kernel
from ..solver.resident import (ResidentSolver, STATUS_COMMITTED,
                               STATUS_FAILED, STATUS_RETRY, _solve_one,
                               model_wave_bytes, pack_out_compact)
from ..solver.tensorize import PackedBatch

# PartitionSpec per solve_kernel positional arg (node axis = "nodes").
_ARG_SPECS: List[P] = [
    P("nodes", None),        # avail [Np, R]
    P("nodes", None),        # reserved
    P("nodes", None),        # used0
    P("nodes"),              # valid [Np]
    P("nodes"),              # node_dc [Np]
    P("nodes", None),        # attr_rank [Np, A]
    P(),                     # ask_res [Gp, R]
    P(),                     # ask_desired [Gp]
    P(),                     # distinct [Gp]
    P(),                     # dc_ok [Gp, NDC]
    P(None, "nodes"),        # host_ok [Gp, Np]
    P(None, "nodes"),        # coll0 [Gp, Np]
    P(None, "nodes"),        # penalty [Gp, Np]
    P(), P(), P(),           # c_op / c_col / c_rank [Gp, C]
    P(), P(), P(), P(),      # a_op / a_col / a_rank / a_weight [Gp, CA]
    P(None, "nodes"),        # a_host [Gp, Np]
    P(), P(), P(),           # sp_col / sp_weight / sp_targeted [Gp, S]
    P(), P(), P(),           # sp_desired / sp_implicit / sp_used0
    P("nodes", None),        # dev_cap [Np, D]
    P("nodes", None),        # dev_used0 [Np, D]
    P(),                     # dev_ask [Gp, D]
    P(),                     # p_ask [K]
    P(),                     # n_place (scalar)
]


def _kernel_positional_count() -> int:
    """Required positional parameters of solve_kernel (everything
    before the defaulted `seed`)."""
    sig = inspect.signature(inspect.unwrap(solve_kernel))
    return sum(1 for p in sig.parameters.values()
               if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
               and p.default is p.empty)


# _ARG_SPECS is maintained BY HAND parallel to solve_kernel's
# positional signature: a kernel arg added without a spec would be
# silently replicated (or worse, the specs would shift and misshard an
# unrelated arg).  Fail at import time instead.
_N_KERNEL_POSITIONAL = _kernel_positional_count()
assert len(_ARG_SPECS) == _N_KERNEL_POSITIONAL, (
    f"sharded._ARG_SPECS lists {len(_ARG_SPECS)} specs but solve_kernel "
    f"takes {_N_KERNEL_POSITIONAL} positional args — update _ARG_SPECS "
    "for the new/removed kernel argument")


def kernel_args(pb: PackedBatch) -> Tuple:
    """PackedBatch -> solve_kernel positional args."""
    return (pb.avail, pb.reserved, pb.used0, pb.valid, pb.node_dc,
            pb.attr_rank, pb.ask_res, pb.ask_desired, pb.distinct, pb.dc_ok,
            pb.host_ok, pb.coll0, pb.penalty, pb.c_op, pb.c_col, pb.c_rank,
            pb.a_op, pb.a_col, pb.a_rank, pb.a_weight, pb.a_host, pb.sp_col,
            pb.sp_weight, pb.sp_targeted, pb.sp_desired, pb.sp_implicit,
            pb.sp_used0, pb.dev_cap, pb.dev_used0, pb.dev_ask, pb.p_ask,
            np.int32(pb.n_place))


def make_mesh(n_devices: Optional[int] = None,
              n_regions: int = 1) -> Mesh:
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    assert n % n_regions == 0, "devices must divide evenly into regions"
    grid = np.array(devices).reshape(n_regions, n // n_regions)
    return Mesh(grid, ("region", "nodes"))


def _shard_args(args: Tuple, mesh: Mesh, region_axis: bool) -> Tuple:
    out = []
    for arg, spec in zip(args, _ARG_SPECS):
        if region_axis:
            spec = P("region", *spec)
        out.append(jax.device_put(arg, NamedSharding(mesh, spec)))
    return tuple(out)


def sharded_solve_args(args: Tuple, mesh: Mesh):
    """Run one solve with the node axis sharded over mesh axis "nodes".
    XLA partitions the kernel and inserts the cross-shard reductions for
    the masked top-k and commit scatter."""
    return solve_kernel(*_shard_args(args, mesh, region_axis=False))


def sharded_solve(pb: PackedBatch, mesh: Mesh):
    return sharded_solve_args(kernel_args(pb), mesh)


# vmap over a leading region axis: each region is an independent solve
# (regions don't share nodes), mapping onto disjoint device rows.
# wave_mode="while": under vmap the scan shape's cond-skip lowers to
# select and pays the full wave budget per lane (see kernel.py loop-
# shape note); the while_loop runs only as deep as the slowest region.
_federated_kernel = jax.jit(jax.vmap(
    # shortlist off: under vmap its cond degrades to select and both
    # branches would execute every wave for every lane
    functools.partial(solve_kernel, wave_mode="while", shortlist_c=-1)))


def federated_solve(pbs: Sequence[PackedBatch], mesh: Mesh):
    """Solve R regions at once: inputs stacked on a leading region axis,
    sharded over the mesh "region" axis (all batches must share shapes —
    use one Tensorizer per region with identical padding)."""
    per_region = [kernel_args(pb) for pb in pbs]
    shapes = {tuple(np.shape(a) for a in args) for args in per_region}
    assert len(shapes) == 1, "region batches must be shape-aligned"
    stacked = tuple(np.stack([args[i] for args in per_region])
                    for i in range(len(per_region[0])))
    return _federated_kernel(*_shard_args(stacked, mesh, region_axis=True))


# ===================================================================
# Mesh-resident sharded solve (ISSUE 5)
# ===================================================================
# The GSPMD wrapper above is STATELESS: every solve re-ships the whole
# packed batch and lets XLA guess the collectives, so each wave re-reads
# (and re-gathers) full [G, N] planes.  The mesh-resident path below
# keeps each shard's node planes in its own HBM under a "nodes"-axis
# NamedSharding and runs the wave loop under shard_map with explicit
# candidate-only ICI traffic: per-shard [G, TK_local] (score, global
# node id) keys all-gathered and exactly lex-merged, K-sized commit/
# counter psums — never a [G, N] plane (see solver/kernel.py mesh_axis).

#: ask-side args whose TRAILING axis is the node axis
_PLANE_ASK_ARGS = ("host_ok", "coll0", "penalty", "a_host")

MESH_NODE_AXIS = "nodes"
#: two-tier hierarchy axes (ISSUE 8): the node axis splits over
#: ("hosts", "chips") — candidate keys merge per host over ICI, only
#: host-winner keys cross the DCN between hosts
MESH_HOST_AXIS = "hosts"
MESH_CHIP_AXIS = "chips"
#: three-tier hierarchy axis (ISSUE 13): the node axis splits over
#: ("regions", "hosts", "chips") — candidate keys merge per host over
#: ICI and per region over DCN; only region-winner keys cross the WAN
MESH_REGION_AXIS = "regions"


def make_node_mesh(n_devices: Optional[int] = None) -> Mesh:
    """A 1-D mesh over the node axis (the mesh-resident solver's
    layout; make_mesh keeps the region x nodes grid for the stateless
    wrapper and the federated vmap)."""
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (MESH_NODE_AXIS,))


def env_mesh_hosts() -> Optional[int]:
    """NOMAD_TPU_MESH_HOSTS: host-group count for the two-tier mesh
    (unset/empty/0 -> None: flat single-tier)."""
    import os
    raw = os.environ.get("NOMAD_TPU_MESH_HOSTS", "").strip()
    if not raw or raw == "0":
        return None
    try:
        h = int(raw)
    except ValueError:
        raise ValueError(
            f"NOMAD_TPU_MESH_HOSTS={raw!r} invalid: use a positive "
            "host-group count (0/unset = flat mesh)") from None
    if h <= 0:
        raise ValueError(
            f"NOMAD_TPU_MESH_HOSTS={h} invalid: must be positive")
    return h


def env_mesh_regions() -> Optional[int]:
    """NOMAD_TPU_MESH_REGIONS: region count for the three-tier mesh
    (unset/empty/0 -> None: no WAN tier)."""
    import os
    raw = os.environ.get("NOMAD_TPU_MESH_REGIONS", "").strip()
    if not raw or raw == "0":
        return None
    try:
        r = int(raw)
    except ValueError:
        raise ValueError(
            f"NOMAD_TPU_MESH_REGIONS={raw!r} invalid: use a positive "
            "region count (0/unset = no WAN tier)") from None
    if r <= 0:
        raise ValueError(
            f"NOMAD_TPU_MESH_REGIONS={r} invalid: must be positive")
    return r


def make_two_tier_mesh(n_hosts: Optional[int] = None,
                       n_devices: Optional[int] = None) -> Mesh:
    """A ("hosts", "chips") mesh: the device list factored into
    n_hosts contiguous groups (real fleets would group by actual host
    topology; the CPU simulation groups by enumeration order).
    n_hosts defaults to NOMAD_TPU_MESH_HOSTS."""
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    if n_hosts is None:
        n_hosts = env_mesh_hosts() or 1
    if n_hosts <= 0 or n % n_hosts:
        raise ValueError(
            f"{n} devices do not factor into {n_hosts} hosts x "
            f"{n / max(n_hosts, 1):g} chips; pick a host count that "
            "divides the device count")
    grid = np.array(devices).reshape(n_hosts, n // n_hosts)
    return Mesh(grid, (MESH_HOST_AXIS, MESH_CHIP_AXIS))


def make_three_tier_mesh(n_regions: Optional[int] = None,
                         n_hosts: Optional[int] = None,
                         n_devices: Optional[int] = None) -> Mesh:
    """A ("regions", "hosts", "chips") mesh (ISSUE 13): the device
    list factored into n_regions contiguous region groups of n_hosts
    hosts each (n_hosts is hosts PER REGION).  Defaults come from
    NOMAD_TPU_MESH_REGIONS / NOMAD_TPU_MESH_HOSTS."""
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    if n_regions is None:
        n_regions = env_mesh_regions() or 1
    if n_hosts is None:
        n_hosts = env_mesh_hosts() or 1
    if (n_regions <= 0 or n_hosts <= 0 or n % n_regions
            or (n // n_regions) % n_hosts):
        raise ValueError(
            f"{n} devices do not factor into {n_regions} regions x "
            f"{n_hosts} hosts x chips; pick counts whose product "
            "divides the device count")
    grid = np.array(devices).reshape(
        n_regions, n_hosts, n // (n_regions * n_hosts))
    return Mesh(grid, (MESH_REGION_AXIS, MESH_HOST_AXIS,
                       MESH_CHIP_AXIS))


def _sharded_stream_body(avail, reserved, valid, node_dc, attr_rank,
                         dev_cap, used0, dev_used0, stacked, n_places,
                         seeds, ev_res, ev_prio, node_gid, owner_map,
                         slot_map, *, n_shards, mesh_axes, mesh_hosts,
                         mesh_regions, mesh_nt, tile_np,
                         has_spread, group_count_hint, max_waves,
                         wave_mode, has_distinct, has_devices,
                         stack_commit, compact, pallas_mode,
                         shortlist_c, has_preempt):
    """shard_map body: the resident stream scan with every solve run in
    mesh mode.  All node args are this shard's LOCAL planes; ask
    tensors are replicated except the [B, G, N] planes (node-sharded on
    their last axis).  The eviction planes (ISSUE 7) are node-sharded
    like every other node plane — the kernel's preemption pass is
    shard-local and only per-group eviction KEYS (score, global node
    id) ride the candidate-key ICI exchange.  Outputs: local
    used/dev_used blocks, replicated packed results, psum-replicated
    evict masks, wave counters."""
    def step(carry, xs):
        used, dev_used = carry
        batch, n_place, seed = xs
        res = _solve_one(avail, reserved, valid, node_dc, attr_rank,
                         dev_cap, used, dev_used, batch, n_place, seed,
                         has_spread, group_count_hint, max_waves,
                         wave_mode, has_distinct, has_devices,
                         stack_commit, pallas_mode, shortlist_c,
                         mesh_axis=mesh_axes, mesh_shards=n_shards,
                         has_preempt=has_preempt, ev_res=ev_res,
                         ev_prio=ev_prio, mesh_hosts=mesh_hosts,
                         mesh_regions=mesh_regions,
                         mesh_nt=mesh_nt, tile_np=tile_np,
                         node_gid=node_gid, owner_map=owner_map,
                         slot_map=slot_map)
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


def mesh_node_axes(mesh: Mesh):
    """The node-axis split of a solver mesh: the flat "nodes" axis
    (PR 5), the two-tier ("hosts", "chips") hierarchy (ISSUE 8), or
    the three-tier ("regions", "hosts", "chips") hierarchy (ISSUE 13).
    Returns (axes, n_hosts) where axes is the solve_kernel mesh_axis
    value AND the PartitionSpec element splitting the node dim;
    n_hosts is hosts PER REGION in the three-tier case (use
    mesh_region_count for the region fan-out)."""
    names = mesh.axis_names
    if MESH_HOST_AXIS in names and MESH_CHIP_AXIS in names:
        if MESH_REGION_AXIS in names:
            return ((MESH_REGION_AXIS, MESH_HOST_AXIS,
                     MESH_CHIP_AXIS), int(mesh.shape[MESH_HOST_AXIS]))
        return ((MESH_HOST_AXIS, MESH_CHIP_AXIS),
                int(mesh.shape[MESH_HOST_AXIS]))
    if MESH_NODE_AXIS in names:
        return MESH_NODE_AXIS, 1
    raise ValueError(
        f"mesh must carry a '{MESH_NODE_AXIS}' axis or the "
        f"('{MESH_HOST_AXIS}', '{MESH_CHIP_AXIS}') pair "
        f"(optionally under '{MESH_REGION_AXIS}'), got {names}")


def mesh_region_count(mesh: Mesh) -> int:
    """Region fan-out of a solver mesh (1 when no WAN tier)."""
    return (int(mesh.shape[MESH_REGION_AXIS])
            if MESH_REGION_AXIS in mesh.axis_names else 1)


def _build_sharded_stream_kernel(mesh: Mesh):
    """jit(shard_map(stream)) closed over one mesh: node tensors stay
    sharded in HBM across calls, results and counters come back
    replicated.  The node dimension splits over the flat "nodes" axis
    or the two-tier ("hosts", "chips") pair — the kernel's merge and
    psum tiering follows the axis structure."""
    axis, n_hosts = mesh_node_axes(mesh)
    n_regions = mesh_region_count(mesh)
    n_shards = int(np.prod([mesh.shape[a] for a in
                            (axis if isinstance(axis, tuple)
                             else (axis,))]))
    node2 = P(axis, None)
    node1 = P(axis)
    plane = P(None, None, axis)

    @functools.partial(jax.jit, static_argnames=(
        "has_spread", "group_count_hint", "max_waves", "wave_mode",
        "has_distinct", "has_devices", "stack_commit", "compact",
        "pallas_mode", "shortlist_c", "has_preempt", "mesh_nt",
        "tile_np"))
    def kern(avail, reserved, valid, node_dc, attr_rank, dev_cap,
             used0, dev_used0, stacked, n_places, seeds,
             ev_res=None, ev_prio=None, node_gid=None, owner_map=None,
             slot_map=None, *,
             has_spread=True, group_count_hint=0, max_waves=0,
             wave_mode="scan", has_distinct=True, has_devices=True,
             stack_commit=False, compact=True, pallas_mode="off",
             shortlist_c=0, has_preempt=False, mesh_nt=0, tile_np=0):
        stacked_specs = {k: (plane if k in _PLANE_ASK_ARGS else P())
                         for k in stacked}
        # eviction planes shard on the node axis with the rest of the
        # node-side state; without preemption the (None) placeholders
        # are replicated empties
        ev3 = P(axis, None, None) if has_preempt else P()
        ev2 = P(axis, None) if has_preempt else P()
        gid1 = P(axis) if tile_np else P()
        body = functools.partial(
            _sharded_stream_body, n_shards=n_shards,
            mesh_axes=axis, mesh_hosts=n_hosts,
            mesh_regions=n_regions, mesh_nt=mesh_nt,
            tile_np=tile_np,
            has_spread=has_spread, group_count_hint=group_count_hint,
            max_waves=max_waves, wave_mode=wave_mode,
            has_distinct=has_distinct, has_devices=has_devices,
            stack_commit=stack_commit, compact=compact,
            pallas_mode=pallas_mode, shortlist_c=shortlist_c,
            has_preempt=has_preempt)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(node2, node2, node1, node1, node2, node2,
                      node2, node2, stacked_specs, P(), P(),
                      ev3, ev2, gid1, P(), P()),
            out_specs=(node2, node2, P(), P(), P(), P()),
            check_vma=False)(
            avail, reserved, valid, node_dc, attr_rank, dev_cap,
            used0, dev_used0, stacked, n_places, seeds,
            ev_res, ev_prio, node_gid, owner_map, slot_map)

    return kern


def model_ici_bytes(Gp: int, K: int, A: int, R: int, TKl: int,
                    n_shards: int, want_tables: bool, V: int, TW: int,
                    has_spread: bool) -> Dict:
    """Per-wave ICI byte model for the mesh-resident solve (the third
    tier next to resident.model_wave_bytes' two HBM tiers).

    `bytes_ici_per_wave` is the candidate-KEY traffic: each shard's
    [Gp, tk_local] (f32 score, i32 global id) window+table keys
    all-gathered across `n_shards` — by construction it equals
    tk_local x Gp x n_shards x key_bytes, the ISSUE-5 acceptance
    bound; no [Gp, Np] plane term appears anywhere.
    `bytes_ici_commit_per_wave` adds the K-sized commit-phase psums
    (fit votes, candidate attr rows, explainability counters)."""
    key_bytes = 8                       # f32 score + i32 node id
    tk_local = TKl + ((V + 1) * TW if want_tables else 0)
    window = Gp * tk_local * key_bytes * n_shards
    commit = (2 * K * 4                          # fit / dev-fit votes
              + (K * A * 4 if has_spread else 0)  # candidate attr rows
              + (3 * Gp + Gp * R) * 4             # counters + grp_any
              ) * n_shards
    return {"key_bytes": key_bytes, "tk_local": int(tk_local),
            "devices": int(n_shards),
            "bytes_ici_per_wave": int(window),
            "bytes_ici_commit_per_wave": int(commit),
            "bytes_ici_total_per_wave": int(window + commit),
            "bound_candidate_keys": int(
                tk_local * Gp * n_shards * key_bytes)}


def model_ici_dcn_bytes(Gp: int, K: int, A: int, R: int, TK: int,
                        TKl: int, n_shards: int, n_hosts: int,
                        want_tables: bool, V: int, TKv: int, TW: int,
                        has_spread: bool) -> Dict:
    """Two-tier per-wave interconnect byte model (ISSUE 8), the DCN
    generalization of model_ici_bytes.

    Convention: a tier's bytes/wave counts the bytes ENTERING devices
    across that tier's links (import volume), fleet-wide.  The flat
    single-tier exchange is host-OBLIVIOUS — its all-gather
    materializes every remote shard's window on every chip, so each
    chip imports (S - CPH) remote chunks over DCN.  The tiered
    exchange merges each host over ICI first and ships only
    chip-SLICED host-winner windows across DCN — one host window per
    DCN traversal, in log2(H) recursive-doubling rounds (pow2 H; one
    sliced all-gather otherwise).  Commit psums tier the same way:
    the host-level reduction moves host partials, not shard partials.

    `dcn_cut_vs_flat` is the acceptance figure: modeled DCN bytes/wave
    of the tiered exchange over the flat exchange's cross-host bytes.
    """
    key_bytes = 8                       # f32 score + i32 node id
    H = max(n_hosts, 1)
    CPH = n_shards // H
    # per-shard window chunk (keys + per-value table keys)
    tk_local = TKl + ((V + 1) * TW if want_tables else 0)
    ck = Gp * tk_local * key_bytes
    # host-merged window chunk after the ICI tier
    tk_host = (min(TK, TKl * CPH)
               + ((V + 1) * min(TKv, TW * CPH) if want_tables else 0))
    ch = Gp * tk_host * key_bytes
    # commit-phase vector (fit votes, candidate attr rows, counters)
    cc = (2 * K * 4
          + (K * A * 4 if has_spread else 0)
          + (3 * Gp + Gp * R) * 4)
    # ---- flat single-tier exchange, charged per-chip import ----
    flat_dcn_window = H * CPH * (n_shards - CPH) * ck
    flat_ici_window = H * CPH * (CPH - 1) * ck
    # psum ~ reduce-scatter + all-gather: 2(S-1)/S chunk imports per
    # chip, (S-CPH)/(S-1) of them crossing hosts
    flat_dcn_commit = (2 * H * CPH * (n_shards - CPH) * cc
                       // max(n_shards, 1))
    # ---- tiered exchange ----
    # ICI tier: within-host window gather + the sliced DCN rounds'
    # reassembly gathers
    if H > 1 and H & (H - 1) == 0:
        rounds = H.bit_length() - 1
        dcn_window = H * rounds * ch
    elif H > 1:
        rounds = 1
        dcn_window = H * (H - 1) * ch
    else:
        rounds = 0
        dcn_window = 0
    ici_window = (H * CPH * (CPH - 1) * ck
                  + H * CPH * rounds * ch * (CPH - 1) // max(CPH, 1))
    # commit psums: ICI reduce, then the CHIP-SLICED host tier — each
    # chip ships its 1/CPH slice of the host-reduced vector across
    # DCN (reduce-scatter + host psum + ICI reassembly gather), so a
    # commit vector crosses DCN ~2(H-1)/H times per host, not per chip
    ici_commit = 2 * H * CPH * (CPH - 1) * cc // max(CPH, 1)
    dcn_commit = (2 * (H - 1) * cc) if H > 1 else 0
    dcn_total = dcn_window + dcn_commit
    flat_dcn_total = flat_dcn_window + flat_dcn_commit
    return {
        "key_bytes": key_bytes, "n_hosts": int(H),
        "chips_per_host": int(CPH),
        "tk_local": int(tk_local), "tk_host": int(tk_host),
        "bytes_ici_per_wave": int(ici_window + ici_commit),
        "bytes_dcn_window_per_wave": int(dcn_window),
        "bytes_dcn_commit_per_wave": int(dcn_commit),
        "bytes_dcn_total_per_wave": int(dcn_total),
        "flat_dcn_window_per_wave": int(flat_dcn_window),
        "flat_dcn_total_per_wave": int(flat_dcn_total),
        "dcn_cut_vs_flat": (float(dcn_total) / float(flat_dcn_total)
                            if flat_dcn_total else 0.0),
    }


def model_ici_dcn_wan_bytes(Gp: int, K: int, A: int, R: int, TK: int,
                            TKl: int, n_shards: int, n_regions: int,
                            n_hosts: int, want_tables: bool, V: int,
                            TKv: int, TW: int,
                            has_spread: bool) -> Dict:
    """Three-tier per-wave interconnect byte model (ISSUE 13): the WAN
    generalization of model_ici_dcn_bytes.  `n_hosts` is hosts PER
    REGION; shards split n_regions x n_hosts x chips.

    Same import-volume convention as the DCN model.  Within a region
    the two-tier ICI/DCN exchange runs unchanged (restated here per
    region); across regions only region-winner candidate-key windows
    travel — one region window per WAN traversal, in log2(Rg)
    recursive-doubling rounds (pow2 region counts; one sliced
    all-gather otherwise) — and ONE commit vector crosses the WAN per
    region per psum (reduce-scatter over the chip x host slice, WAN
    psum, in-region reassembly), not one per host or chip.

    `wan_cut_vs_flat` is the acceptance figure: modeled WAN bytes/wave
    of the tiered exchange over the flat single-tier exchange's
    cross-REGION bytes."""
    key_bytes = 8
    Rg = max(n_regions, 1)
    SPR = n_shards // Rg                # shards per region
    base = model_ici_dcn_bytes(Gp, K, A, R, TK, TKl, SPR, n_hosts,
                               want_tables, V, TKv, TW, has_spread)
    H = max(n_hosts, 1)
    CPH = SPR // H
    tk_local = base["tk_local"]
    ck = Gp * tk_local * key_bytes
    # region-merged window chunk after the ICI + DCN tiers
    tk_region = (min(TK, TKl * SPR)
                 + ((V + 1) * min(TKv, TW * SPR) if want_tables
                    else 0))
    cr = Gp * tk_region * key_bytes
    cc = (2 * K * 4
          + (K * A * 4 if has_spread else 0)
          + (3 * Gp + Gp * R) * 4)
    # ---- flat single-tier exchange, charged per-chip import ----
    # every chip imports every chunk outside its own region
    flat_wan_window = n_shards * (n_shards - SPR) * ck
    flat_wan_commit = (2 * n_shards * (n_shards - SPR) * cc
                       // max(n_shards, 1))
    # ---- tiered exchange ----
    if Rg > 1 and Rg & (Rg - 1) == 0:
        rounds = Rg.bit_length() - 1
        wan_window = Rg * rounds * cr
    elif Rg > 1:
        rounds = 1
        wan_window = Rg * (Rg - 1) * cr
    else:
        rounds = 0
        wan_window = 0
    # the WAN rounds' chip-sliced reassembly gathers ride the
    # in-region links: (SPR-1)/SPR of each round's region window
    # re-gathers over ICI+DCN inside every region
    intra_reassembly = (Rg * SPR * rounds * cr * (SPR - 1)
                        // max(SPR, 1))
    wan_commit = (2 * (Rg - 1) * cc) if Rg > 1 else 0
    wan_total = wan_window + wan_commit
    flat_wan_total = flat_wan_window + flat_wan_commit
    out = {
        "key_bytes": key_bytes, "n_regions": int(Rg),
        "shards_per_region": int(SPR), "n_hosts": int(H),
        "chips_per_host": int(CPH),
        "tk_local": int(tk_local), "tk_host": base["tk_host"],
        "tk_region": int(tk_region),
        # per-region two-tier exchange restated fleet-wide, plus the
        # WAN reassembly riding the in-region links
        "bytes_ici_per_wave": int(
            Rg * base["bytes_ici_per_wave"] + intra_reassembly),
        "bytes_dcn_total_per_wave": int(
            Rg * base["bytes_dcn_total_per_wave"]),
        "bytes_wan_window_per_wave": int(wan_window),
        "bytes_wan_commit_per_wave": int(wan_commit),
        "bytes_wan_total_per_wave": int(wan_total),
        "flat_wan_window_per_wave": int(flat_wan_window),
        "flat_wan_total_per_wave": int(flat_wan_total),
        "wan_cut_vs_flat": (float(wan_total) / float(flat_wan_total)
                            if flat_wan_total else 0.0),
    }
    return out


class ShardedResidentSolver(ResidentSolver):
    """ResidentSolver whose node planes live SHARDED across a TPU mesh.

    Same surface as ResidentSolver (pack_batch / merge_asks /
    solve_stream / apply_delta / wave_traffic), but:

      * avail/reserved/valid/attr_rank/dev_cap and the carried
        used/dev_used live in each chip's HBM under a "nodes"-axis
        NamedSharding — packed and placed ONCE;
      * apply_delta scatters delta rows through the same donate-buffer
        kernels; GSPMD routes each row to its owning shard and the
        result is re-pinned to the node sharding (no full re-put);
      * solve_stream runs the wave loop under shard_map: full-N scoring
        and the PR 4 shortlist contention waves are shard-local, and
        only per-shard top-K candidate keys cross ICI (see
        solver/kernel.py `mesh_axis`) — placements and explainability
        counters stay bit-identical to the single-device host twin;
      * wave_traffic grows the ICI tier (`bytes_ici_per_wave`).

    Bool ask planes ship dense (not bitpacked): a uint32 lane packs 32
    node columns and cannot be split on the node axis.
    """

    _pack_bool_planes = False

    def __init__(self, nodes, probe_asks, *args,
                 mesh: Optional[Mesh] = None,
                 n_devices: Optional[int] = None, **kw):
        if mesh is None:
            # NOMAD_TPU_MESH_REGIONS > 1 defaults new solvers onto the
            # three-tier hierarchy, NOMAD_TPU_MESH_HOSTS > 1 onto the
            # two-tier one; unset keeps the flat PR-5 mesh
            regions = env_mesh_regions()
            hosts = env_mesh_hosts()
            if regions and regions > 1:
                mesh = make_three_tier_mesh(regions, hosts or 1,
                                            n_devices)
            elif hosts and hosts > 1:
                mesh = make_two_tier_mesh(hosts, n_devices)
            else:
                mesh = make_node_mesh(n_devices)
        self._set_mesh(mesh)
        super().__init__(nodes, probe_asks, *args, **kw)
        Np = self.template.avail.shape[0]
        if not self._elastic and Np % self.n_shards:
            raise ValueError(
                f"padded node axis {Np} does not divide over "
                f"{self.n_shards} shards")

    #: subclass flag: the elastic solver owns the node axis by tile
    #: remap instead of contiguous blocks
    _elastic = False

    def _set_mesh(self, mesh: Mesh) -> None:
        """Bind a mesh: resolves the node-axis split (flat or
        two-tier), rebuilds the stream kernel and the scatter-kernel
        cache.  The elastic reshard/recovery path re-binds meshes as
        shards leave and rejoin."""
        self._mesh = mesh
        axes, n_hosts = mesh_node_axes(mesh)
        self._axis = axes            # P element splitting the node dim
        self.n_hosts = n_hosts       # hosts PER REGION (three-tier)
        self.n_regions = mesh_region_count(mesh)
        self.n_shards = int(np.prod(
            [mesh.shape[a] for a in (axes if isinstance(axes, tuple)
                                     else (axes,))]))
        self.shards_per_region = self.n_shards // max(self.n_regions, 1)
        self.chips_per_host = self.shards_per_region // max(n_hosts, 1)
        self.two_tier = isinstance(axes, tuple)
        self.three_tier = self.two_tier and len(axes) == 3
        self._kern = _build_sharded_stream_kernel(mesh)
        self._scatter_kerns: Dict = {}

    # ---------------- sharded placement hooks ----------------
    def _put_node(self, name, arr):
        # leading node axis sharded, trailing axes replicated (covers
        # the 3-D ev_res eviction plane alongside the 1/2-D planes)
        spec = P(self._axis, *([None] * (np.ndim(arr) - 1)))
        # copy before placing — see ResidentSolver._put_node (host-side
        # in-place template updates must never alias device buffers)
        return jax.device_put(np.array(arr),
                              NamedSharding(self._mesh, spec))

    def _put_ask(self, name, arr):
        if name in _PLANE_ASK_ARGS:
            spec = P(*([None] * (np.ndim(arr) - 1)), self._axis)
        else:
            spec = P()
        return jax.device_put(arr, NamedSharding(self._mesh, spec))

    # ---------------- delta lifecycle ----------------
    # Incremental tensorize across the mesh: the inherited apply_delta
    # drives these hooks, which route each pow2-padded row bundle to
    # the shard OWNING its node slot under shard_map — every shard
    # scatters only its own rows (non-owned indices pin to the dropped
    # Np slot), so a delta wave moves only the scattered rows and the
    # arrays never leave their node-axis sharding.  (A plain jit
    # scatter on a sharded operand is NOT partition-safe: GSPMD may
    # replicate the update and apply it once per shard.)
    def _sharded_scatter(self, op: str, arr, idx, rows):
        """idx are DEVICE-LAYOUT rows (== global rows for the
        contiguous block layout; the elastic solver translates global
        rows through its tile tables before calling)."""
        key = (op, np.ndim(arr))
        fn = self._scatter_kerns.get(key)
        if fn is None:
            spec = P(self._axis, *([None] * (np.ndim(arr) - 1)))
            axes = self._axis
            cph = self.chips_per_host
            spr = self.shards_per_region

            def body(a_l, idx_, rows_, _op=op):
                Npl = a_l.shape[0]
                if isinstance(axes, tuple) and len(axes) == 3:
                    lin = (jax.lax.axis_index(axes[0]) * spr
                           + jax.lax.axis_index(axes[1]) * cph
                           + jax.lax.axis_index(axes[2]))
                elif isinstance(axes, tuple):
                    lin = (jax.lax.axis_index(axes[0]) * cph
                           + jax.lax.axis_index(axes[1]))
                else:
                    lin = jax.lax.axis_index(axes)
                off = lin * Npl
                loc = idx_.astype(jnp.int32) - off
                # negative locals WRAP before mode="drop" bounds-checks;
                # pin non-owned rows to the always-dropped Npl slot
                loc = jnp.where((loc >= 0) & (loc < Npl), loc, Npl)
                if _op == "set":
                    return a_l.at[loc].set(rows_, mode="drop")
                return a_l.at[loc].add(rows_, mode="drop")

            fn = jax.jit(jax.shard_map(body, mesh=self._mesh,
                                       in_specs=(spec, P(), P()),
                                       out_specs=spec,
                                       check_vma=False))
            self._scatter_kerns[key] = fn
        return fn(arr, idx, rows)

    def _delta_set(self, arr, idx, rows):
        return self._sharded_scatter("set", arr, idx, rows)

    def _delta_add(self, arr, idx, rows):
        return self._sharded_scatter("add", arr, idx, rows)

    # ---------------- solving ----------------
    def solve_stream_async(self, batches: Sequence[PackedBatch],
                           seeds: Optional[Sequence[int]] = None):
        self._check_stream_jobs(batches)
        self._check_batch_axis(batches)
        stacked = self._stack_args(batches)
        n_places = np.asarray([pb.n_place for pb in batches], np.int32)
        seed_arr = (np.zeros(len(batches), np.int32) if seeds is None
                    else np.asarray(list(seeds), np.int32))
        has_distinct = self._has_distinct(batches)
        preempt = self._preempt_on(has_distinct)
        node_gid, owner_map, slot_map, tile_np, mesh_nt = \
            self._elastic_operands()
        (self._used, self._dev_used, out, self.last_evict,
         self.last_waves, self.last_rescore_waves) = self._kern(
            self._dev_node["avail"], self._dev_node["reserved"],
            self._dev_node["valid"], self._dev_node["node_dc"],
            self._dev_node["attr_rank"], self._dev_node["dev_cap"],
            self._used, self._dev_used, stacked, n_places, seed_arr,
            self._dev_node.get("ev_res"), self._dev_node.get("ev_prio"),
            node_gid, owner_map, slot_map,
            has_spread=self._has_spread(batches),
            group_count_hint=self._group_count_hint(batches),
            max_waves=self.max_waves, wave_mode=self.wave_mode,
            has_distinct=has_distinct,
            has_devices=self._has_devices(batches),
            stack_commit=self.stack_commit, compact=self._compact,
            pallas_mode=self.pallas, shortlist_c=self.shortlist_c,
            has_preempt=preempt, mesh_nt=mesh_nt, tile_np=tile_np)
        return out

    def _elastic_operands(self):
        """(node_gid, owner_map, slot_map, tile_np, mesh_nt) — the
        contiguous block layout needs none of them (tile_np 0 keeps
        the kernel on the axis-offset arithmetic)."""
        return None, None, None, 0, 0

    # ---------------- byte model ----------------
    def measured_wave_counters(self) -> Optional[Dict]:
        """Mesh units: rescore_waves counts per-SHARD full passes (the
        kernel psums its per-shard escape counter), so the shortlist
        remainder is taken against waves x shards."""
        m = super().measured_wave_counters()
        if m is not None:
            m["shard_waves_total"] = m["waves_total"] * self.n_shards
            m["shortlist_waves"] = max(
                m["shard_waves_total"] - m["rescore_waves"], 0)
        return m

    def wave_traffic(self, batches: Sequence[PackedBatch]) -> Dict:
        """Three-tier model: the inherited two HBM tiers plus the ICI
        tier.  HBM tiers are restated PER SHARD (each chip walks only
        its Np/devices slice of every plane); `measured` gains
        `modeled_bytes_ici_total` (per-wave ICI model x measured wave
        counters).  `rescore_waves` counts per-SHARD full passes (a
        mixed wave where 3 of 8 shards escape costs 3 shard-plane
        walks, not 8)."""
        from ..solver import pallas_kernel as _pk
        from ..solver.kernel import (TOP_K as _TOP_K, WAVE_K,
                                     _MERGED_W_CAP, _WIDE_W_CAP,
                                     MERGED_GP_MAX, resolve_shortlist_c)
        out = super().wave_traffic(batches)
        t = self.template
        Np, R = t.avail.shape
        Npl = self._np_local()
        Gp = max(pb.ask_res.shape[0] for pb in batches)
        K = max(pb.p_ask.shape[0] for pb in batches)
        A = t.attr_rank.shape[1]
        S = t.sp_desired.shape[1]
        V = t.sp_desired.shape[2]
        has_spread = self._has_spread(batches)
        hint = self._group_count_hint(batches)
        w_cap = (_MERGED_W_CAP if Gp <= MERGED_GP_MAX else _WIDE_W_CAP)
        TK = min(max(WAVE_K, min(2 * hint, w_cap)) + _TOP_K, Np)
        TKl = min(TK, Npl)
        C = (0 if self._has_distinct(batches)
             else resolve_shortlist_c(Npl, TKl, self.shortlist_c))
        mode = self.pallas
        if mode == "auto":
            mode = _pk.resolve_mode(Npl, Gp, TKl, V, has_spread)
        want_tables = has_spread and V <= 8 and not self.stack_commit
        TKv = -(-TK // (V + 1)) if want_tables else 0
        TW = min(TKv, Npl) if want_tables else 0
        out["ici"] = model_ici_bytes(Gp, K, A, R, TKl, self.n_shards,
                                     want_tables, V, TW, has_spread)
        out["bytes_ici_per_wave"] = out["ici"]["bytes_ici_per_wave"]
        n_reg = getattr(self, "n_regions", 1)
        if self.two_tier or self._elastic:
            # ISSUE 8: the DCN tier next to ICI — and the flat
            # exchange's cross-host exposure it is measured against.
            # Per REGION on a three-tier mesh (the WAN block below
            # restates the fleet-wide totals).
            out["dcn"] = model_ici_dcn_bytes(
                Gp, K, A, R, TK, TKl, self.n_shards // max(n_reg, 1),
                self.n_hosts if self.two_tier else 1,
                want_tables, V, TKv, TW, has_spread)
            out["bytes_dcn_per_wave"] = \
                out["dcn"]["bytes_dcn_total_per_wave"]
        if getattr(self, "three_tier", False) and n_reg > 1:
            # ISSUE 13: the WAN tier — and the flat exchange's
            # cross-region exposure it is measured against
            out["wan"] = model_ici_dcn_wan_bytes(
                Gp, K, A, R, TK, TKl, self.n_shards, n_reg,
                self.n_hosts, want_tables, V, TKv, TW, has_spread)
            out["bytes_wan_per_wave"] = \
                out["wan"]["bytes_wan_total_per_wave"]
        b1, brw, passes = model_wave_bytes(
            Npl, Gp, K, S, R, has_spread, mode, TKl, C)
        out["per_shard"] = {"np_local": int(Npl),
                            "bytes_wave1": int(b1),
                            "bytes_rewave": int(brw),
                            "shortlist_c": int(C),
                            "fused_pass_count": passes}
        m = out.get("measured")
        if m is not None:
            # rescore_waves counts PER-SHARD full passes in mesh mode
            shortlist_shard_waves = (m["waves_total"] * self.n_shards
                                     - m["rescore_waves"])
            m["modeled_bytes_total"] = int(
                b1 * m["rescore_waves"]
                + brw * max(shortlist_shard_waves, 0))
            m["modeled_bytes_ici_total"] = int(
                out["ici"]["bytes_ici_total_per_wave"]
                * m["waves_total"])
            if "dcn" in out:
                m["modeled_bytes_dcn_total"] = int(
                    out["dcn"]["bytes_dcn_total_per_wave"]
                    * m["waves_total"])
                m["modeled_bytes_dcn_flat_total"] = int(
                    out["dcn"]["flat_dcn_total_per_wave"]
                    * m["waves_total"])
            if "wan" in out:
                m["modeled_bytes_wan_total"] = int(
                    out["wan"]["bytes_wan_total_per_wave"]
                    * m["waves_total"])
                m["modeled_bytes_wan_flat_total"] = int(
                    out["wan"]["flat_wan_total_per_wave"]
                    * m["waves_total"])
        return out

    def _np_local(self) -> int:
        """Per-shard node-axis width (the elastic layout carries
        capacity slack beyond Np // n_shards)."""
        return self.template.avail.shape[0] // self.n_shards


# ===================================================================
# Elastic mesh (ISSUE 8): tile-granular reshard + shard-loss recovery
# ===================================================================

#: dead-slot fill per node plane (matching the tensorizer's padding)
_LAYOUT_FILLS = {"valid": False, "attr_rank": -1, "ev_prio": -1}


class ElasticShardedResidentSolver(ShardedResidentSolver):
    """ShardedResidentSolver whose node axis is owned in SHARD-TILES
    routed by an owner remap table (tensorize.TileLayout) instead of
    contiguous axis-index blocks.

    What that buys (ISSUE 8):

      * ``grow_tiles`` extends the global node axis by whole tiles and
        ships ONLY the new tiles' plane rows (measured, not modeled) —
        no world repack, no re-put of resident state;
      * ``move_tile`` rebalances one tile between shards, carrying its
        delta-carried usage: the moved tile's rows are the only bytes
        that travel;
      * ``fail_shard`` / ``recover`` is the shard-loss state machine:
        on loss the surviving shards keep solving at DEGRADED width
        (the lost tiles' nodes drop out of the solve; every surviving
        solve stays on the device fast path), while the lost planes
        are rebuilt from the host-side template — the raft-backed
        store's view of the world — and ``recover`` rejoins them,
        restoring usage to the last plan-fed state.

    Placements and explainability counters stay bit-identical to the
    host twin through ANY reshard/fail/rejoin interleaving: candidate
    keys carry stable GLOBAL node ids and the kernel's extraction and
    merge order them by (score desc, global id asc) regardless of
    where a tile physically lives (solve_kernel `tile_np`).
    """

    _elastic = True
    _fresh_layout = True

    def __init__(self, nodes, probe_asks, *args,
                 mesh: Optional[Mesh] = None,
                 n_devices: Optional[int] = None,
                 tile_np: Optional[int] = None,
                 slack_tiles: Optional[int] = None, **kw):
        import os
        self._tile_np_req = tile_np
        self._slack_tiles = (
            slack_tiles if slack_tiles is not None
            else int(os.environ.get("NOMAD_TPU_RESHARD_SLACK", "1")))
        #: reshard/recovery observability (bench + acceptance tests)
        self.reshard_counters = {
            "tiles_grown": 0, "tiles_moved": 0, "tiles_shrunk": 0,
            "tiles_reclaimed": 0,
            "last_reshard_bytes": 0, "reshard_bytes_total": 0,
            "recoveries": 0, "last_recovery_bytes": 0,
            "last_recovery_s": 0.0, "degraded_solves": 0,
        }
        #: mesh event log (ISSUE 10): every grow/shrink/move/fail/
        #: recover transition lands here with its measured bytes and
        #: duration — the /v1/agent/events surface.  The process-global
        #: log by default so one HTTP endpoint sees every mesh.
        from ..utils.tracing import global_mesh_events
        _log = kw.pop("event_log", None)
        # explicit None test: an EMPTY MeshEventLog is falsy (__len__)
        self.event_log = global_mesh_events if _log is None else _log
        super().__init__(nodes, probe_asks, *args, mesh=mesh,
                         n_devices=n_devices, **kw)

    # ---------------- layout lifecycle ----------------
    def _put_node_side(self) -> None:
        from ..solver.tensorize import TileLayout, pick_tile_np
        if self._fresh_layout:
            NT = self.template.avail.shape[0]
            tile = self._tile_np_req or pick_tile_np(NT, self.n_shards)
            if tile <= 0 or NT % tile:
                raise ValueError(
                    f"tile_np={tile} does not divide the padded node "
                    f"axis {NT}")
            self._layout = TileLayout(NT // tile, self.n_shards, tile,
                                      slack_tiles=self._slack_tiles)
            self.mesh_state = "healthy"
            self._lost_tiles: List[int] = []
            self._orig_mesh = self._mesh
        self._src_cache = self._layout.dev_src()
        super()._put_node_side()
        self._refresh_tables()

    @property
    def tile_np(self) -> int:
        return self._layout.tile_np

    def _np_local(self) -> int:
        return self._layout.npl

    def _elastic_operands(self):
        # mesh_nt caps the kernel's candidate-window width (TK).  Use
        # the FROM-SCRATCH pad of the real universe, not the tile-
        # grown template axis: a grow adds dead slack tiles, and a
        # window cap that tracked them would diverge from the host
        # twin / a fresh pack at the same node set (the dead slots can
        # never hold candidates, so the narrower cap is exact).
        from ..solver.tensorize import _pad_nodes
        return (self._dev_gid, self._dev_owner, self._dev_slot,
                self._layout.tile_np,
                _pad_nodes(max(self.template.n_real, 1)))

    def _refresh_tables(self, gid_rows=None) -> int:
        """(Re)place the device-side layout tables.  gid_rows
        incremental: (dev_rows, gids) scatters only the touched rows
        of the [n_slots] gid vector.  Returns bytes shipped."""
        om, sm = self._layout.tables()
        self._dev_owner = jax.device_put(
            om, NamedSharding(self._mesh, P()))
        self._dev_slot = jax.device_put(
            sm, NamedSharding(self._mesh, P()))
        shipped = int(om.nbytes + sm.nbytes)
        if gid_rows is not None and getattr(self, "_dev_gid",
                                            None) is not None:
            rows, gids = gid_rows
            self._dev_gid = self._sharded_scatter(
                "set", self._dev_gid, np.asarray(rows, np.int32),
                np.asarray(gids, np.int32))
            shipped += int(np.asarray(rows).nbytes
                           + np.asarray(gids).nbytes)
        else:
            gid = self._layout.node_gid(self.template.avail.shape[0])
            self._dev_gid = jax.device_put(
                gid, NamedSharding(self._mesh, P(self._axis)))
            shipped += int(gid.nbytes)
        return shipped

    # ---------------- layout-aware placement hooks ----------------
    def _to_layout(self, name, arr, axis):
        src = self._src_cache
        take = np.clip(src, 0, np.asarray(arr).shape[axis] - 1)
        fill = _LAYOUT_FILLS.get(name, 0)
        if axis == 0:
            out = np.ascontiguousarray(np.asarray(arr)[take])
            out[src < 0] = fill
        else:
            out = np.ascontiguousarray(np.asarray(arr)[..., take])
            out[..., src < 0] = fill
        return out

    def _put_node(self, name, arr):
        lay = self._to_layout(
            "used0" if name in ("used", "dev_used") else name, arr, 0)
        spec = P(self._axis, *([None] * (np.ndim(lay) - 1)))
        return jax.device_put(lay, NamedSharding(self._mesh, spec))

    def _put_ask(self, name, arr):
        if name in _PLANE_ASK_ARGS:
            lay = self._to_layout(name, arr, -1)
            spec = P(*([None] * (np.ndim(lay) - 1)), self._axis)
            return jax.device_put(lay,
                                  NamedSharding(self._mesh, spec))
        return jax.device_put(arr, NamedSharding(self._mesh, P()))

    def plane_checksum(self) -> int:
        """Layout-inverting override: the elastic planes live in
        tile-routed device order, so fetch and route rows back to
        template (global) order before hashing — healthy meshes cover
        every global row with exactly one live tile, making the result
        directly comparable to template_checksum (ISSUE 14)."""
        from ..solver.tensorize import plane_crc
        t = self.template
        dn = self._dev_node
        src = self._src_cache
        live = src >= 0
        Np = t.avail.shape[0]

        def back(arr):
            a = np.asarray(arr)
            out = np.zeros((Np,) + a.shape[1:], a.dtype)
            out[src[live]] = a[live]
            return out

        meta = f"{t.n_real}:{','.join(t.node_ids)}".encode()
        return plane_crc(
            back(dn["avail"]), back(dn["reserved"]),
            back(dn["valid"]), back(dn["node_dc"]),
            back(dn["attr_rank"]), back(dn["dev_cap"]),
            ev_prio=(back(dn["ev_prio"]) if "ev_prio" in dn
                     else None),
            ev_res=(back(dn["ev_res"]) if "ev_res" in dn else None),
            meta=meta)

    # delta scatters arrive with GLOBAL rows; route through the tile
    # tables to device-layout rows (the base scatter kernel's space).
    # Rows landing in a RETIRED tile (shrunk away, then handed to a
    # joining node by the host-side slot allocator) re-own that tile on
    # demand; rows in a LOST tile (shard down) drop device-side — the
    # template keeps the truth and recover() replays it.
    def _reclaim_tiles(self, idx) -> None:
        lay = self._layout
        tiles = np.unique(np.asarray(idx, np.int64) // lay.tile_np)
        lost = set(self._lost_tiles)
        for t in tiles:
            t = int(t)
            if (0 <= t < lay.n_tiles and lay.owner[t] < 0
                    and t not in lost):
                lay.assign(t, lay.least_loaded())
                self._src_cache = lay.dev_src()
                shipped = self._ship_tile(t)
                self._fresh_tiles.add(t)
                self.reshard_counters["tiles_reclaimed"] += 1
                self.reshard_counters["reshard_bytes_total"] += shipped

    def apply_delta(self, delta) -> str:
        # tiles reclaimed while THIS delta applies ship template rows
        # that already include the delta's host-applied usage; the
        # usage-add scatter below must not re-add it (see _delta_add)
        self._fresh_tiles: set = set()
        return super().apply_delta(delta)

    def _delta_set(self, arr, idx, rows):
        # only `set` scatters can reclaim: their rows are genuinely
        # touched node slots (add-side pow2 padding zero-fills idx,
        # and row 0's tile must not be resurrected by a pad artifact)
        self._reclaim_tiles(idx)
        return super()._delta_set(
            arr, self._layout.g2d(idx, unowned="drop").astype(np.int32),
            rows)

    def _delta_add(self, arr, idx, rows):
        fresh = getattr(self, "_fresh_tiles", None)
        if fresh:
            t = np.asarray(idx, np.int64) // self._layout.tile_np
            hit = np.isin(t, list(fresh))
            if hit.any():
                rows = np.where(
                    hit.reshape((-1,) + (1,) * (rows.ndim - 1)),
                    0, rows)
        return super()._delta_add(
            arr, self._layout.g2d(idx, unowned="drop").astype(np.int32),
            rows)

    def usage(self):
        """Carried usage in GLOBAL row order (dead/unowned rows 0)."""
        src = self._src_cache
        real = src >= 0
        u_dev = np.asarray(self._used)
        du_dev = np.asarray(self._dev_used)
        u = np.zeros((self.template.avail.shape[0], u_dev.shape[1]),
                     u_dev.dtype)
        du = np.zeros((self.template.avail.shape[0], du_dev.shape[1]),
                      du_dev.dtype)
        u[src[real]] = u_dev[real]
        du[src[real]] = du_dev[real]
        return u, du

    def _health_live_mask(self):
        """Device-row liveness for the health kernel (ISSUE 15):
        retired / lost tile rows keep STALE plane values (including
        valid=True) because layout fills apply only at put time, so
        the kernel must mask on tile residency, not the valid plane.
        Cached per layout epoch — `_src_cache` is replaced (never
        mutated) on every grow/shrink/move/fail/recover."""
        src = self._src_cache
        cache = self.__dict__.get("_health_live_dev")
        if cache is None or cache[0] is not src:
            dev = jax.device_put(
                np.ascontiguousarray(src >= 0),
                NamedSharding(self._mesh, P(self._axis)))
            self.__dict__["_health_live_dev"] = cache = (src, dev)
        return cache[1]

    def health_row_mask(self) -> np.ndarray:
        """GLOBAL-order row mask of device-resident rows — the host
        twin's view of what `_health_live_mask` keeps (lost tiles drop
        out of both)."""
        src = self._src_cache
        mask = np.zeros(self.template.avail.shape[0], bool)
        mask[src[src >= 0]] = True
        return mask

    def solve_stream_async(self, batches, seeds=None):
        if self.mesh_state == "degraded":
            self.reshard_counters["degraded_solves"] += 1
        return super().solve_stream_async(batches, seeds)

    def repack(self, delta=None) -> None:
        """A full repack rebuilds the whole world from the raft-fed
        template — on a degraded mesh that SUBSUMES recovery, so
        rejoin first: the lost tiles' planes and usage restore from
        the template before the repack re-reads device usage (going
        straight to repack would fold the lost tiles' zeroed device
        rows into the rebuilt used0, losing their plan-fed state)."""
        if getattr(self, "mesh_state", "healthy") == "degraded":
            self.recover()
        super().repack(delta)

    # ---------------- tile-granular reshard ----------------
    def _bump_layout_epoch(self) -> None:
        self._node_epoch += 1
        self._ev_epoch += 1
        self._row_cache.clear()
        self._drv_cache.clear()
        self._eval_cache.clear()
        self._const_cache.clear()

    def _ship_tile(self, t: int, usage=None) -> int:
        """Scatter one tile's plane rows (from the host template — the
        raft-fed source of truth) into its device location.  Returns
        the bytes shipped — THE grow/move measurement."""
        tile = self._layout.tile_np
        tmpl = self.template
        g_lo = t * tile
        rows = np.arange(g_lo, g_lo + tile)
        dev = self._layout.dev_rows(t).astype(np.int32)
        shipped = 0
        dn = self._dev_node
        plane_srcs = {
            "avail": tmpl.avail, "reserved": tmpl.reserved,
            "valid": tmpl.valid, "node_dc": tmpl.node_dc,
            "attr_rank": tmpl.attr_rank, "dev_cap": tmpl.dev_cap}
        if "ev_prio" in dn:
            plane_srcs["ev_prio"] = tmpl.ev_prio
            plane_srcs["ev_res"] = tmpl.ev_res
        for name, srca in plane_srcs.items():
            payload = np.ascontiguousarray(srca[rows])
            dn[name] = self._sharded_scatter("set", dn[name], dev,
                                             payload)
            shipped += payload.nbytes
        if usage is None:
            u_rows = np.ascontiguousarray(tmpl.used0[rows])
            du_rows = np.ascontiguousarray(tmpl.dev_used0[rows])
        else:
            u_rows, du_rows = usage
        self._used = self._sharded_scatter("set", self._used, dev,
                                           u_rows)
        self._dev_used = self._sharded_scatter("set", self._dev_used,
                                               dev, du_rows)
        shipped += int(u_rows.nbytes + du_rows.nbytes)
        shipped += self._refresh_tables(
            gid_rows=(dev, rows.astype(np.int32)))
        return shipped

    def grow_tiles(self, n: int = 1, shard: Optional[int] = None
                   ) -> List[int]:
        """Grow the global node axis by n whole shard-tiles: extends
        the host template with dead rows, assigns the tiles to the
        least-loaded shards (or `shard`), and ships ONLY those tiles'
        rows.  Joining nodes then fill the new slots through the
        normal delta path.  Raises if the per-shard capacity slack is
        exhausted — grow the slack (NOMAD_TPU_RESHARD_SLACK) or take
        a full repack."""
        import time as _t
        from ..solver.tensorize import extend_template_rows
        _t0 = _t.perf_counter()
        tile = self._layout.tile_np
        new = self._layout.grow(n)
        try:
            for t in new:
                self._layout.assign(
                    t, shard if shard is not None
                    else self._layout.least_loaded())
        except ValueError:
            raise ValueError(
                "no free tile slots left on any shard; increase "
                "slack_tiles/NOMAD_TPU_RESHARD_SLACK or repack")
        extend_template_rows(self.template, n * tile)
        NT = self.template.avail.shape[0]
        self._src_cache = self._layout.dev_src()
        self._compact = NT < 32768
        self._default_host_ok = np.zeros((self.gp, NT), bool)
        self._default_host_ok[:, :self.template.n_real] = True
        shipped = 0
        for t in new:
            shipped += self._ship_tile(t)
        self._bump_layout_epoch()
        self.reshard_counters["tiles_grown"] += n
        self.reshard_counters["last_reshard_bytes"] = shipped
        self.reshard_counters["reshard_bytes_total"] += shipped
        self.event_log.record(
            "grow", tiles=[int(t) for t in new], n_tiles=n,
            tile_np=tile, bytes=shipped,
            duration_s=round(_t.perf_counter() - _t0, 6),
            n_shards=self.n_shards)
        return new

    def move_tile(self, t: int, dst: int) -> int:
        """Rebalance one tile to shard `dst`, carrying its live usage.
        Only the tile's rows (planes + usage + gid marks) travel.
        Returns the measured bytes."""
        import time as _t
        _t0 = _t.perf_counter()
        lay = self._layout
        if lay.owner[t] < 0:
            raise ValueError(f"tile {t} is not owned")
        if lay.owner[t] == dst:
            return 0
        src_shard = int(lay.owner[t])
        tile = lay.tile_np
        old_rows = lay.dev_rows(t).astype(np.int32)
        # live usage rides along (small device gather)
        u_rows = np.ascontiguousarray(np.asarray(self._used)[old_rows])
        du_rows = np.ascontiguousarray(
            np.asarray(self._dev_used)[old_rows])
        # kill the old location: dead gids + valid False + zero usage
        NT = self.template.avail.shape[0]
        dead = (NT + old_rows).astype(np.int32)
        dn = self._dev_node
        dn["valid"] = self._sharded_scatter(
            "set", dn["valid"], old_rows, np.zeros(tile, bool))
        self._used = self._sharded_scatter(
            "set", self._used, old_rows, np.zeros_like(u_rows))
        self._dev_used = self._sharded_scatter(
            "set", self._dev_used, old_rows, np.zeros_like(du_rows))
        self._refresh_tables(gid_rows=(old_rows, dead))
        lay.release(t)
        lay.assign(t, dst)
        self._src_cache = lay.dev_src()
        shipped = self._ship_tile(t, usage=(u_rows, du_rows))
        self._bump_layout_epoch()
        self.reshard_counters["tiles_moved"] += 1
        self.reshard_counters["last_reshard_bytes"] = shipped
        self.reshard_counters["reshard_bytes_total"] += shipped
        self.event_log.record(
            "move", tile=int(t), src_shard=src_shard, dst_shard=int(dst),
            bytes=shipped,
            duration_s=round(_t.perf_counter() - _t0, 6))
        return shipped

    def shrink_tiles(self, n: int = 1) -> List[int]:
        """Shrink Np by whole shard-tiles: retire up to n EMPTY owned
        tiles (every template row invalid — the nodes were drained
        through the normal delta path first).  The retired tiles'
        device rows die (dead gids, zero usage) and their tile slots
        free up; only those rows' dead marks travel, never the world.
        A joining node later handed a retired tile's rows re-owns the
        tile on demand (see _reclaim_tiles).  Returns the retired tile
        ids ([] if nothing is empty)."""
        lay = self._layout
        tile = lay.tile_np
        v = self.template.valid
        u_dev = np.asarray(self._used)
        du_dev = np.asarray(self._dev_used)
        out: List[int] = []
        for t in range(lay.n_tiles):
            if len(out) >= n:
                break
            if lay.owner[t] < 0:
                continue
            if v[t * tile:(t + 1) * tile].any():
                continue                       # live nodes: not empty
            dr = lay.dev_rows(t)
            if u_dev[dr].any() or du_dev[dr].any():
                # a tombstone keeps its carried usage row so a revived
                # node resumes exactly; retiring it would zero that
                continue
            dev = lay.dev_rows(t).astype(np.int32)
            NT = self.template.avail.shape[0]
            dead = (NT + dev).astype(np.int32)
            dn = self._dev_node
            dn["valid"] = self._sharded_scatter(
                "set", dn["valid"], dev, np.zeros(tile, bool))
            self._used = self._sharded_scatter(
                "set", self._used, dev,
                np.zeros((tile,) + np.asarray(self._used).shape[1:],
                         np.asarray(self._used).dtype))
            self._dev_used = self._sharded_scatter(
                "set", self._dev_used, dev,
                np.zeros((tile,)
                         + np.asarray(self._dev_used).shape[1:],
                         np.asarray(self._dev_used).dtype))
            self._refresh_tables(gid_rows=(dev, dead))
            lay.release(t)
            out.append(t)
        if out:
            self._src_cache = lay.dev_src()
            self._bump_layout_epoch()
            self.reshard_counters["tiles_shrunk"] += len(out)
            self.event_log.record("shrink",
                                  tiles=[int(t) for t in out],
                                  n_tiles=len(out))
        return out

    # ---------------- shard-loss recovery ----------------
    def _shard_devices(self):
        return list(np.asarray(self._mesh.devices).reshape(-1))

    def _rebind(self, mesh: Mesh, layout, u, du) -> None:
        """Re-place resident state under a new mesh/layout with the
        given GLOBAL usage (the fail/recover transitions; surviving
        tiles' planes re-marshal device-side — simulation fetches
        through the host, a real fleet would move them over ICI)."""
        self._layout = layout
        self._set_mesh(mesh)
        self._fresh_layout = False
        try:
            self._put_node_side()
        finally:
            self._fresh_layout = True
        self._used = self._put_node("used", u)
        self._dev_used = self._put_node("dev_used", du)
        self._bump_layout_epoch()

    def fail_shard(self, shard: int) -> List[int]:
        """Declare one shard (device) lost.  Its tiles become unowned
        — their nodes drop out of every solve — while the surviving
        shards re-bind to a flat mesh over the remaining devices and
        KEEP SOLVING with their carried usage (degraded width, still
        the device fast path).  Returns the lost tile ids."""
        if self.mesh_state != "healthy":
            raise ValueError(f"mesh is {self.mesh_state}; recover "
                             "before failing another shard")
        if not 0 <= shard < self.n_shards:
            raise ValueError(f"no shard {shard}")
        if self.n_shards < 2:
            raise ValueError("cannot lose the only shard")
        u, du = self.usage()
        lost = self._layout.tiles_of(shard)
        tile = self._layout.tile_np
        for t in lost:
            u[t * tile:(t + 1) * tile] = 0      # HBM state is GONE
            du[t * tile:(t + 1) * tile] = 0
        devices = self._shard_devices()
        self._failed_device = devices[shard]
        self._failed_shard = shard
        survivors = [d for i, d in enumerate(devices) if i != shard]
        remap = {}
        j = 0
        for i in range(self.n_shards):
            if i != shard:
                remap[i] = j
                j += 1
        self._recover_remap = {v: k for k, v in remap.items()}
        new_layout = self._layout.remap_shards(remap, len(survivors))
        self._lost_tiles = lost
        self._rebind(Mesh(np.array(survivors), (MESH_NODE_AXIS,)),
                     new_layout, u, du)
        self.mesh_state = "degraded"
        self.event_log.record(
            "fail", shard=int(shard),
            tiles=[int(t) for t in lost],
            surviving_shards=len(survivors))
        return lost

    def recover(self) -> int:
        """Rebuild the lost shard's planes from the host template (the
        raft-backed store's view) and rejoin it: lost tiles return to
        the restored shard with usage as of the last plan-fed state;
        surviving tiles keep their live carried usage untouched.
        Returns the measured recovery bytes (the lost tiles' rows)."""
        import time
        if self.mesh_state != "degraded":
            raise ValueError("mesh is not degraded")
        t0 = time.perf_counter()
        u, du = self.usage()                    # survivors' live state
        tmpl = self.template
        tile = self._layout.tile_np
        recovered_bytes = 0
        for t in self._lost_tiles:
            rows = slice(t * tile, (t + 1) * tile)
            u[rows] = tmpl.used0[rows]
            du[rows] = tmpl.dev_used0[rows]
            recovered_bytes += int(
                tmpl.avail[rows].nbytes + tmpl.reserved[rows].nbytes
                + tmpl.valid[rows].nbytes + tmpl.node_dc[rows].nbytes
                + tmpl.attr_rank[rows].nbytes
                + tmpl.dev_cap[rows].nbytes + tmpl.used0[rows].nbytes
                + tmpl.dev_used0[rows].nbytes)
        mesh = self._orig_mesh
        axes, n_hosts = mesh_node_axes(mesh)
        S = int(np.prod([mesh.shape[a] for a in
                         (axes if isinstance(axes, tuple)
                          else (axes,))]))
        layout = self._layout.remap_shards(self._recover_remap, S)
        for t in self._lost_tiles:
            layout.assign(t, self._failed_shard)
        self._lost_tiles = []
        self._rebind(mesh, layout, u, du)
        self.mesh_state = "healthy"
        self.reshard_counters["recoveries"] += 1
        self.reshard_counters["last_recovery_bytes"] = recovered_bytes
        self.reshard_counters["last_recovery_s"] = (
            time.perf_counter() - t0)
        self.event_log.record(
            "recover", shard=int(self._failed_shard),
            bytes=recovered_bytes,
            duration_s=round(self.reshard_counters["last_recovery_s"],
                             6),
            n_shards=self.n_shards)
        return recovered_bytes


class ElasticMeshSupervisor:
    """The recovery trigger: maps fleet membership / node events onto
    the elastic solver's fail/recover state machine.

    Two event planes feed it (ISSUE 8):

      * serf-plane — plug ``on_fail`` / ``on_join`` straight into
        ``membership.gossip.GossipAgent(on_fail=..., on_join=...)``;
        a registered mesh host transitioning to dead fails its shard
        (survivors keep solving at degraded width), and its rejoin
        triggers the rebuild-and-rejoin recovery;
      * scheduler-plane — ``note_node_event`` from the worker's
        node-update eval path (EVAL_TRIGGER_NODE_UPDATE), for fleets
        whose mesh hosts are registered workload nodes rather than
        gossip members.

    Callbacks fire on gossip/worker threads while the solver is
    driven elsewhere, so transitions serialize under one lock; the
    solver's own solve calls are NOT held by it — fail/recover
    re-bind between solves, exactly like the direct API."""

    def __init__(self, solver: "ElasticShardedResidentSolver"):
        import threading
        self.solver = solver
        self._hosts: Dict[str, int] = {}
        self._lock = threading.Lock()
        self.events: List[Tuple[str, str]] = []

    def register_host(self, member_id: str, shard: int) -> None:
        """Declare that `member_id` (a gossip member or node id) hosts
        mesh shard `shard`."""
        with self._lock:
            self._hosts[member_id] = int(shard)

    def _member_id(self, member) -> str:
        return getattr(member, "id", member)

    def on_fail(self, member) -> None:
        mid = self._member_id(member)
        with self._lock:
            shard = self._hosts.get(mid)
            if shard is None or self.solver.mesh_state != "healthy":
                return
            self.solver.fail_shard(shard)
            self.events.append(("fail", mid))
            self.solver.event_log.record("supervisor.fail",
                                         member=mid, shard=int(shard))

    def on_join(self, member) -> None:
        mid = self._member_id(member)
        with self._lock:
            if mid not in self._hosts \
                    or self.solver.mesh_state != "degraded":
                return
            self.solver.recover()
            self.events.append(("recover", mid))
            self.solver.event_log.record("supervisor.recover",
                                         member=mid)

    def note_node_event(self, node_id: str, status: str) -> None:
        """Scheduler-plane trigger: a node-update eval observed
        `node_id` at `status` (structs NODE_STATUS_*)."""
        from ..structs.consts import NODE_STATUS_DOWN
        if status == NODE_STATUS_DOWN:
            self.on_fail(node_id)
        else:
            self.on_join(node_id)
