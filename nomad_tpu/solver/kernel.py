"""The jitted placement solve.

Replaces the reference's per-placement iterator chain
(scheduler/stack.go:107 Select -> feasible.go checks -> rank.go scoring ->
select.go limit/max) with dense tensor math over the full node axis:

  static feasibility mask  [G, N]   (constraints, dc, host-evaluated ops)
  wave loop: batched [G, N] scoring -> per-group top-k -> parallel commit

Wave semantics (the TPU recast of in-plan visibility,
scheduler/context.go:120 ProposedAllocs): instead of committing one
placement per step, every wave

  1. scores all (group, node) pairs against current usage in one batched
     pass — the MXU-friendly shape,
  2. ranks each group's remaining placements and assigns the r-th one to
     the group's r-th best node (top-k), so same-group placements fan out
     across nodes exactly as the reference's job anti-affinity pressure
     (rank.go:462) makes them do one step at a time,
  3. commits every assignment that survives cross-group conflict checks:
     cumulative capacity on shared nodes (segment-sum by node),
     first-per-(node, distinct-group) for distinct_hosts, a spread
     quota per (group, value) so targeted/even spread cannot be
     overfilled inside a single wave (spread.go semantics), and for a
     distinct_property limit no more commits per (slot, value) than
     the value has left (propertyset.go, made hard),
  4. placements that lose a conflict simply retry next wave against
     refreshed usage.

Every committed placement's capacity is checked against the usage its
wave started from plus all earlier same-wave commits on the node, so no
node ever oversubscribes.  A batch of K placements converges in
O(K / WAVE_K) waves instead of K serial scan steps; each wave is one
fused XLA program over [G, N] tensors.

Scores follow the reference's conditional-append-then-average
normalization (rank.go:667).  Where the reference subsamples nodes
(limit = max(2, log2 N), scheduler/stack.go:80-87), this solve scores
every node — strictly better placements at far higher eval throughput.
"""
from __future__ import annotations

import functools
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from . import score_spec as _score_spec
from .tensorize import (OP_EQ, OP_GE, OP_GT, OP_IS_SET, OP_LE, OP_LT, OP_NE,
                        OP_NONE, OP_NOT_SET, R_CPU, R_MEM)

TOP_K = 4
WAVE_K = 32       # min per-group wave width; scales up with batch size
MAX_WAVES = 12    # static wave budget per solve (see scan note below)
NEG_INF = _score_spec.NEG_INF
# victim eligibility gate: ask priority must exceed the victim's by at
# least this (scheduler/preemption.PRIORITY_DELTA — duplicated here so
# the device module stays import-light; pinned equal by a test)
EV_PRIORITY_DELTA = 10
# test hook: force the sort-based conflict path at small K (read at
# trace time; tests clear jit caches after flipping it)
_FORCE_SORT_CONFLICTS = False
# node count from which top-k extraction switches to approx_max_k
_APPROX_MIN_NP = 4096
# value-vocabulary size up to which spread lookups unroll as select-sums
# (gather-free); larger vocabularies fall back to take_along_axis
_SELECT_SUM_MAX_V = 16
# backend shim handing the spec-driven wave scorer its jnp ops (see
# score_spec: this kernel is a DRIVEN backend — no scoring arithmetic
# of its own)
_JAX_OPS = _score_spec.JaxOps(select_sum_max_v=_SELECT_SUM_MAX_V)
# group-count at or below which a batch is treated as "merged few-group"
# (throughput-mode ask dedup): the wave-width cap widens since top-k
# over so few rows is cheap. Shared by resident._group_count_hint and
# merged-mode callers sizing gp.
MERGED_GP_MAX = 16
# per-group candidate-window caps (wave width W <= cap): merged
# few-group batches carry thousands of placements per group, and a
# wider window is more same-wave commit capacity — i.e. fewer waves —
# at near-zero extraction cost with so few rows (read at trace time)
_MERGED_W_CAP = 1024
_WIDE_W_CAP = 256


def window_tk(Gp: int, K: int, NT: int, group_count_hint: int = 0
              ) -> int:
    """Per-group candidate-window width TK of a solve (static).

    A group may commit up to W = TK - TOP_K placements per wave, so a
    K-placement batch converges in O(K / W) waves.  W is sized to ~2x
    the LARGEST per-group placement count when the caller supplies it
    (group_count_hint, computed host-side at pack time): per-group
    candidate demand is what W serves, and oversizing it multiplies
    every wave's top-k / interleave / candidate costs for no extra
    commits.  Without a hint (direct callers, the served one-shot
    path), the conservative K-based bound keeps skewed batches
    converging.  Merged few-group batches carry far more placements
    per group and top-k over so few rows is cheap, so their cap is
    wider.  One definition: the kernel, the byte model
    (resident.wave_traffic) and the solve trace (solve_trace_attrs)
    must agree on it, because it decides the pallas mode."""
    per_group = group_count_hint if group_count_hint > 0 else K // 8
    w_cap = _MERGED_W_CAP if Gp <= MERGED_GP_MAX else _WIDE_W_CAP
    return min(max(WAVE_K, min(2 * per_group, w_cap)) + TOP_K, NT)


def exact_dot(a, b):
    """f32 matmul whose products and sums are f32-exact on every
    backend.  A TPU dot at default precision rounds its f32 inputs to
    bf16 (8 significant bits): a 0/1 mask times resource asks like
    550 MHz or 4321 MB would mis-sum prior usage on a shared node and
    over-commit it.  HIGHEST keeps the full f32 mantissa, so integer
    operands below 2^24 — attribute ranks, MHz, MB — sum exactly, as
    they do on CPU.  Every f32 dot in the solver goes through here
    (tests/test_precision.py)."""
    return jnp.dot(a, b, precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


# ---------------------------------------------------------------- delta
# Scatter-apply kernels for the device-resident cluster state
# (resident.apply_delta): the HBM arrays update in place — the old
# buffer is DONATED where the backend supports it (TPU/GPU), so a delta
# wave moves only the scattered rows, never a full [Np, ...] copy.
# CPU ignores donation; building the jit without it avoids the
# "donated buffers unused" warning storm in host-only runs.
_DELTA_JITS: dict = {}
_DELTA_JITS_LOCK = threading.Lock()


def _delta_scatter(op: str):
    """Lazily-built jit (backend probing at import would pay backend
    init for every package import, including pure-host test runs)."""
    fn = _DELTA_JITS.get(op)
    if fn is None:
        with _DELTA_JITS_LOCK:     # double-checked cache fill
            fn = _DELTA_JITS.get(op)
            if fn is None:
                donate = jax.default_backend() != "cpu"
                if op == "set":
                    def f(arr, idx, rows):
                        return arr.at[idx].set(rows)
                else:
                    def f(arr, idx, rows):
                        return arr.at[idx].add(rows)
                fn = jax.jit(f, donate_argnums=(0,) if donate else ())
                _DELTA_JITS[op] = fn
    return fn


def delta_scatter_set(arr, idx, rows):
    return _delta_scatter("set")(arr, idx, rows)


def delta_scatter_add(arr, idx, rows):
    return _delta_scatter("add")(arr, idx, rows)


def _op_eval(vals: jnp.ndarray, op: jnp.ndarray, rank: jnp.ndarray
             ) -> jnp.ndarray:
    """Evaluate vectorizable constraint ops.

    vals: [N, C] node value ranks (-1 missing); op/rank: [C].
    Semantics mirror scheduler/feasible.go:671 checkConstraint — note `!=`
    passes when the attribute is missing.
    """
    found = vals >= 0
    eq = found & (vals == rank[None, :])
    res = jnp.ones_like(found)
    res = jnp.where(op[None, :] == OP_EQ, eq, res)
    res = jnp.where(op[None, :] == OP_NE, ~eq, res)
    res = jnp.where(op[None, :] == OP_LT, found & (vals < rank[None, :]), res)
    res = jnp.where(op[None, :] == OP_LE, found & (vals <= rank[None, :]), res)
    res = jnp.where(op[None, :] == OP_GT, found & (vals > rank[None, :]), res)
    res = jnp.where(op[None, :] == OP_GE, found & (vals >= rank[None, :]), res)
    res = jnp.where(op[None, :] == OP_IS_SET, found, res)
    res = jnp.where(op[None, :] == OP_NOT_SET, ~found, res)
    return res


class SolveResult(NamedTuple):
    choice: jnp.ndarray        # [K, TOP_K] node indices, best first
    choice_ok: jnp.ndarray     # [K, TOP_K] bool (feasible + fits)
    score: jnp.ndarray         # [K, TOP_K] final normalized scores
    n_feasible: jnp.ndarray    # [K] feasible node count at commit wave
    n_exhausted: jnp.ndarray   # [K] feasible but resource-exhausted
    dim_exhausted: jnp.ndarray  # [K, R] counts per exhausted dimension
    feas: jnp.ndarray          # [G, N] static feasibility mask
    cons_filtered: jnp.ndarray  # [G, C] nodes filtered per constraint slot
    used_final: jnp.ndarray    # [N, R] resource usage after all commits
    dev_used_final: jnp.ndarray  # [N, D] device usage after all commits
    n_waves: jnp.ndarray       # [] wave-loop iterations that did work
    unfinished: jnp.ndarray    # [K] active but undecided after MAX_WAVES
    #  (rare; absorbed by the blocked-eval retry path)
    n_rescore: jnp.ndarray = None  # [] waves that ran the full-N pass
    #  (shortlist-resident waves make up n_waves - n_rescore; None when
    #   a kernel predates / sidesteps the shortlist path)
    evict: jnp.ndarray = None  # [K, E] bool victim-slot mask for
    #  placements committed by the in-kernel preemption pass (ISSUE 7);
    #  slots index the node's ev planes. None when has_preempt is off.
    commit_wave: jnp.ndarray = None  # [K] i32 wave each placement
    #  committed on (-1 = failed/unfinished). Only populated with
    #  has_preempt: evictions make usage non-monotone, so the host
    #  fixup must replay commits in WAVE order — an ask-order replay
    #  can transiently exceed avail on a node whose eviction (by a
    #  later-p placement) the kernel sequenced earlier.


# ------------------------------------------------------- shortlist
# Contention waves (waves >= 2) only ever re-rank nodes that already
# scored on top: the carried per-group top-C shortlist lets them gather
# live usage for <= C nodes and re-rank in VMEM instead of re-reading
# every [Gp, Np] plane from HBM.  Exactness is trigger-guarded — see
# solve_kernel's wave loop.
_SHORTLIST_TILE = 128          # auto width rounds up to this


class _SLState(NamedTuple):
    """Wave-loop carry for the shortlist-resident contention path.

    Per-entry planes are [Gp, C] gathered once per full-N wave; `vn` /
    `de` are the hoisted spread lookups restricted to shortlist nodes.
    `cut_s`/`cut_i` hold the era cutoff key (the C-th best (score,
    node) at the building wave): every non-shortlisted node's key was
    strictly worse and — under the validity triggers — stays frozen,
    so a re-ranked window whose TK-th key still dominates the cutoff
    provably equals the full-N window.  `comp` marks groups whose
    entire placeable set fit inside C (outsiders are permanently
    NEG_INF: every trigger is bypassed).  `win_*`/`nfeas`/`nexh`/
    `ndim`/`gany` are the NEXT wave's pre-computed window and
    explainability counters; `ok` gates using them."""
    idx: jnp.ndarray           # [Gp, C] node ids, ascending
    feas: jnp.ndarray          # [Gp, C] static feasibility
    pen: jnp.ndarray           # [Gp, C] penalty flag
    aff: jnp.ndarray           # [Gp, C] affinity score
    vn: jnp.ndarray            # [S, Gp, C] spread value ranks
    de: jnp.ndarray            # [S, Gp, C] spread desired counts
    coll: jnp.ndarray          # [Gp, C] own-group collocation counts
    cut_s: jnp.ndarray         # [Gp] era cutoff score
    cut_i: jnp.ndarray         # [Gp] era cutoff node id
    comp: jnp.ndarray          # [Gp] shortlist holds ALL placeable
    nfeas: jnp.ndarray         # [Gp] n_feasible for the next wave
    nexh: jnp.ndarray          # [Gp] n_exhausted for the next wave
    ndim: jnp.ndarray          # [Gp, R] dim_exhausted for the next wave
    win_s: jnp.ndarray         # [Gp, TKl] next wave's window scores
    win_i: jnp.ndarray         # [Gp, TKl] next wave's window nodes
    #  (window/table node ids are GLOBAL — in mesh mode they feed the
    #   cross-shard candidate-key merge directly)
    tb_s: jnp.ndarray          # [Gp, V+1, TW] next wave's value tables
    tb_i: jnp.ndarray          # ([Gp, 1, 1] dummies when tables off)
    gany: jnp.ndarray          # [Gp] next wave's grp_any
    ok: jnp.ndarray            # [] next wave may skip the full pass


def resolve_shortlist_c(Np: int, TK: int, requested: int = 0) -> int:
    """Static shortlist width C for a solve (0 = path disabled).

    `requested` 0 auto-sizes: the candidate window TK rounded UP to the
    next _SHORTLIST_TILE multiple (so there is always slack above the
    window for entries that drain), clamped to the node axis.  -1
    disables the path.  Explicit values are validated — never silently
    clamped: they must cover TOP_K fall-through slots, lie within the
    node axis, satisfy lane alignment (multiple of 8), and be at least
    the candidate window TK (narrower could not even fill one wave's
    window).  NOMAD_TPU_SHORTLIST_C feeds this via ResidentSolver."""
    if requested == -1:
        return 0
    if requested in (0, None):
        return min(Np, (TK // _SHORTLIST_TILE + 1) * _SHORTLIST_TILE)
    if not isinstance(requested, int) or requested < TOP_K:
        raise ValueError(
            f"shortlist_c={requested!r} invalid: must be -1 (off), 0 "
            f"(auto) or an int >= TOP_K ({TOP_K})")
    if requested % 8:
        raise ValueError(
            f"shortlist_c={requested} invalid: must be a multiple of 8 "
            "(vector lane alignment)")
    if requested > Np:
        raise ValueError(
            f"shortlist_c={requested} exceeds the padded node axis "
            f"({Np}); pick <= Np — it will not be clamped silently")
    if requested < TK:
        raise ValueError(
            f"shortlist_c={requested} is narrower than the candidate "
            f"window TK={TK} for this problem shape; the shortlist "
            "could not fill a single wave's window. Pass a value >= TK "
            "or 0 for auto sizing")
    return requested


@functools.partial(jax.jit,
                   static_argnames=("has_spread", "group_count_hint",
                                    "max_waves", "wave_mode",
                                    "has_distinct", "has_devices",
                                    "stack_commit", "pallas_mode",
                                    "shortlist_c", "mesh_axis",
                                    "mesh_shards", "has_preempt",
                                    "mesh_hosts", "mesh_nt", "tile_np",
                                    "mesh_regions", "lane_axis"))
def solve_kernel(avail, reserved, used0, valid, node_dc, attr_rank,
                 ask_res, ask_desired, distinct, dc_ok, host_ok, coll0,
                 penalty,
                 c_op, c_col, c_rank, a_op, a_col, a_rank, a_weight, a_host,
                 sp_col, sp_weight, sp_targeted, sp_desired, sp_implicit,
                 sp_used0, dev_cap, dev_used0, dev_ask, p_ask, n_place,
                 seed=0, *, has_spread=True,
                 group_count_hint=0, max_waves=0,
                 wave_mode="scan", has_distinct=True,
                 has_devices=True, stack_commit=False,
                 pallas_mode="off", shortlist_c=0,
                 mesh_axis=None, mesh_shards=0,
                 has_preempt=False, ev_res=None, ev_prio=None,
                 ask_prio=None, mesh_hosts=0, mesh_nt=0, tile_np=0,
                 node_gid=None, owner_map=None, slot_map=None,
                 learned=None, mesh_regions=0,
                 region_bias=None, lane_axis=None,
                 pl_slot=None, pl_col=None, pl_left=None) -> SolveResult:
    # pl_slot / pl_col / pl_left: the batch's distinct_property slots
    # (tensorize "Property limits"); None leaves the program as it is
    # without them
    # has_distinct / has_devices: trace-time guarantees from the packer
    # that NO ask in this batch uses distinct_hosts / requests devices —
    # the per-wave conflict sort, blocking scatter, and device-fit
    # arithmetic those features need then drop out of the program
    # entirely (the common fresh-service-job case)
    max_waves = max_waves or MAX_WAVES
    Np = avail.shape[0]
    Gp = ask_res.shape[0]
    S = sp_col.shape[1]
    R = avail.shape[1]
    K = p_ask.shape[0]
    # ---------- mesh-resident sharding (ISSUE 5 / ISSUE 8) ----------
    # mesh_axis names the shard_map axis the NODE dimension is split
    # over: every [.., Np, ..] arg here is that shard's LOCAL plane.
    # Scoring, extraction, and the shortlist stay shard-local; only
    # per-group candidate KEYS (score, global node id) and K-sized
    # commit/counter vectors cross the interconnect — never a
    # [Gp, Np] plane.
    #
    # ISSUE 8 generalizes the flat "nodes" axis to a TWO-TIER
    # ("hosts", "chips") hierarchy: the candidate-key exchange first
    # all-gathers within a host over fast ICI and lex-merges the host's
    # shards into ONE host window, and only the merged host-winner keys
    # cross the (10-40x slower) DCN between hosts — chip-sliced so each
    # host window traverses DCN once, not once per chip.  Commit psums
    # tier the same way (ICI reduce, then host-level reduce).  Both
    # tiers merge in the exact (score desc, global id asc) lex order of
    # the single-device tournament, so placements stay bit-identical.
    # ISSUE 13 adds a THIRD tier: ("regions", "hosts", "chips").  Each
    # region runs the two-tier exchange above locally (the named-axis
    # collectives over host/chip axes stay within the fixed region
    # coordinate), and only the region-merged top-K window — sliced
    # across the region's shards — crosses the (WAN-modeled) region
    # axis per wave.  mesh_hosts then counts hosts PER REGION.  The
    # final lex merge of the union is tier-structure-independent and
    # the commit psums are integer, so placements and every counter
    # stay bit-identical to the flat and two-tier meshes.
    in_mesh = mesh_axis is not None
    two_tier = in_mesh and isinstance(mesh_axis, tuple)
    three_tier = two_tier and len(mesh_axis) == 3
    if in_mesh:
        assert mesh_shards >= 1, \
            "mesh_axis requires the static mesh_shards axis size"
        if three_tier:
            assert mesh_regions >= 1 \
                and mesh_shards % mesh_regions == 0, (
                    "three-tier mesh_axis needs (region_axis, "
                    "host_axis, chip_axis) and mesh_regions dividing "
                    f"mesh_shards; got {mesh_axis!r} "
                    f"regions={mesh_regions} shards={mesh_shards}")
            region_ax, host_ax, chip_ax = mesh_axis
            SPR = mesh_shards // mesh_regions
            assert mesh_hosts >= 1 and SPR % mesh_hosts == 0, (
                "mesh_hosts (hosts PER REGION) must divide the "
                f"per-region shard count; got hosts={mesh_hosts} "
                f"shards_per_region={SPR}")
            CPH = SPR // mesh_hosts
            my_lin = (lax.axis_index(region_ax).astype(jnp.int32)
                      * jnp.int32(SPR)
                      + lax.axis_index(host_ax).astype(jnp.int32)
                      * jnp.int32(CPH)
                      + lax.axis_index(chip_ax).astype(jnp.int32))
        elif two_tier:
            assert len(mesh_axis) == 2 and mesh_hosts >= 1 \
                and mesh_shards % mesh_hosts == 0, (
                    "two-tier mesh_axis needs (host_axis, chip_axis) "
                    "and mesh_hosts dividing mesh_shards; got "
                    f"{mesh_axis!r} hosts={mesh_hosts} "
                    f"shards={mesh_shards}")
            region_ax = None
            host_ax, chip_ax = mesh_axis
            SPR = mesh_shards
            CPH = mesh_shards // mesh_hosts
            my_lin = (lax.axis_index(host_ax).astype(jnp.int32)
                      * jnp.int32(CPH)
                      + lax.axis_index(chip_ax).astype(jnp.int32))
        else:
            region_ax = host_ax = chip_ax = None
            SPR = CPH = mesh_shards
            my_lin = lax.axis_index(mesh_axis).astype(jnp.int32)
    # elastic tile layout (ISSUE 8): tile_np > 0 means the node axis is
    # owned in TILES of tile_np slots routed by an owner remap table
    # instead of contiguous axis-index blocks — a reshard moves one
    # tile's planes, never the world.  node_gid maps this shard's local
    # slots to stable GLOBAL node ids; owner_map/slot_map (replicated,
    # with a trailing -1 sentinel row) invert a global id to its owning
    # shard and local tile position.
    elastic = in_mesh and tile_np > 0
    if elastic:
        assert node_gid is not None and owner_map is not None \
            and slot_map is not None, \
            "tile_np > 0 needs node_gid/owner_map/slot_map tables"
    # global node axis: the elastic layout carries per-shard slack
    # (dead slots), so the true global width is passed in via mesh_nt —
    # it must match the host twin's padded axis or the TK clamp (and
    # with it the candidate window) would diverge from the twin
    NT = ((mesh_nt or Np * mesh_shards) if in_mesh else Np)
    # shard offset (contiguous layout): NamedSharding splits the node
    # axis into contiguous axis-index-ordered blocks, so global id =
    # axis_index * Np + local
    off = (my_lin * jnp.int32(Np) if (in_mesh and not elastic)
           else None)
    if in_mesh:
        if elastic:
            g_of_local = node_gid.astype(jnp.int32)       # [Np]
            n_tiles_s = owner_map.shape[0] - 1            # sentinel row

            def _l2g(idx):
                return g_of_local[idx]

            def _g2l(gid):
                """global id -> (owned-here, scatter-safe local slot
                (non-owned pinned to the dropped Np slot), clipped
                gather-safe slot).  Dead-slot gids land on the
                sentinel owner row (-1) and are never owned."""
                t = jnp.clip(gid // jnp.int32(tile_np), 0, n_tiles_s)
                own = (owner_map[t] == my_lin) & (gid >= 0)
                loc_ = (slot_map[t] * jnp.int32(tile_np)
                        + gid % jnp.int32(tile_np))
                loc = jnp.where(own, loc_, Np)
                return own, loc, jnp.clip(loc, 0, Np - 1)
        else:
            g_of_local = off + jnp.arange(Np, dtype=jnp.int32)

            def _l2g(idx):
                return idx + off

            def _g2l(gid):
                loc_ = gid - off
                own = (loc_ >= 0) & (loc_ < Np)
                loc = jnp.where(own, loc_, Np)
                return own, loc, jnp.clip(loc, 0, Np - 1)

    def _sliced_psum(x, n_slices, my_slice, over_ax, inner_axes):
        """Reduce x over `over_ax` shipping only a 1/n_slices chunk
        per shard: x is replicated across the `inner_axes` group (whose
        linear index is `my_slice`), so the reduce-scatter degrades to
        a slice (dynamic_slice keeps it collective-free on the inner
        tiers); the reduced chunks reassemble by tiled all-gathers,
        innermost axis first (matching the slice index order)."""
        shp = x.shape
        n = 1
        for d in shp:
            n *= d
        np_ = -(-n // n_slices) * n_slices
        flat = jnp.ravel(x)
        if np_ != n:
            flat = jnp.pad(flat, (0, np_ - n))
        wl = np_ // n_slices
        sl = lax.dynamic_slice_in_dim(flat, my_slice * wl, wl, axis=0)
        sl = lax.psum(sl, over_ax)
        for ax in inner_axes:
            sl = lax.all_gather(sl, ax, axis=0, tiled=True)
        return sl[:n].reshape(shp)

    def _psum_mesh(x):
        """Tiered reduction: ICI (chips) first, then a CHIP-SLICED
        host tier — each chip ships only its 1/CPH slice of the
        host-reduced vector across DCN (reduce-scatter over ICI, host
        psum on the slice, reassembled over ICI), so a commit vector
        crosses DCN once per host, not once per chip — then (three
        tiers) a region tier sliced the same way across ALL of the
        region's shards, so one commit vector crosses the WAN per
        region, not once per host.  Integer operands everywhere, so
        the tiering is order-exact."""
        if not two_tier:
            return lax.psum(x, mesh_axis)
        x = lax.psum(x, chip_ax)
        if mesh_hosts > 1:
            if CPH == 1:
                x = lax.psum(x, host_ax)
            else:
                x = _sliced_psum(x, CPH, lax.axis_index(chip_ax),
                                 host_ax, (chip_ax,))
        if not three_tier or mesh_regions == 1:
            return x
        if SPR == 1:
            return lax.psum(x, region_ax)
        wli = (lax.axis_index(host_ax) * jnp.int32(CPH)
               + lax.axis_index(chip_ax))
        return _sliced_psum(x, SPR, wli, region_ax,
                            (chip_ax, host_ax))

    def _tier_merge(s, i, k, over_ax, n_peers, n_slices, my_slice,
                    inner_axes):
        """One hierarchy level of the candidate-key exchange: merge
        the n_peers windows along `over_ax` into the top-k of their
        union, each transfer SLICED 1/n_slices across the inner-tier
        group (linear index `my_slice`) so one window crosses the
        slow tier once, not once per inner shard.  Power-of-two peer
        counts run a recursive-doubling tournament (every peer ships
        log2(n) windows); other counts fall back to one sliced
        all-gather + single merge (order-free — the lex sort restores
        the tournament order)."""
        ax_last = s.ndim - 1
        pad_c = lambda w: -(-w // n_slices) * n_slices   # noqa: E731

        def _padw(s, i, w):
            d = w - s.shape[ax_last]
            if d <= 0:
                return s, i
            pads = [(0, 0)] * ax_last + [(0, d)]
            return (jnp.pad(s, pads, constant_values=NEG_INF),
                    jnp.pad(i, pads,
                            constant_values=jnp.int32(2 ** 30)))

        def _slice(x):
            wl = x.shape[ax_last] // n_slices
            return lax.dynamic_slice_in_dim(x, my_slice * wl, wl,
                                            axis=ax_last)

        def _reassemble(x):
            for ax in inner_axes:
                x = lax.all_gather(x, ax, axis=ax_last, tiled=True)
            return x

        kp = pad_c(min(k, NT))
        s, i = _padw(s, i, pad_c(s.shape[ax_last]))
        if n_peers & (n_peers - 1) == 0:
            # tournament: round r exchanges with the peer at distance
            # 2^r; widths grow toward kp so no candidate that could
            # reach the global top-k is ever truncated
            for r in range(n_peers.bit_length() - 1):
                d = 1 << r
                perm = [(x, x ^ d) for x in range(n_peers)]
                ps = lax.ppermute(_slice(s), over_ax, perm)
                pi = lax.ppermute(_slice(i), over_ax, perm)
                fs = _reassemble(ps)
                fi = _reassemble(pi)
                w = min(kp, 2 * s.shape[ax_last])
                s, i = _lex_topk(jnp.concatenate([s, fs], axis=ax_last),
                                 jnp.concatenate([i, fi], axis=ax_last),
                                 w)
                s, i = _padw(s, i, pad_c(w))
            return _lex_topk(s, i, k)
        gs_ = lax.all_gather(_slice(s), over_ax, axis=ax_last,
                             tiled=True)
        gi_ = lax.all_gather(_slice(i), over_ax, axis=ax_last,
                             tiled=True)
        return _lex_topk(_reassemble(gs_), _reassemble(gi_), k)

    def _merge_mesh(s, i, k):
        """Hierarchical candidate-key merge: returns the top-k of the
        union of every shard's (score, global id) keys in the exact
        (score desc, id asc) lex order, replicated on all shards.

        Flat mesh: one all-gather + merge (the PR-5 exchange).  Two
        tiers: all-gather + merge within the host over ICI; then a
        chip-SLICED exchange over DCN — each chip ships 1/CPH of its
        host's window to the partner host and the slices reassemble
        over ICI, so one host window crosses DCN once per transfer,
        not once per chip.  Three tiers (ISSUE 13) repeat the same
        move one level up: the region-merged window — sliced across
        ALL of the region's shards — crosses the WAN once per region
        per transfer, never once per host."""
        ax_last = s.ndim - 1
        if not two_tier:
            gs_ = lax.all_gather(s, mesh_axis, axis=ax_last, tiled=True)
            gi_ = lax.all_gather(i, mesh_axis, axis=ax_last, tiled=True)
            return _lex_topk(gs_, gi_, k)
        if CPH > 1:                      # ICI tier: merge the host
            gs_ = lax.all_gather(s, chip_ax, axis=ax_last, tiled=True)
            gi_ = lax.all_gather(i, chip_ax, axis=ax_last, tiled=True)
            s, i = _lex_topk(gs_, gi_, min(k, gs_.shape[ax_last]))
        if mesh_hosts > 1:               # DCN tier: merge the region
            s, i = _tier_merge(s, i, k, host_ax, mesh_hosts, CPH,
                               lax.axis_index(chip_ax), (chip_ax,))
        if not three_tier or mesh_regions == 1:
            return _lex_topk(s, i, k) if mesh_hosts == 1 else (s, i)
        # WAN tier: merge the fleet — slices span the region's full
        # (host, chip) shard grid, reassembled chips-then-hosts to
        # match the within-region linear index
        wli = (lax.axis_index(host_ax) * jnp.int32(CPH)
               + lax.axis_index(chip_ax))
        return _tier_merge(s, i, k, region_ax, mesh_regions, SPR,
                           wli, (chip_ax, host_ax))
    TK = window_tk(Gp, K, NT, group_count_hint)
    W = max(TK - TOP_K, 1)          # effective per-group wave width
    # local extraction width: each shard contributes its top-TKl keys
    # to the all-gather merge; TKl = TK off-mesh, so the single-device
    # trace is unchanged.  Correctness of the merge only needs every
    # shard to surface min(TK, Np_local) candidates (a shard can hold
    # at most that many of the global top-TK).
    TKl = min(TK, Np)
    # shortlist width C (0 = disabled): waves >= 2 re-rank the carried
    # top-C instead of re-reading the full node planes, whenever the
    # validity triggers prove the result identical to a full rescore.
    # distinct_hosts blocking mutates feasibility across groups through
    # nodes outside any shortlist — those batches always full-rescore.
    # In mesh mode the shortlist is SHARD-LOCAL (resolved against the
    # local plane): triggers prove each shard's window contribution
    # exact, and escapes rescore only that shard's plane.
    # the learned-head and region-affinity terms flow through the
    # spec-DRIVEN scorers only (host twin + this wave path); the
    # hand-written shortlist twin and pallas tiles don't implement
    # them, so both stay disabled while either plane is active (see
    # score_spec.TERMS backends tuples)
    has_property = pl_slot is not None
    C = (0 if (has_distinct or has_property or learned is not None
               or region_bias is not None)
         else resolve_shortlist_c(Np, TKl, shortlist_c))
    use_sl = C > 0
    NE = C if use_sl else TKl       # full-wave extraction width
    ks = jnp.arange(K)
    gs = jnp.arange(Gp)

    # ---------- in-kernel preemption planes (ISSUE 7) ----------
    # Extra wave passes score the top-E evictable allocs per node as
    # negative-capacity deltas: a group with NOTHING placeable selects,
    # per feasible node, the min-cost victim set (a float-order-exact
    # twin of scheduler/preemption.victim_distance), ranks nodes by the
    # post-eviction bin-pack score, and commits (place, evict) pairs
    # through the same conflict/commit machinery as normal placements.
    if has_property and in_mesh:
        raise ValueError(
            "property limits are counted on one device only; the mesh "
            "solve leaves them to the host fixup")
    if has_preempt:
        if has_distinct or has_property:
            raise ValueError(
                "has_preempt does not compose with distinct_hosts "
                "batches (cross-group blocking is invisible to the "
                "eviction pass); callers fall back to host preemption")
        assert ev_res is not None and ev_prio is not None \
            and ask_prio is not None, \
            "has_preempt needs ev_res/ev_prio/ask_prio planes"
        EV = ev_prio.shape[1]
        ev_prio_i = ev_prio.astype(jnp.int32)
        ev_res_f = ev_res.astype(jnp.float32)
        ask_prio_i = ask_prio.astype(jnp.int32)
        # wave-invariant slot eligibility: real slot, priority at least
        # EV_PRIORITY_DELTA below the ask's (preemptible_allocs gate)
        ev_slot_ok = ((ev_prio_i[None, :, :] >= 0)
                      & (ask_prio_i[:, None, None] - ev_prio_i[None, :, :]
                         >= EV_PRIORITY_DELTA))       # [Gp, Np, E]
    else:
        EV = 1

    # ---------- static feasibility [Gp, Np] ----------
    def per_ask_feas(g):
        vals = attr_rank[:, c_col[g]]                      # [Np, C]
        ok = _op_eval(vals, c_op[g], c_rank[g])            # [Np, C]
        base = valid & dc_ok[g][node_dc] & host_ok[g]      # [Np]
        # per-constraint filtered counts with sequential (first-fail) credit
        passed_prev = jnp.cumprod(
            jnp.concatenate([jnp.ones((Np, 1), bool), ok[:, :-1]], axis=1),
            axis=1).astype(bool)
        first_fail = base[:, None] & passed_prev & ~ok
        filtered = first_fail.sum(axis=0)                  # [C]
        return base & ok.all(axis=1), filtered

    # vmap, not lax.map: map would serialize Gp dispatch rounds; the
    # batched [Gp, Np, C] intermediates are small
    feas, cons_filtered = jax.vmap(per_ask_feas)(gs)
    if in_mesh:
        # [Gp, C] explainability sums reduce once per solve; `feas`
        # itself stays a shard-local plane (reassembled by the caller's
        # out_spec when fetched at all)
        cons_filtered = _psum_mesh(cons_filtered)

    # affinity matches are also placement-invariant: [Gp, Np]
    def per_ask_aff(g):
        vals = attr_rank[:, a_col[g]]                      # [Np, CA]
        match = _op_eval(vals, a_op[g], a_rank[g])
        return (match * a_weight[g][None, :]).sum(axis=1)  # [Np]

    aff_score = jax.vmap(per_ask_aff)(gs) + a_host
    pen_score, pen_counts = _score_spec.static_terms(_JAX_OPS, penalty)

    # ---------- distinct_property slots (wave-invariant part) ----------
    # each slot's value rank per node (a one-hot matmul, exact, as the
    # spread lookup below), the node-by-value one-hot the per-wave
    # "value has nothing left" test reads, which slots each group is
    # charged under, and the nodes that lack a slot's attribute
    if has_property:
        PS, VL = pl_left.shape
        J = pl_slot.shape[1]
        pl_sel = (pl_col[:, None] == jnp.arange(attr_rank.shape[1])[None, :]
                  ).astype(jnp.float32)                    # [PS, A]
        pl_vnode = exact_dot(pl_sel, attr_rank.T.astype(jnp.float32)
                             ).astype(jnp.int32)           # [PS, Np]
        pl_oh = (pl_vnode[:, None, :] == jnp.arange(VL)[None, :, None]
                 ).astype(jnp.float32)                     # [PS, VL, Np]
        pl_member = ((pl_slot[:, :, None] == jnp.arange(PS)[None, None, :])
                     & (pl_slot >= 0)[:, :, None]).any(axis=1
                                                       ).astype(jnp.float32)
        pl_missing = exact_dot(pl_member,
                               (pl_vnode < 0).astype(jnp.float32)) > 0.5

        def pl_charged(mask, nodes):
            """[PS, VL] commits of the placements in `mask` on `nodes`,
            charged to every slot of their group."""
            sl = pl_slot[p_ask]                            # [K, J]
            slc = jnp.maximum(sl, 0)
            v = pl_vnode[slc, nodes[:, None]]
            ok = mask[:, None] & (sl >= 0) & (v >= 0)
            return jnp.zeros((PS, VL), jnp.float32).at[
                slc, jnp.maximum(v, 0)].add(ok.astype(jnp.float32))

        def pl_blocked(left):
            """[Gp, Np]: nodes whose value has nothing left in one of the
            group's slots, or that lack a slot's attribute."""
            full_v = (left <= 0).astype(jnp.float32)       # [PS, VL]
            full_n = jax.vmap(exact_dot)(full_v, pl_oh)    # [PS, Np]
            return (exact_dot(pl_member, (full_n > 0.5).astype(
                jnp.float32)) > 0.5) | pl_missing

    # ---------- hoisted spread lookups (wave-invariant) ----------
    # The per-(group, node) spread value and desired-count are functions
    # of static batch tensors only; gathering them once per solve keeps
    # the wave loop gather-free (per-wave [Gp, Np] gathers dominated the
    # solve cost before this hoist).
    V = sp_desired.shape[2]
    A = attr_rank.shape[1]
    if has_spread:
        def spread_static(s):
            col = sp_col[:, s]                             # [Gp]
            has = col >= 0
            # column lookup as a one-hot matmul: a per-element gather of
            # [Gp, Np] lowers to a near-scalar loop on TPU (~10ns/elem —
            # it was 2/3 of the whole solve); the MXU does it in one pass.
            # attr ranks are small ints, exact in f32 (exact_dot),
            # matching the exact gathers in the quota/commit paths
            onehot = (col[:, None] == jnp.arange(A)[None, :]
                      ).astype(jnp.float32)                # [Gp, A]
            v = exact_dot(onehot, attr_rank.T.astype(jnp.float32)
                          ).astype(jnp.int32)              # [Gp, Np]
            v = jnp.where(has[:, None], v, -1)
            # desired-count lookup: select-sum over small vocabularies
            # (unrolled V ops); gather fallback for high-cardinality
            # attributes where a V-unrolled loop would blow up the trace
            if V <= _SELECT_SUM_MAX_V:
                desired = jnp.zeros(v.shape, jnp.float32)
                for val in range(V):
                    desired = desired + jnp.where(
                        v == val, sp_desired[:, s, val][:, None], 0.0)
            else:
                desired = jnp.take_along_axis(sp_desired[:, s],
                                              jnp.maximum(v, 0), axis=1)
            desired = jnp.where(v >= 0, desired, -1.0)
            desired = jnp.where(desired < 0, sp_implicit[:, s][:, None],
                                desired)
            return v, desired
        sp_vnode, sp_des = jax.vmap(spread_static)(jnp.arange(S))
    else:
        sp_vnode = sp_des = None

    # tie-break jitter: the reference visits nodes in per-worker shuffled
    # order (stack.go NewRandomIterator), so equal-scoring nodes resolve
    # differently per worker. seed=0 keeps exact deterministic scoring;
    # seed != 0 decorrelates both sibling batches (a stream's caller may
    # pass distinct seeds) and sibling GROUPS within a batch, fanning
    # same-shaped asks across equal-scoring nodes instead of colliding on
    # one argmax — fewer contention waves for identical placements.
    node_gids = jnp.arange(Np, dtype=jnp.uint32)
    if in_mesh:
        # jitter hashes the GLOBAL node id so seeded scoring is
        # invariant to how the node axis is split (or re-tiled) over
        # the mesh
        node_gids = g_of_local.astype(jnp.uint32)
    h = (node_gids[None, :] * jnp.uint32(2654435761)
         + (gs.astype(jnp.uint32)[:, None] * jnp.uint32(7919)
            + jnp.uint32(seed)) * jnp.uint32(40503))
    h = (h ^ (h >> 16)) * jnp.uint32(2246822519)
    # Seeded mode quantizes scores into coarse bins and jitters within
    # the bin: once cluster usage is heterogeneous, exact scores make
    # every group rank the same few nodes on top and waves stall on
    # conflicts; binning disperses groups across the whole top score
    # band. The reference's limit iterator picks the max of a random
    # max(2, log2 N) node sample (scheduler/stack.go:80-87) — selection
    # within a near-tied band is no further from its semantics than
    # exact argmax, and converges an order of magnitude faster.
    SCORE_BIN = _score_spec.SCORE_BIN
    jitter = jnp.where(jnp.int32(seed) == 0, 0.0,
                       (h & jnp.uint32(1023)).astype(jnp.float32)
                       * (SCORE_BIN / 1023.0))             # [Gp, Np]

    # ---------- pallas fused-wave path (static, trace-time pick) ----
    # "auto" resolves against the problem shape: "topk" fuses scoring
    # AND per-tile top-K extraction (the [G, N] wave never reaches
    # HBM), "score" fuses the scoring chain into one pass and leaves
    # wide-window extraction to approx_max_k/top_k, "off" keeps the
    # unfused jnp path (the host twin's reference shape).
    if learned is not None or region_bias is not None:
        pallas_mode = "off"
    if pallas_mode == "auto":
        from . import pallas_kernel as _pk
        pallas_mode = _pk.resolve_mode(Np, Gp, TK, V, has_spread)
    Vs_i = sp_desired.shape[2]
    want_tables = has_spread and Vs_i <= 8 and not stack_commit
    # per-value candidate-table widths: TKv is the GLOBAL interleave
    # window per value class; TW the shard-local extraction width (the
    # merge only needs each shard's top min(TKv, Np_local) per class).
    TKv = -(-TK // (Vs_i + 1)) if want_tables else 0
    TW = min(TKv, Np) if want_tables else 0
    if in_mesh and pallas_mode == "topk" and want_tables and TW < TKv:
        # the fused kernel derives its table width from TK, which on a
        # shard narrower than TKv would pad tables past the local
        # plane; the "score" pass is the same exact math unfused and
        # lets the jnp extraction use the shard-local width
        pallas_mode = "score"
    if elastic and pallas_mode == "topk":
        # the fused top-K tournament tie-breaks by LOCAL slot order,
        # which under a tile remap is not global-id order; the "score"
        # pass is the same exact math with extraction left to the
        # gid-ordered lex sort below
        pallas_mode = "score"
    use_pk = pallas_mode != "off"
    if use_pk:
        from . import pallas_kernel as _pk
        from .masks import pack_groups_i32
        # the layouts the fused pass reads (pallas_kernel.fused_wave):
        # group-bitpacked boolean planes and [R, Np] node planes, the
        # static ones built ONCE per solve, outside the wave loop
        pk_feas = pack_groups_i32(feas)
        pk_pen = pack_groups_i32(penalty)
        pk_sp_has = (sp_col >= 0) if has_spread else None
        pk_avail_t, pk_reserved_t = avail.T, reserved.T
        pk_dev_cap_t = dev_cap.T if has_devices else None

    def group_scores(used, dev_used, coll, sp_used, blocked):
        """Batched scoring of every (group, node) pair against current
        usage — one instance of the reference's rank pipeline, [Gp, Np].
        Spec-driven: assembles the plane context and defers every float
        op to score_spec.evaluate_wave (nomadlint SCORE6xx flags
        scoring arithmetic hand-added back here)."""
        ctx = dict(
            used=used, dev_used=dev_used, coll=coll, sp_used=sp_used,
            blocked=blocked, avail=avail, reserved=reserved,
            ask_res=ask_res, ask_desired=ask_desired, dev_cap=dev_cap,
            dev_ask=dev_ask, feas=feas, pen_score=pen_score,
            pen_counts=pen_counts, aff_score=aff_score,
            has_devices=has_devices, has_spread=has_spread,
            sp_col=sp_col, sp_weight=sp_weight, sp_targeted=sp_targeted,
            vnode=sp_vnode, des=sp_des, S=S, V=V, shape=(Gp, Np),
            seed=seed, jitter=jitter, learned=learned,
            region_bias=region_bias)
        return _score_spec.evaluate_wave(_JAX_OPS, ctx)

    # ---------- shortlist scoring twin ----------
    def _lex_topk(score, idx, k):
        """Descending (score, ascending node id) top-k — the exact
        tie order lax.top_k uses over the full node axis, and the
        order the cross-shard candidate-key merge sorts in."""
        neg, six = lax.sort((-score, idx), num_keys=2)
        return -neg[..., :k], six[..., :k]

    if use_sl:
        def _sl_eval(sl, used_x, dev_used_x, sp_used_x):
            """EXACT score/indicator recompute for the <= C shortlist
            entries from gathered live state.  Every float expression
            mirrors group_scores term for term (same op order), so the
            result is bitwise the full rescore restricted to these
            nodes.  Returns (score, placeable, exh_ind, dim_ind)."""
            idx = sl.idx
            u = used_x[idx]                            # [Gp, C, R]
            av = avail[idx]
            rsv = reserved[idx]
            after = u + ask_res[:, None, :]
            fit_dims = after <= av
            fit = fit_dims.all(axis=-1)
            if has_devices:
                dev_fit = (dev_used_x[idx] + dev_ask[:, None, :]
                           <= dev_cap[idx]).all(axis=-1)
            else:
                dev_fit = jnp.ones((Gp, C), bool)
            placeable = sl.feas & fit & dev_fit

            denom_cpu = av[:, :, R_CPU]
            denom_mem = av[:, :, R_MEM]
            util_cpu = after[:, :, R_CPU] + rsv[:, :, R_CPU]
            util_mem = after[:, :, R_MEM] + rsv[:, :, R_MEM]
            ok_denoms = (denom_cpu > 0) & (denom_mem > 0)
            free_cpu = 1.0 - util_cpu / jnp.maximum(denom_cpu, 1.0)
            free_mem = 1.0 - util_mem / jnp.maximum(denom_mem, 1.0)
            raw = 20.0 - (10.0 ** free_cpu + 10.0 ** free_mem)
            binpack = jnp.where(ok_denoms,
                                jnp.clip(raw, 0.0, 18.0) / 18.0, 0.0)

            anti = jnp.where(sl.coll > 0,
                             -(sl.coll + 1.0) / ask_desired[:, None],
                             0.0)
            anti_counts = sl.coll > 0

            if has_spread:
                spread_total = jnp.zeros((Gp, C), jnp.float32)
                for s in range(S):
                    col = sp_col[:, s]
                    has = col >= 0
                    v = sl.vn[s]
                    has_v = v >= 0
                    used_vec = sp_used_x[:, s]
                    cur = jnp.where(v >= 0, jnp.take_along_axis(
                        used_vec, jnp.maximum(v, 0), axis=1), 0.0)
                    desired = sl.de[s]
                    boost = ((desired - (cur + 1.0))
                             / jnp.maximum(desired, 1e-9)
                             ) * sp_weight[:, s][:, None]
                    targeted = jnp.where(~has_v, -1.0,
                                         jnp.where(desired <= 0, -1.0,
                                                   boost))
                    present = used_vec > 0
                    any_present = present.any(axis=1)[:, None]
                    minc = jnp.min(jnp.where(present, used_vec,
                                             jnp.inf), axis=1)[:, None]
                    maxc = jnp.max(jnp.where(present, used_vec,
                                             -jnp.inf), axis=1)[:, None]
                    delta_boost = (minc - cur) / jnp.maximum(minc, 1e-9)
                    even = jnp.where(cur != minc, delta_boost,
                                     jnp.where(minc == maxc, -1.0,
                                               (maxc - minc)
                                               / jnp.maximum(minc,
                                                             1e-9)))
                    even = jnp.where(~has_v, -1.0, even)
                    even = jnp.where(any_present, even, 0.0)
                    contrib = jnp.where(sp_targeted[:, s][:, None],
                                        targeted, even)
                    spread_total = spread_total + jnp.where(
                        has[:, None], contrib, 0.0)
                spread_counts = spread_total != 0.0
            else:
                spread_total = 0.0
                spread_counts = False

            aff_counts = sl.aff != 0.0
            pen_sc = jnp.where(sl.pen, -1.0, 0.0)
            n_scorers = (1.0 + anti_counts + sl.pen + aff_counts
                         + spread_counts)
            total = (binpack + anti + pen_sc + sl.aff
                     + spread_total) / n_scorers
            total = jnp.where(jnp.int32(seed) == 0, total,
                              jnp.floor(total / SCORE_BIN) * SCORE_BIN)
            gid = (_l2g(idx) if in_mesh else idx).astype(jnp.uint32)
            h2 = (gid * jnp.uint32(2654435761)
                  + (gs.astype(jnp.uint32)[:, None] * jnp.uint32(7919)
                     + jnp.uint32(seed)) * jnp.uint32(40503))
            h2 = (h2 ^ (h2 >> 16)) * jnp.uint32(2246822519)
            jit_sl = jnp.where(jnp.int32(seed) == 0, 0.0,
                               (h2 & jnp.uint32(1023)).astype(
                                   jnp.float32) * (SCORE_BIN / 1023.0))
            total = total + jit_sl
            score = jnp.where(placeable, total, NEG_INF)
            exh = sl.feas & ~(fit & dev_fit)
            dim_ind = sl.feas[:, :, None] & ~fit_dims
            return score, placeable, exh, dim_ind

        sl0 = _SLState(
            idx=jnp.zeros((Gp, C), jnp.int32),
            feas=jnp.zeros((Gp, C), bool),
            pen=jnp.zeros((Gp, C), bool),
            aff=jnp.zeros((Gp, C), jnp.float32),
            vn=jnp.zeros((S, Gp, C) if has_spread else (1, 1, 1),
                         jnp.int32),
            de=jnp.zeros((S, Gp, C) if has_spread else (1, 1, 1),
                         jnp.float32),
            coll=jnp.zeros((Gp, C), jnp.float32),
            cut_s=jnp.zeros(Gp, jnp.float32),
            cut_i=jnp.zeros(Gp, jnp.int32),
            comp=jnp.zeros(Gp, bool),
            nfeas=jnp.zeros(Gp, jnp.int32),
            nexh=jnp.zeros(Gp, jnp.int32),
            ndim=jnp.zeros((Gp, R), jnp.int32),
            win_s=jnp.full((Gp, TKl), NEG_INF, jnp.float32),
            win_i=jnp.zeros((Gp, TKl), jnp.int32),
            tb_s=jnp.full((Gp, Vs_i + 1, TW) if want_tables
                          else (Gp, 1, 1), NEG_INF, jnp.float32),
            tb_i=jnp.zeros((Gp, Vs_i + 1, TW) if want_tables
                           else (Gp, 1, 1), jnp.int32),
            gany=jnp.zeros(Gp, bool),
            ok=jnp.bool_(False))
    else:
        sl0 = None

    # ---------- wave loop ----------
    # The carry is kept COMPACT (per-placement vectors, no [Gp, Np]
    # matrices): collocation counts and distinct-hosts blocking are
    # rebuilt each wave from the committed outputs with one scatter
    # instead of being carried.  The shortlist-resident path
    # additionally carries the [Gp, C] shortlist state (_SLState) and a
    # wave splits statically into:
    #
    #   full wave  — scores all N (pallas fused or jnp), extracts the
    #                top-C shortlist along with the TK window;
    #   shortlist  — uses the window + counters pre-computed at the end
    #                of the previous wave from the carried shortlist
    #                (fresh gathers of live usage, bitwise the full
    #                rescore restricted to those nodes).
    #
    # Validity is decided at the END of each wave, when the post-commit
    # state already equals the next wave's input: the carried window is
    # used only if (a) the group's whole placeable set fits in C
    # (`comp` — outsiders are permanently NEG_INF since usage only
    # grows), or (b) every commit this wave landed inside the group's
    # shortlist (no outsider's bin-pack score moved), the group has no
    # spread (a spread-state change shifts ALL the group's node scores)
    # and the re-ranked window's TK-th key still dominates the era
    # cutoff (no frozen outsider can rank inside the window).  Any
    # other condition falls back to a full-N rescore wave — the escape
    # hatch that keeps placements bit-identical to the host twin.
    def body(st):
        (used, dev_used, sp_used, done,
         out_idx, out_ok, out_score, out_nfeas, out_nexh, out_dimexh,
         wave, n_resc, SL, EVT, out_evict, out_wave) = st
        active = ~done & (ks < n_place)
        g_idx = p_ask
        used_pre, dev_used_pre = used, dev_used
        if has_property:
            # what each (slot, value) has left, rebuilt from the
            # committed outputs as coll and blocked are
            committed_p = done & out_ok[:, 0]
            pl_left_w = pl_left - pl_charged(
                committed_p, jnp.where(committed_p, out_idx[:, 0], 0))

        Vs = Vs_i

        def full_wave(SL):
            """The full-N pass: rebuild coll/blocked from the committed
            outputs, score every (group, node) pair (pallas fused or
            jnp), extract the top-NE, window the first TK (+ spread
            interleave), reduce the explainability counters — and, when
            the shortlist path is on, rebuild the carried shortlist
            from the same extraction."""
            committed = done & out_ok[:, 0]
            # out_idx holds GLOBAL node ids; scatters into the local
            # plane drop rows owned by other shards (mode="drop";
            # negative locals are pinned to Np first — scatter WRAPS
            # python-style negatives before the drop check)
            chosen = jnp.where(committed, out_idx[:, 0], 0)
            if in_mesh:
                _, chosen_l, _ = _g2l(chosen)
            else:
                chosen_l = chosen
            coll = coll0.at[g_idx, chosen_l].add(
                committed.astype(jnp.float32), mode="drop")
            if has_distinct:
                dg_all = distinct[g_idx]
                hit = jnp.zeros((Gp, Np), jnp.int32).at[
                    jnp.maximum(dg_all, 0), chosen_l].add(
                    (committed & (dg_all >= 0)).astype(jnp.int32),
                    mode="drop") > 0
                blocked = hit[jnp.maximum(distinct, 0)] \
                    & (distinct >= 0)[:, None]
            else:
                blocked = jnp.zeros((Gp, Np), bool)
            if has_property:
                blocked = blocked | pl_blocked(pl_left_w)

            pk = None
            if use_pk:
                # fused pallas pass: scoring chain + counters (+ top-K
                # and per-value tables in "topk" mode) in ONE walk of
                # each node tile; no [Gp, Np, R] intermediate ever
                # reaches HBM
                if has_spread:
                    pres = sp_used > 0                 # [Gp, S, V]
                    anyp = pres.any(axis=2)
                    minc_w = jnp.min(jnp.where(pres, sp_used, jnp.inf),
                                     axis=2)
                    maxc_w = jnp.max(jnp.where(pres, sp_used, -jnp.inf),
                                     axis=2)
                    # masked rows (nothing present) are pinned finite:
                    # the kernel's contribution for them is masked to 0
                    # either way, and finite inputs keep the VPU out of
                    # inf/nan
                    spread_pack = (
                        sp_vnode, sp_des, sp_used,
                        sp_weight, sp_targeted, pk_sp_has,
                        jnp.where(anyp, minc_w, 0.0).astype(jnp.float32),
                        jnp.where(anyp, maxc_w, 0.0).astype(jnp.float32),
                        anyp)
                else:
                    spread_pack = None
                pk = _pk.fused_wave(
                    mode=pallas_mode, feas=pk_feas,
                    blocked=(pack_groups_i32(blocked)
                             if has_distinct or has_property else None),
                    aff=aff_score, pen=pk_pen, jitter=jitter, coll=coll,
                    used_t=used.T, avail_t=pk_avail_t,
                    reserved_t=pk_reserved_t,
                    ask_res=ask_res, ask_desired=ask_desired,
                    dev=((dev_used.T, pk_dev_cap_t, dev_ask)
                         if has_devices else None),
                    spread=spread_pack, seed=jnp.int32(seed), TK=TK,
                    n_extract=NE,
                    tables_v=(Vs_i if (want_tables
                                       and pallas_mode == "topk")
                              else 0))
                n_feas_g, n_exh_g = pk["n_feas"], pk["n_exh"]
                dim_exh_g, grp_any = pk["dim_exh"], pk["grp_any"]
                score = pk.get("score")          # None in "topk" mode
            else:
                score, placeable, feas_b, fit, fit_dims, dev_fit = \
                    group_scores(used, dev_used, coll, sp_used, blocked)
                grp_any = placeable.any(axis=1)            # [Gp]
                # metrics snapshot for placements finishing this wave
                n_feas_g = (feas_b & valid[None, :]).sum(axis=1)
                n_exh_g = (feas_b & valid[None, :]
                           & ~(fit & dev_fit)).sum(axis=1)
                dim_exh_g = (feas_b[:, :, None] & valid[None, :, None]
                             & ~fit_dims).sum(axis=1)      # [Gp, R]

            # full sort-based top_k dominates wave cost at scale; TPU's
            # approx_max_k (recall ~0.95 over near-tied scores) is the
            # hardware-native candidate search — the solve still scores
            # every node, only the top-W *extraction* is approximate, a
            # far smaller perturbation than the reference's 14-node
            # subsample. Small problems (tests, dryruns) keep the exact
            # path.
            if elastic:
                # under a tile remap local slot order is NOT global-id
                # order, so top_k's index tie-break would diverge from
                # the host twin; extract by the explicit (score desc,
                # GLOBAL id asc) lex key, carrying the local slot
                gid_pl = jnp.broadcast_to(g_of_local[None, :],
                                          (Gp, Np))
                slot_pl = jnp.broadcast_to(
                    jnp.arange(Np, dtype=jnp.int32)[None, :], (Gp, Np))
                neg, eg, ei = lax.sort((-score, gid_pl, slot_pl),
                                       num_keys=2)
                ext_s, ext_g, ext_i = -neg[:, :NE], eg[:, :NE], \
                    ei[:, :NE]
            elif use_pk and pallas_mode == "topk":
                ext_s, ext_i = pk["top_score"], pk["top_idx"]
            elif Np >= _APPROX_MIN_NP:
                ext_s, ext_i = lax.approx_max_k(score, NE)
            else:
                ext_s, ext_i = lax.top_k(score, NE)        # [Gp, NE]
            top_score, top_idx = ext_s[:, :TKl], ext_i[:, :TKl]
            if elastic:
                top_idx = ext_g[:, :TKl]       # window keys are GLOBAL

            # per-value candidate tables for the spread interleave
            # (applied to the window AFTER the cross-shard merge — see
            # _interleave in the wave body); extraction is shard-local
            # at width TW (= TKv off-mesh: unchanged single-device
            # trace).  One class per value PLUS a class for nodes
            # MISSING the spread attribute — the reference still places
            # on those with a -1 score penalty (spread.go), so they
            # must stay candidates or feasible nodes would livelock
            # unplaced.
            if want_tables:
                if use_pk and pallas_mode == "topk":
                    # per-value tables came out of the fused pass; the
                    # tile-partial merge is exact-equal to the full-row
                    # top_k below (tournament + node-order tie-break)
                    tab_s, tab_i = pk["tab_s"], pk["tab_i"]
                else:
                    vnode = sp_vnode[0]                    # [Gp, Np]
                    tabs_i, tabs_s = [], []
                    for v in range(Vs + 1):
                        vmask = (vnode == v) if v < Vs else (vnode < 0)
                        sv = jnp.where(vmask, score, NEG_INF)
                        if elastic:
                            # gid-ordered ties, ids leave GLOBAL
                            ts, ti = _lex_topk(sv, gid_pl, TW)
                        elif Np >= _APPROX_MIN_NP:
                            ts, ti = lax.approx_max_k(sv, TW)
                        else:
                            ts, ti = lax.top_k(sv, TW)
                        tabs_i.append(ti)
                        tabs_s.append(ts)
                    tab_i = jnp.stack(tabs_i, axis=1)      # [Gp, V+1, TW]
                    tab_s = jnp.stack(tabs_s, axis=1)
                if in_mesh and not elastic:
                    tab_i = tab_i + off
            else:
                tab_s = jnp.full((Gp, 1, 1), NEG_INF, jnp.float32)
                tab_i = jnp.zeros((Gp, 1, 1), jnp.int32)
            if in_mesh and not elastic:
                # window keys leave the shard with GLOBAL node ids
                top_idx = top_idx + off

            if use_sl:
                # rebuild the carried shortlist from this extraction
                # (stored node-ascending so commit positions resolve
                # with one searchsorted); the cutoff key freezes the
                # best possible outsider for the whole era
                perm = jnp.argsort(ext_i, axis=1)
                sl_i = jnp.take_along_axis(ext_i, perm, axis=1)
                if has_spread:
                    bidx = jnp.broadcast_to(sl_i, (S, Gp, C))
                    vn = jnp.take_along_axis(sp_vnode, bidx, axis=2)
                    de = jnp.take_along_axis(sp_des, bidx, axis=2)
                else:
                    vn, de = SL.vn, SL.de
                SL = _SLState(
                    idx=sl_i,
                    feas=jnp.take_along_axis(feas, sl_i, axis=1),
                    pen=jnp.take_along_axis(penalty, sl_i, axis=1),
                    aff=jnp.take_along_axis(aff_score, sl_i, axis=1),
                    vn=vn, de=de,
                    coll=jnp.take_along_axis(coll, sl_i, axis=1),
                    cut_s=ext_s[:, NE - 1],
                    # era cutoff tie-break key: GLOBAL id under the
                    # elastic remap (the extraction's lex order), local
                    # slot otherwise (identical — the block map is
                    # monotonic)
                    cut_i=(ext_g[:, NE - 1] if elastic
                           else ext_i[:, NE - 1]),
                    comp=(n_feas_g - n_exh_g) <= jnp.int32(C),
                    nfeas=n_feas_g, nexh=n_exh_g, ndim=dim_exh_g,
                    win_s=top_score, win_i=top_idx,
                    tb_s=tab_s, tb_i=tab_i,
                    gany=grp_any, ok=jnp.bool_(False))
            return (top_score, top_idx, tab_s, tab_i, n_feas_g,
                    n_exh_g, dim_exh_g, grp_any, SL, jnp.int32(1))

        if use_sl:
            def carried_wave(SL):
                # shortlist wave: the window and counters were
                # pre-computed at the end of the previous wave from the
                # carried shortlist — no [Gp, Np] plane is touched
                return (SL.win_s, SL.win_i, SL.tb_s, SL.tb_i, SL.nfeas,
                        SL.nexh, SL.ndim, SL.gany, SL, jnp.int32(0))

            if lane_axis is not None:
                # lane-uniform predicate (ISSUE 20): a psum over the
                # lane vmap axis is UNBATCHED, so this cond stays a
                # real branch under `jax.vmap(..., axis_name=lane_axis)`
                # — a per-lane (batched) predicate would lower to
                # select and run the full [Gp, Np] pass every wave for
                # every lane, the PR 4 "pure overhead" that forced
                # shortlists off on vmapped lanes.  Any lane losing its
                # carried window sends ALL lanes through the full pass:
                # conservative (extra rescores, counted in n_resc) and
                # always exact, since the full pass is the escape hatch.
                take_carried = lax.psum(
                    jnp.int32(~SL.ok), lane_axis) == jnp.int32(0)
            else:
                take_carried = SL.ok
            (top_score, top_idx, tab_s, tab_i, n_feas_g, n_exh_g,
             dim_exh_g, grp_any, SL, resc) = lax.cond(
                 take_carried, carried_wave, full_wave, SL)
        else:
            (top_score, top_idx, tab_s, tab_i, n_feas_g, n_exh_g,
             dim_exh_g, grp_any, SL, resc) = full_wave(SL)
        n_resc = n_resc + resc

        # ---- cross-shard candidate-key merge (mesh mode) ----
        # The ONLY per-wave ICI traffic: each shard's [Gp, TKl] window
        # keys (+ [Gp, V+1, TW] value-table keys when the spread
        # interleave is on) are all-gathered and exactly merged by the
        # same lex order the per-shard extraction used — equal to a
        # single device's top-TK over the whole node axis.  Counters
        # reduce with a [Gp]-sized psum; no [Gp, Np] plane ever leaves
        # a shard.  Either branch of the cond above is collective-free,
        # so shards may mix carried/full waves freely — each shard's
        # contribution is trigger-proven exact either way.
        if in_mesh:
            top_score, top_idx = _merge_mesh(top_score, top_idx, TK)
            if want_tables:
                tab_s, tab_i = _merge_mesh(tab_s, tab_i, TKv)
            n_feas_out = _psum_mesh(n_feas_g)
            n_exh_out = _psum_mesh(n_exh_g)
            dim_exh_out = _psum_mesh(dim_exh_g)
            grp_any = _psum_mesh(grp_any.astype(jnp.int32)) > 0
        else:
            n_feas_out, n_exh_out, dim_exh_out = (n_feas_g, n_exh_g,
                                                  dim_exh_g)

        # spread-aware candidate interleaving (slot 0): when node
        # classes correlate with the spread attribute (racks live in
        # one dc, zones in one region — the common cluster layout), a
        # group's global top-W concentrates in ONE value and the spread
        # quota strands all but a few commits per wave. Instead,
        # interleave the per-value tables (slot j -> value j mod V), so
        # a group's candidates arrive pre-balanced across values; holes
        # (exhausted values) compact to the tail to keep the rank-wrap
        # contiguous. Skipped for huge vocabularies where per-value
        # extraction would dominate.
        # (skipped in stack_commit mode: stacking aims every placement
        # at slot 0, and the reference picks the max TOTAL score — the
        # spread term is already inside the score; forcing slot 0 to
        # the spread-preferred value would override the argmax)
        if want_tables:
            has0 = sp_col[:, 0] >= 0                       # [Gp]
            # visit values in each group's preference order (best head
            # candidate first), so the first interleaved slot — where a
            # lone remaining placement always lands — is the value the
            # spread scoring actually favors this wave
            vord = jnp.argsort(-tab_s[:, :, 0], axis=1)    # [Gp, V+1]
            j = jnp.arange(TK)
            vj = vord[:, j % (Vs + 1)]                     # [Gp, TK]
            inter_i = tab_i[gs[:, None], vj, (j // (Vs + 1))[None, :]]
            inter_s = tab_s[gs[:, None], vj, (j // (Vs + 1))[None, :]]
            order = jnp.argsort((inter_s <= NEG_INF / 2)
                                .astype(jnp.int32), axis=1,
                                stable=True)
            inter_i = jnp.take_along_axis(inter_i, order, axis=1)
            inter_s = jnp.take_along_axis(inter_s, order, axis=1)
            top_idx = jnp.where(has0[:, None], inter_i, top_idx)
            top_score = jnp.where(has0[:, None], inter_s, top_score)

        # rank each active placement within its group, then assign the
        # r-th remaining placement the group's (r mod M)-th best node,
        # where M is the group's real candidate count this wave: ranks
        # beyond the candidate list WRAP onto it, so every active
        # placement gets a candidate every wave and per-node cumulative
        # fit commits as many as capacity allows — a count >> W group
        # converges in a couple of waves instead of count/W
        grp_onehot = ((g_idx[None, :] == gs[:, None])
                      & active[None, :]).astype(jnp.int32)  # [Gp, K]
        act_g = grp_onehot.sum(axis=1)                     # [Gp]
        rank = (jnp.cumsum(grp_onehot, axis=1)
                - grp_onehot)[g_idx, ks]                   # exclusive count
        n_cand = (top_score > NEG_INF / 2).sum(axis=1)     # [Gp] real slots
        M = jnp.clip(jnp.minimum(n_cand, W), 1, W)
        # seeded per-group offset into the candidate window: without it,
        # every group's placements sit on slots 0..act-1 and all groups
        # hammer the same few top-scoring (often score-tied) nodes, so
        # per-wave commits are capped by that narrow pool's capacity.
        # Offsetting disperses groups across the whole top-W window —
        # candidates stay within the best W of N nodes (vs the
        # reference's random max(2, log2 N) subsample). seed=0 keeps the
        # exact deterministic mapping.
        g_hash = ((gs.astype(jnp.uint32) * jnp.uint32(2654435761))
                  ^ (jnp.uint32(seed) * jnp.uint32(2246822519)))
        g_off = jnp.where(jnp.int32(seed) == 0, 0,
                          ((g_hash >> 8) % jnp.uint32(W)).astype(
                              jnp.int32))                  # [Gp]
        # rotate the candidate window each wave (seeded mode): a
        # placement bounced by a same-wave conflict probes a DIFFERENT
        # slot next wave instead of re-contending for the node it lost,
        # which otherwise stalls convergence once the cluster fills and
        # scores tie across groups
        # step of 1 is coprime with every window size M (a fixed larger
        # step would be a no-op for groups where M divides it)
        rot = jnp.where(jnp.int32(seed) == 0, 0, wave)
        if stack_commit:
            # serial-fidelity mode (quality/exact path): every active
            # placement of a group aims at the group's CURRENT best
            # node; the cumulative per-node fit below commits as many
            # as actually fit and the rest re-score next wave against
            # updated usage — the reference's per-placement best-fit
            # stacking (rank.go:149 BinPackIterator), wave-batched.
            # Fan-out mode spreads a group across its top-W nodes in
            # one wave (fast), but fragments capacity near the packing
            # limit; stacking trades waves for the reference's quality.
            cr = jnp.zeros_like(rank)
        else:
            cr = (rank + g_off[g_idx] + rot) % M[g_idx]
        cand = top_idx[g_idx, cr]                          # [K]
        cand_score = top_score[g_idx, cr]
        cand_ok = active & (cand_score > NEG_INF / 2)

        # a group with nothing placeable fails all its remaining placements
        fail_now = active & ~grp_any[g_idx]

        # -- same-wave conflict checks over shared nodes --
        # prior_rank(key)[p] = #earlier candidates with the same key;
        # prior_sum(key, v)[p] = sum of v over them. Small K uses [K, K]
        # masks (matmul on the MXU); large K uses sort-based segmented
        # prefix sums, O(K log K) — identical results.
        if K <= 2048 and not _FORCE_SORT_CONFLICTS:
            earlier = ks[None, :] < ks[:, None]            # [K, K]
            both_ok = cand_ok[None, :] & cand_ok[:, None]
            same_node = ((cand[None, :] == cand[:, None])
                         & both_ok & earlier)

            def prior_sum_node(vals):
                return exact_dot(same_node.astype(jnp.float32), vals)

            def prior_rank_any(key, m):
                # exclusive count of earlier members with equal key,
                # under an arbitrary membership mask (the preemption
                # pass ranks candidates whose cand_ok is False)
                same = ((key[None, :] == key[:, None])
                        & m[None, :] & m[:, None] & earlier)
                return same.sum(axis=1)

            def prior_rank(key, member):
                return prior_rank_any(key, member & cand_ok)
        else:
            def _seg(key, ok):
                """Sort (key, idx) over `ok` members; return per-element
                exclusive segment rank and a segmented exclusive-prefix
                summer."""
                keyc = jnp.where(ok, key, jnp.int32(0x7FFFFFF0))
                s_key, s_ix = lax.sort((keyc, ks), num_keys=2)
                pos = ks
                is_start = jnp.concatenate(
                    [jnp.ones(1, bool), s_key[1:] != s_key[:-1]])
                start_pos = lax.cummax(jnp.where(is_start, pos, 0))

                def summer(vals):
                    v = vals[s_ix]
                    cum = jnp.cumsum(v, axis=0) - v        # exclusive
                    prior_sorted = cum - cum[start_pos]
                    return jnp.zeros_like(vals).at[s_ix].set(prior_sorted)

                rank = jnp.zeros(K, jnp.int32).at[s_ix].set(
                    (pos - start_pos).astype(jnp.int32))
                return rank, summer

            _, prior_sum_node = _seg(cand, cand_ok)

            def prior_rank_any(key, m):
                rank, _ = _seg(key, m)
                return jnp.where(m, rank, 0)

            def prior_rank(key, member):
                # exclusive count of earlier ok members with equal key;
                # non-members get a key outside every real segment
                keyc = jnp.where(member, key, jnp.int32(0x3FFFFFF0))
                rank, _ = _seg(keyc, cand_ok)
                return jnp.where(member, rank, 0)

        res_k = ask_res[g_idx] * cand_ok[:, None]
        prior = prior_sum_node(res_k)                      # [K, R]
        if in_mesh:
            # candidate rows live on their owning shard: each shard
            # evaluates the fit for the <= K candidates it owns and the
            # K-sized bit vectors reduce over the tiered interconnect
            # (candidate-only traffic — the [Np, R] planes stay put).
            # _g2l pins every non-owned candidate to the always-dropped
            # Np slot (scatter WRAPS python-style negatives before
            # mode="drop" checks bounds).
            inb, loc, locc = _g2l(cand)
            fits_l = ((used[locc] + prior + ask_res[g_idx])
                      <= avail[locc]).all(axis=-1) & inb
            fits = _psum_mesh(fits_l.astype(jnp.int32)) > 0
        else:
            loc = locc = cand
            inb = None
            fits = ((used[cand] + prior + ask_res[g_idx])
                    <= avail[cand]).all(axis=-1)
        if has_devices:
            dev_k = dev_ask[g_idx] * cand_ok[:, None]
            prior_dev = prior_sum_node(dev_k)              # [K, D]
            if in_mesh:
                dev_fits_l = ((dev_used[locc] + prior_dev
                               + dev_ask[g_idx])
                              <= dev_cap[locc]).all(axis=-1) & inb
                dev_fits = _psum_mesh(
                    dev_fits_l.astype(jnp.int32)) > 0
            else:
                dev_fits = ((dev_used[cand] + prior_dev + dev_ask[g_idx])
                            <= dev_cap[cand]).all(axis=-1)
        else:
            dev_fits = jnp.ones(K, bool)
        if in_mesh and has_spread:
            # one [K, A] psum-gather of the candidates' attribute-rank
            # rows serves both the spread quota and the commit below
            ar_cand = _psum_mesh(
                jnp.where(inb[:, None],
                          attr_rank[locc].astype(jnp.int32), 0))
        else:
            ar_cand = None

        # distinct_hosts: one commit per (node, distinct group) per wave;
        # cross-wave blocking keeps later waves off the node too
        if has_distinct:
            dg = distinct[g_idx]
            dg_key = cand * jnp.int32(Gp) + jnp.maximum(dg, 0)
            dg_ok = prior_rank(dg_key, dg >= 0) == 0
        else:
            dg_ok = jnp.ones(K, bool)

        # distinct_property: a commit only while this wave's earlier
        # candidates on each of its (slot, value) pairs leave room in
        # what the value has left (a candidate counts in every slot of
        # its group: one sort over the K x J pairs, ranked by candidate)
        if has_property:
            sl_c = pl_slot[g_idx]                          # [K, J]
            slc_c = jnp.maximum(sl_c, 0)
            v_c = pl_vnode[slc_c, cand[:, None]]
            memb = cand_ok[:, None] & (sl_c >= 0)
            L = K * J
            keyf = jnp.where(memb, slc_c * jnp.int32(VL)
                             + jnp.maximum(v_c, 0),
                             jnp.int32(0x7FFFFFF0)).reshape(L)
            posf = jnp.broadcast_to(ks[:, None], (K, J)).reshape(L)
            ixf = jnp.arange(L, dtype=jnp.int32)
            s_key, _s_pos, s_ix = lax.sort((keyf, posf, ixf), num_keys=2)
            is_start = jnp.concatenate(
                [jnp.ones(1, bool), s_key[1:] != s_key[:-1]])
            seg0 = lax.cummax(jnp.where(is_start, ixf, 0))
            pl_rank = jnp.zeros(L, jnp.int32).at[s_ix].set(
                ixf - seg0).reshape(K, J)
            left_c = pl_left_w[slc_c, jnp.maximum(v_c, 0)]
            pl_ok = ((sl_c < 0) | ((v_c >= 0) & (
                pl_rank.astype(jnp.float32) < left_c))).all(axis=1)
        else:
            pl_ok = jnp.ones(K, bool)

        # spread quota: cap same-wave commits per (group, slot, value) so
        # a wave cannot blow far past a spread target the serial
        # reference would have steered away from; targeted spreads stop
        # at their desired counts, even spreads at a balanced level
        # (S is a small static pad; unrolled)
        sp_ok = jnp.ones(K, bool)
        for s in (range(S) if has_spread else range(0)):
            cols = sp_col[g_idx, s]
            if in_mesh:
                vs = jnp.take_along_axis(
                    ar_cand, jnp.maximum(cols, 0)[:, None], axis=1)[:, 0]
            else:
                vs = attr_rank[cand, jnp.maximum(cols, 0)]
            has_s = (cols >= 0) & (vs >= 0)
            vsc = jnp.maximum(vs, 0)
            des_s = sp_desired[:, s]                       # [Gp, V]
            use_s = sp_used[:, s]
            des_eff = jnp.where(des_s < 0, sp_implicit[:, s][:, None],
                                des_s)
            present = use_s > 0
            maxc = jnp.max(jnp.where(present, use_s, 0.0),
                           axis=1)[:, None]
            minc = jnp.min(jnp.where(present, use_s,
                                     jnp.where(present.any(axis=1)[:, None],
                                               jnp.inf, 0.0)),
                           axis=1)[:, None]
            minc = jnp.where(jnp.isfinite(minc), minc, 0.0)
            # even spread: every value may grow to a common level L =
            # max(current max, min + fair share of this wave's active
            # placements) — for the FIRST HALF of the wave budget.
            # Near capacity the min value may be almost exhausted yet
            # keep absorbing a node or two per wave; anchored to it,
            # every other value's quota collapses to 1/wave and the
            # batch stalls (config 3's retry storm).  The serial
            # reference only ever steers by SCORE (spread.go penalizes
            # an overfilled value, never hard-blocks), so after the
            # balanced half-budget the quota relaxes and the remaining
            # placements fill whatever capacity exists, score-steered.
            share = jnp.ceil(act_g.astype(jnp.float32) / V)[:, None]
            level = jnp.maximum(maxc, minc + share)
            even_q = jnp.where(wave < jnp.int32(max(max_waves // 2, 1)),
                               jnp.maximum(1.0, level - use_s),
                               jnp.inf)
            quota = jnp.where(
                sp_targeted[:, s][:, None],
                jnp.maximum(1.0, des_eff - use_s),
                even_q)                                    # [Gp, V]
            gv_key = (g_idx * jnp.int32(V) + vsc) * jnp.int32(2) + 1
            gv_rank = prior_rank(gv_key, has_s).astype(jnp.float32)
            sp_ok &= ~has_s | (gv_rank < quota[g_idx, vsc])

        commit = cand_ok & fits & dev_fits & dg_ok & sp_ok & pl_ok
        cm = commit[:, None]

        # -- apply all of this wave's commits at once (coll/blocked are
        # rebuilt from the outputs next wave, not carried); in mesh
        # mode each shard scatters only the rows it owns (mode="drop"
        # discards other shards' candidates) while the replicated
        # sp_used updates identically everywhere --
        used = used.at[loc].add(ask_res[g_idx] * cm, mode="drop")
        if has_devices:
            dev_used = dev_used.at[loc].add(dev_ask[g_idx] * cm,
                                            mode="drop")
        if has_spread:
            if in_mesh:
                svals = jnp.take_along_axis(
                    ar_cand, jnp.maximum(sp_col[g_idx], 0), axis=1)
            else:
                svals = attr_rank[cand[:, None],
                                  jnp.maximum(sp_col[g_idx], 0)]
            okslot = (sp_col[g_idx] >= 0) & (svals >= 0) & cm
            sp_used = sp_used.at[g_idx[:, None], jnp.arange(S)[None, :],
                                 jnp.maximum(svals, 0)].add(
                okslot.astype(jnp.float32))

        # ---------------- preemption wave pass (ISSUE 7) ----------------
        # Runs AFTER the normal commits against post-commit usage, only
        # for groups with nothing placeable this wave.  Greedy min-cost
        # victim selection per (group, node) over the top-E evictable
        # planes — the float-order-exact twin of
        # scheduler/preemption.victim_distance — then node choice by
        # post-eviction bin-pack score (the reference feeds preemption
        # options through the regular rank/max pipeline).  In mesh mode
        # the heavy work is shard-local; only per-group best eviction
        # KEYS (score, global node id) ride the candidate-key ICI
        # exchange, exactly like the placement windows.
        if has_preempt:
            want = active & ~commit & ~grp_any[g_idx]
            want_g = (jnp.zeros(Gp, jnp.int32).at[g_idx]
                      .add(want.astype(jnp.int32)) > 0)

            def do_evict(args):
                used_x, dev_used_x, evt = args
                f32 = jnp.float32
                es = jnp.arange(EV)
                # shortfall base: usage + ask - capacity, per (g, n)
                base_short = (used_x[None, :, :] + ask_res[:, None, :]
                              - avail[None, :, :])     # [Gp, Np, R]
                slot_free = ev_slot_ok & ~evt[None, :, :]
                freed = jnp.zeros((Gp, Np, R), f32)
                picked = jnp.zeros((Gp, Np, EV), bool)
                prank = jnp.full((Gp, Np, EV), EV, jnp.int32)
                for t in range(EV):
                    s = jnp.maximum(base_short - freed, 0.0)
                    covered = (s <= 0.0).all(axis=-1)
                    norm = jnp.maximum(s, 1.0)
                    diff = ((s[:, :, None, :] - ev_res_f[None, :, :, :])
                            / norm[:, :, None, :])     # [Gp, Np, E, R]
                    d2 = diff * diff
                    # explicit association — part of the host-twin
                    # float-order contract (victim_distance)
                    dist = jnp.sqrt(((d2[..., 0] + d2[..., 1])
                                     + d2[..., 2]) + d2[..., 3])
                    cand_e = slot_free & ~picked
                    dist = jnp.where(cand_e, dist, f32(1e30))
                    e_star = jnp.argmin(dist, axis=-1)  # first min wins
                    take = cand_e.any(axis=-1) & ~covered
                    oh = ((es[None, None, :] == e_star[..., None])
                          & take[..., None])
                    picked = picked | oh
                    prank = jnp.where(oh, jnp.int32(t), prank)
                    freed = freed + (ev_res_f[None, :, :, :]
                                     * oh[..., None]).sum(axis=2)
                # redundancy prune (preemption.prune_superset order:
                # highest-priority victims first, pick order on ties)
                key = jnp.where(
                    picked,
                    (jnp.int32(32768) - ev_prio_i[None, :, :])
                    * jnp.int32(EV + 1) + prank,
                    jnp.int32(2 ** 30))
                seq = jnp.argsort(key, axis=-1)
                for t in range(EV):
                    e_t = seq[..., t]
                    oh = es[None, None, :] == e_t[..., None]
                    is_p = (picked & oh).any(axis=-1)
                    vec = (ev_res_f[None, :, :, :]
                           * oh[..., None]).sum(axis=2)
                    trial = freed - vec
                    still = ((base_short - trial) <= 0.0).all(axis=-1)
                    drop = is_p & still
                    picked = picked & ~(oh & drop[..., None])
                    freed = jnp.where(drop[..., None], trial, freed)

                covered_f = ((base_short - freed) <= 0.0).all(axis=-1)
                if has_devices:
                    # device instances are never evicted in-kernel: the
                    # node must fit the device ask as-is (device-dim
                    # shortfalls keep the host preemption fallback)
                    dev_fit_ev = (dev_used_x[None, :, :]
                                  + dev_ask[:, None, :]
                                  <= dev_cap[None, :, :]).all(axis=-1)
                else:
                    dev_fit_ev = jnp.ones((Gp, Np), bool)
                ok_node = (covered_f & picked.any(axis=-1) & feas
                           & dev_fit_ev & want_g[:, None])
                after = (used_x[None, :, :] + ask_res[:, None, :]
                         - freed)
                binpack = _score_spec.rescore_binpack(
                    _JAX_OPS, after, avail, reserved)
                ev_score = jnp.where(ok_node, binpack, f32(NEG_INF))
                ids = (g_of_local if in_mesh
                       else jnp.arange(Np, dtype=jnp.int32))
                ids2 = jnp.broadcast_to(ids[None, :], (Gp, Np))
                slots2 = jnp.broadcast_to(
                    jnp.arange(Np, dtype=jnp.int32)[None, :], (Gp, Np))
                # lex top-1 by (score desc, GLOBAL id asc), carrying
                # the local slot (under the elastic remap slot order is
                # not id order, so the slot cannot be derived back)
                neg_e, nv_i2, nv_l2 = lax.sort(
                    (-ev_score, ids2, slots2), num_keys=2)
                nv_s_l, nv_i_l = -neg_e[:, 0], nv_i2[:, 0]
                # freed/picked at the LOCAL best node: the cross-shard
                # winner is always some shard's local best, so the
                # owner already holds its victim set
                loc_best = nv_l2[:, 0]
                sel_freed = freed[gs, loc_best]             # [Gp, R]
                sel_mask = picked[gs, loc_best]             # [Gp, EV]
                return nv_s_l, nv_i_l, sel_freed, sel_mask

            def skip_evict(args):
                return (jnp.full(Gp, NEG_INF, jnp.float32),
                        jnp.zeros(Gp, jnp.int32),
                        jnp.zeros((Gp, R), jnp.float32),
                        jnp.zeros((Gp, EV), bool))

            # `want` derives from replicated values, so the predicate
            # is mesh-uniform and both branches stay collective-free —
            # the key exchange below runs unconditionally
            nv_s, nv_i, sel_freed, sel_mask = lax.cond(
                want.any(), do_evict, skip_evict,
                (used, dev_used, EVT))

            if in_mesh:
                wv_s2, wv_i2 = _merge_mesh(nv_s[:, None],
                                           nv_i[:, None], 1)
                win_s, win_i = wv_s2[:, 0], wv_i2[:, 0]
            else:
                win_s, win_i = nv_s, nv_i
            ev_any_g = win_s > NEG_INF / 2                  # [Gp]

            e_cand = win_i[g_idx]                           # [K] global
            p_ok = want & ev_any_g[g_idx]
            # one preemption commit per node per wave (across groups):
            # two victim sets computed independently must never both
            # apply to one node
            ev_commit = p_ok & (prior_rank_any(e_cand, p_ok) == 0)
            ecm = ev_commit[:, None]
            if in_mesh:
                e_inb, e_loc, e_locc = _g2l(e_cand)
            else:
                e_loc = e_locc = e_cand
                e_inb = jnp.ones(K, bool)
            own = (e_inb & ev_commit)[:, None]
            # victims leave, the new placement lands — one scatter
            used = used.at[e_loc].add(
                (ask_res[g_idx] - sel_freed[g_idx]) * ecm, mode="drop")
            if has_devices:
                dev_used = dev_used.at[e_loc].add(
                    dev_ask[g_idx] * ecm, mode="drop")
            em_local = sel_mask[g_idx] & own                # [K, EV]
            EVT = EVT | (jnp.zeros((Np, EV), jnp.int32).at[e_loc].add(
                em_local.astype(jnp.int32), mode="drop") > 0)
            if in_mesh:
                em_rep = _psum_mesh(em_local.astype(jnp.int32)) > 0
            else:
                em_rep = em_local
            if has_spread:
                if in_mesh:
                    ar_ev = _psum_mesh(
                        jnp.where(own,
                                  attr_rank[e_locc].astype(jnp.int32),
                                  0))
                    evals_ = jnp.take_along_axis(
                        ar_ev, jnp.maximum(sp_col[g_idx], 0), axis=1)
                else:
                    evals_ = attr_rank[e_cand[:, None],
                                       jnp.maximum(sp_col[g_idx], 0)]
                ok_es = (sp_col[g_idx] >= 0) & (evals_ >= 0) & ecm
                sp_used = sp_used.at[g_idx[:, None],
                                     jnp.arange(S)[None, :],
                                     jnp.maximum(evals_, 0)].add(
                    ok_es.astype(jnp.float32))
            # a group with no placeable node AND no eviction option
            # fails; one with an eviction option keeps retrying
            fail_now = fail_now & ~ev_any_g[g_idx]
        else:
            ev_commit = jnp.zeros(K, bool)

        # -- record results: a committed placement's fall-through top-K is
        # its group's candidate list starting at its own rank --
        offs = cr[:, None] + jnp.arange(TOP_K)[None, :]    # < TK by constr.
        pk_idx = top_idx[g_idx[:, None], offs]
        pk_score = top_score[g_idx[:, None], offs]
        pk_ok = pk_score > NEG_INF / 2
        ok_row = pk_ok & cm
        if has_preempt:
            # an eviction-committed placement records its single chosen
            # node in slot 0 (no fall-through candidates — the victim
            # set is node-specific) with the post-eviction bin-pack
            # score; the evict mask rides in out_evict
            ecol = jnp.arange(TOP_K)[None, :] == 0
            pk_idx = jnp.where(ecm, jnp.where(ecol, e_cand[:, None], 0),
                               pk_idx)
            pk_score = jnp.where(
                ecm, jnp.where(ecol, win_s[g_idx][:, None], NEG_INF),
                pk_score)
            ok_row = jnp.where(ecm, ecol, ok_row)
        newly = commit | ev_commit | fail_now
        upd = newly[:, None]
        out_idx = jnp.where(upd, pk_idx, out_idx)
        out_score = jnp.where(upd, pk_score, out_score)
        out_ok = jnp.where(upd, ok_row, out_ok)
        if has_preempt:
            out_evict = jnp.where(upd, em_rep & ecm, out_evict)
        out_wave = jnp.where(commit | ev_commit, wave, out_wave)
        out_nfeas = jnp.where(newly, n_feas_out[g_idx], out_nfeas)
        out_nexh = jnp.where(newly, n_exh_out[g_idx], out_nexh)
        out_dimexh = jnp.where(newly[:, None], dim_exh_out[g_idx],
                               out_dimexh)
        done = done | newly

        if use_sl:
            # ---- end-of-wave shortlist maintenance ----
            # Post-commit state here IS the next wave's input, so the
            # next window and its validity are decided now: the next
            # wave either reads the carried [Gp, TK] window or runs the
            # full pass — never both.
            active_next = active & ~newly
            act_next_g = jnp.zeros(Gp, jnp.int32).at[g_idx].add(
                active_next.astype(jnp.int32)) > 0
            any_next = active_next.any()
            cf = commit.astype(jnp.float32)
            # TR1: every commit this wave (any group's) landed inside
            # this group's shortlist — otherwise an outsider's bin-pack
            # score moved and the frozen cutoff bound is void.  In mesh
            # mode only commits to THIS shard's nodes can move scores
            # on this shard's plane (binpack/coll are per-node, spread
            # is globally gated below), so the audit is shard-local:
            # owned commits vs the local shortlist.
            if in_mesh:
                tot = (cf * inb.astype(jnp.float32)).sum()
            else:
                tot = cf.sum()
            mark = jnp.zeros(Np, jnp.float32).at[loc].add(cf,
                                                          mode="drop")
            tr1_g = mark[SL.idx].sum(axis=1) == tot
            g_committed = jnp.zeros(Gp, jnp.float32).at[g_idx].add(
                cf) > 0
            if has_spread:
                has_sp_g = (sp_col >= 0).any(axis=1)
            else:
                has_sp_g = jnp.zeros(Gp, bool)
            # spread groups shift ALL their node scores when their OWN
            # sp_used changes (a commit with a spread value); a wave
            # where the group committed nothing leaves its spread state
            # — and so every outsider's score — frozen, and TR1/TR3
            # carry the proof.  Groups riding the per-value interleave
            # (want_tables + slot-0 spread) additionally need FULL
            # class coverage: their window draws from per-class tables
            # whose tails can rank below the global top-C, so only a
            # COMPLETE shortlist (outsiders permanently NEG_INF) makes
            # their re-rank provably exact.
            sp_gate = has_sp_g & g_committed
            if want_tables:
                sp_gate = sp_gate | (sp_col[:, 0] >= 0)
            ok_pre_g = SL.comp | (tr1_g & ~sp_gate)
            pre_ok = any_next & (ok_pre_g | ~act_next_g).all()
            if has_preempt:
                # an eviction REDUCES usage, breaking the monotone-
                # usage argument behind the `comp` bypass and freezing
                # guarantees wholesale: any evict commit this wave
                # forces the next wave back to a full-N rescore (which
                # rebuilds the shortlist and its era state)
                pre_ok = pre_ok & ~ev_commit.any()

            # own-group commit counts fold into the carried coll (the
            # window's shortlist positions resolve by bisection; a
            # full-wave window may hold interleave entries outside the
            # shortlist — those drop here AND fail TR1, forcing the
            # rescore that rebuilds coll from the plane)
            tloc = _g2l(top_idx)[1] if in_mesh else top_idx
            win_pos = jax.vmap(jnp.searchsorted)(SL.idx, tloc)
            pos_hit = jnp.take_along_axis(
                SL.idx, jnp.minimum(win_pos, C - 1), axis=1) == tloc
            win_pos = jnp.where(pos_hit, win_pos, C)       # drop slot
            cand_pos = win_pos[g_idx, cr]
            SL = SL._replace(coll=SL.coll.at[g_idx, cand_pos].add(
                cf, mode="drop"))

            def rerank(sl):
                """Fresh re-rank of the shortlist against post-commit
                state + TR3 cutoff audit + incremental counters."""
                _, _, exh_pre, dim_pre = _sl_eval(
                    sl, used_pre, dev_used_pre, sp_used)
                f_score, f_place, exh_post, dim_post = _sl_eval(
                    sl, used, dev_used, sp_used)
                # only shortlist nodes changed (TR1-guarded), so the
                # full-N counters advance by the shortlist delta
                d_exh = (exh_post.astype(jnp.int32)
                         - exh_pre.astype(jnp.int32)).sum(axis=1)
                d_dim = (dim_post.astype(jnp.int32)
                         - dim_pre.astype(jnp.int32)).sum(axis=1)
                nexh_next = n_exh_g + d_exh
                ndim_next = dim_exh_g + d_dim
                # lex tie-break key: GLOBAL ids under the elastic remap
                # (matching the building extraction and cut_i), local
                # slots otherwise (the block map is monotonic)
                sl_key = _l2g(sl.idx) if elastic else sl.idx
                w_s, w_i = _lex_topk(f_score, sl_key, TKl)
                # TR3: the re-ranked TKl-th key must still dominate the
                # era cutoff — no frozen outsider can rank inside (both
                # sides of the lex compare use the same id space as
                # cut_i)
                ls, li = w_s[:, TKl - 1], w_i[:, TKl - 1]
                tr3_g = (ls > sl.cut_s) | ((ls == sl.cut_s)
                                           & (li <= sl.cut_i))
                if want_tables:
                    # shortlist-local per-value tables for the post-
                    # merge interleave: exact for the groups that reach
                    # here (`comp` guarantees every placeable class
                    # member is present; NEG_INF filler indices differ
                    # from the full pass but are compacted to the tail
                    # and never commit)
                    vnode0 = sl.vn[0]
                    tabs_s, tabs_i = [], []
                    for v in range(Vs + 1):
                        vmask = ((vnode0 == v) if v < Vs
                                 else (vnode0 < 0))
                        sv = jnp.where(vmask, f_score, NEG_INF)
                        ts, ti = _lex_topk(sv, sl_key, TW)
                        tabs_s.append(ts)
                        tabs_i.append(ti)
                    tab_s = jnp.stack(tabs_s, axis=1)   # [Gp, V+1, TW]
                    tab_i = jnp.stack(tabs_i, axis=1)
                    if in_mesh and not elastic:
                        tab_i = tab_i + off
                else:
                    tab_s = jnp.full((Gp, 1, 1), NEG_INF, jnp.float32)
                    tab_i = jnp.zeros((Gp, 1, 1), jnp.int32)
                gany_next = jnp.where(sl.comp, f_place.any(axis=1),
                                      jnp.bool_(True))
                ok_next = ((tr3_g | sl.comp) | ~act_next_g).all()
                if in_mesh and not elastic:
                    w_i = w_i + off
                return (w_s, w_i, tab_s, tab_i, nexh_next, ndim_next,
                        gany_next, ok_next)

            def skip(sl):
                return (jnp.full((Gp, TKl), NEG_INF, jnp.float32),
                        jnp.zeros((Gp, TKl), jnp.int32),
                        jnp.full(sl.tb_s.shape, NEG_INF, jnp.float32),
                        jnp.zeros(sl.tb_i.shape, jnp.int32),
                        sl.nexh, sl.ndim, jnp.zeros(Gp, bool),
                        jnp.bool_(False))

            if lane_axis is not None:
                # same lane-uniform trick as the carried/full dispatch:
                # rerank when ANY lane wants it (the result is gated
                # per-lane by `pre_ok & sl_ok` below — a lane that
                # reranked on a void premise keeps ok=False and its
                # next wave runs the full pass, which rebuilds the
                # window from scratch before anything reads it)
                do_rerank = lax.psum(
                    jnp.int32(pre_ok), lane_axis) > jnp.int32(0)
            else:
                do_rerank = pre_ok
            (nw_s, nw_i, ntb_s, ntb_i, n_nexh, n_ndim, n_gany,
             sl_ok) = lax.cond(do_rerank, rerank, skip, SL)
            SL = SL._replace(win_s=nw_s, win_i=nw_i, tb_s=ntb_s,
                             tb_i=ntb_i, nfeas=n_feas_g,
                             nexh=n_nexh, ndim=n_ndim, gany=n_gany,
                             ok=pre_ok & sl_ok)

        return (used, dev_used, sp_used, done,
                out_idx, out_ok, out_score, out_nfeas, out_nexh, out_dimexh,
                wave + jnp.int32(1), n_resc, SL, EVT, out_evict,
                out_wave)

    # Two loop shapes, chosen statically by the caller:
    #
    # "scan" (default) — fixed-trip scan whose body is skipped through
    # `lax.cond` once every placement is decided.  In unbatched context
    # the cond lowers to a real branch, so drained waves cost only the
    # (compact) carry; the wave budget can be generous.
    #
    # "while" — `lax.while_loop` with the same condition.  Under a vmap
    # (the federated region-stacked solve) `lax.cond` degrades to
    # `select` and BOTH branches execute every wave for every lane, so
    # the scan shape pays the full budget; a while_loop instead runs
    # until every lane drains — the trip count is the max actual
    # convergence depth, evaluated ON DEVICE (no host sync per
    # iteration, the loop is one uninterrupted device program).
    #
    # The rank-wrap commit above converges real batches in a handful of
    # waves either way; anything still unfinished after max_waves is
    # reported in `unfinished` and flows into the system's blocked-eval
    # retry path.
    st0 = (used0, dev_used0, sp_used0,
           jnp.zeros(K, bool),
           jnp.zeros((K, TOP_K), jnp.int32),
           jnp.zeros((K, TOP_K), bool),
           jnp.full((K, TOP_K), NEG_INF, jnp.float32),
           jnp.zeros(K, jnp.int32),
           jnp.zeros(K, jnp.int32),
           jnp.zeros((K, R), jnp.int32),
           jnp.int32(0), jnp.int32(0), sl0,
           (jnp.zeros((Np, EV), bool) if has_preempt
            else jnp.zeros((1, 1), bool)),
           (jnp.zeros((K, EV), bool) if has_preempt
            else jnp.zeros((K, 1), bool)),
           jnp.full(K, -1, jnp.int32))
    if wave_mode == "while":
        def w_cond(st):
            return ((~st[3] & (ks < n_place)).any()
                    & (st[10] < jnp.int32(max_waves)))

        st_final = lax.while_loop(w_cond, body, st0)
    else:
        def body_scan(st, _):
            any_active = (~st[3] & (ks < n_place)).any()
            return lax.cond(any_active, body, lambda s: s, st), None

        (st_final, _) = lax.scan(body_scan, st0, None, length=max_waves)
    (used_final, dev_used_final, _, done, out_idx, out_ok, out_score,
     out_nfeas, out_nexh, out_dimexh, waves, n_resc, _,
     _, out_evict_f, out_wave_f) = st_final
    unfinished = ~done & (ks < n_place)
    if in_mesh:
        # per-shard full-pass count summed over the mesh: the HBM byte
        # model multiplies bytes_wave1 (a PER-SHARD plane walk) by this
        n_resc = (_psum_mesh(n_resc) if use_sl
                  else waves * jnp.int32(mesh_shards))

    return SolveResult(choice=out_idx, choice_ok=out_ok, score=out_score,
                       n_feasible=out_nfeas, n_exhausted=out_nexh,
                       dim_exhausted=out_dimexh, feas=feas,
                       cons_filtered=cons_filtered, used_final=used_final,
                       dev_used_final=dev_used_final, n_waves=waves,
                       unfinished=unfinished,
                       n_rescore=(n_resc if (use_sl or in_mesh)
                                  else waves),
                       evict=(out_evict_f if has_preempt else None),
                       commit_wave=(out_wave_f if has_preempt
                                    else None))
