"""Pallas-tiled fused wave scoring for the placement solve.

The wave kernel's per-iteration cost is pure HBM traffic: the jnp
implementation (`kernel.group_scores`) walks the [Gp, Np] plane half a
dozen times per wave (fit/after/binpack/anti/spread/normalize/select)
and materializes [Gp, Np, R] broadcast intermediates between passes.
This module fuses the whole scoring chain into ONE pass per node tile
resident in VMEM:

  for each tile of T nodes (grid axis):
      load the tile's static planes (feasibility, affinity+penalty
      score, scorer counts, jitter) and dynamic planes (usage,
      collocation, distinct-blocking) into VMEM once;
      compute feasibility ∧ fit ∧ device-fit, bin-pack, anti-affinity,
      spread (targeted + even), append-then-average normalization,
      seeded binning+jitter — all on VMEM-resident values;
      reduce the per-group explainability counters for the tile;
      EITHER write the tile's score row back (mode "score": one
      [Gp, Np] store total, the only HBM write of the wave)
      OR extract the tile's top-K partial in-kernel (mode "topk":
      nothing but [Gp, tiles*TKt] partials ever reaches HBM — the
      [G, N] wave never materializes at all).

Per-tile top-K partials merge with one small `lax.top_k` over
[Gp, tiles*TKt] outside the kernel; the tournament is EXACT: a row's
global top-K is a subset of the per-tile top-Ks, per-tile extraction
breaks ties low-index-first (same as `lax.top_k`), and tiles
concatenate in node order, so equal scores resolve in global node
order — bitwise the same selection the unfused kernel makes.  The
same-wave conflict commit then runs on the compacted [K] candidate
set exactly as before (kernel.py), so placements are identical by
construction; tests/test_pallas_kernel.py property-tests the full
solve against the `host.py` exact twin in interpreter mode on CPU.

Mode selection is static (trace-time): "topk" when the candidate
window is small enough for iterative in-VMEM extraction, "score"
otherwise (merged throughput batches with 1024-wide windows keep
`approx_max_k` on the fused score), "off" when shapes/features fall
outside the fused universe.  On a `tpu` backend the kernel is compiled
by Mosaic and a lowering failure RAISES with the compiler's message —
nothing here downgrades to the unfused path behind the caller's back.
On any other backend it runs in the pallas interpreter (same
semantics, no Mosaic), which is what tier-1 exercises.

Layout contract (what Mosaic accepts, see `fused_wave`): every block's
last dimension is the node tile (a multiple of 128 lanes) or a whole
small axis, so

  * node-side planes arrive TRANSPOSED, [R, Np] / [D, Np]: a resource
    row is a lane-dense (1, T) slice that broadcasts down the group
    sublanes — never a column-to-lane relayout;
  * the boolean planes (feasibility, penalty, distinct-blocking)
    arrive bitpacked along the GROUP axis (masks.pack_groups_i32): one
    int32 word holds 32 groups of one node column, [ceil(Gp/32), Np],
    and unpacks in-kernel with a sublane broadcast and a per-row
    shift.  Packing along the node axis would need a (Gp, T/32) block
    and a minor-dim reshape, both of which Mosaic refuses;
  * everything is 32-bit inside the kernel (v5e has no 8/16-bit VPU
    lanes for sub-(32,128) blocks);
  * small per-tile results leave through lane-dense 128-wide blocks
    (counters, per-tile top-K partials) built with iota selects, not
    single-lane dynamic stores.

`n_extract` decouples the in-kernel extraction width from the
candidate window TK: the full wave extracts the top-C shortlist
(C >= TK) in one pass, the caller windows the first TK and carries
the rest for shortlist-resident contention waves (kernel.py).
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: sentinel strictly below NEG_INF: masks already-extracted slots so
#: the next iterative extraction never re-picks them, while untouched
#: NEG_INF (infeasible) entries still extract in node order like
#: lax.top_k would return them
_EXTRACTED = -2e30
SCORE_BIN = 0.05
#: largest candidate window the in-kernel iterative extraction serves;
#: wider windows (merged throughput batches) use mode "score"
TOPK_MAX = 256
#: per-tile working-set budget, in [Gp, T] 32-bit plane elements: a
#: tile keeps a few dozen such planes live (double-buffered inputs
#: plus the scoring chain's intermediates), so 64K elements = 256 KiB
#: per plane stays inside _VMEM_LIMIT with room to spare at every Gp
_TILE_ELEMS = 1 << 16
#: scoped-VMEM ceiling handed to Mosaic (its 16 MiB default is sized
#: for matmul tiles; a v5e core has 128 MiB)
_VMEM_LIMIT = 64 << 20
#: spread value-vocabulary cap for the unrolled select-sum
_V_MAX = 16
#: lane width every small per-tile output is padded to
_LANES = 128

_R_CPU, _R_MEM = 0, 1


def _env_mode() -> str:
    """NOMAD_TPU_PALLAS: '1'/'interpret' force-enables (interpreted off
    TPU), '0' disables, unset = auto (on only for TPU backends)."""
    return os.environ.get("NOMAD_TPU_PALLAS", "").strip().lower()


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.lru_cache(maxsize=1)
def enabled() -> bool:
    env = _env_mode()
    if env in ("0", "off", "false"):
        return False
    if env in ("1", "on", "true", "interpret"):
        return True
    return jax.default_backend() == "tpu"


def pick_tile(Np: int, Gp: int) -> int:
    """Node-tile width: largest lane-aligned divisor of Np whose
    [Gp, T] working set fits the VMEM budget.  Padded node counts are
    a power of two (<= 4096) or a multiple of 1024 (tensorize
    _pad_nodes), so a divisor always exists."""
    budget = max(_TILE_ELEMS // max(Gp, 1), 128)
    for t in (2048, 1024, 512, 256, 128):
        if Np % t == 0 and t <= budget:
            return t
    return Np                       # tiny pow2 problems: one tile


def resolve_mode(Np: int, Gp: int, TK: int, V: int,
                 has_spread: bool, enabled_hint: Optional[bool] = None
                 ) -> str:
    """Trace-time mode pick for solve_kernel (all args static)."""
    on = enabled() if enabled_hint is None else enabled_hint
    if not on:
        return "off"
    if has_spread and V > _V_MAX:
        return "off"                # select-sum unroll would explode
    T = pick_tile(Np, Gp)
    if Np % T != 0:
        return "off"
    if TK <= TOPK_MAX:
        return "topk"
    return "score"


def _lane_pad(n: int) -> int:
    return -(-n // _LANES) * _LANES


def _vmem(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def fused_wave(*, mode, feas, blocked, aff, pen, jitter,
               coll, used_t, avail_t, reserved_t, ask_res, ask_desired,
               dev=None, spread=None, seed=0, TK=4, n_extract=0,
               tables_v=0):
    """One fused pass over node tiles producing the wave's scoring
    outputs.  Returns a dict:

      mode "score": score [Gp, Np] f32, counters (see below)
      mode "topk":  top_score/top_idx [Gp, n_extract] (exact, merged
                    from per-tile partials; n_extract defaults to TK —
                    the shortlist path extracts top-C >= TK in the same
                    pass), counters, and when tables_v>0
                    tab_s/tab_i [Gp, tables_v+1, TKv] — the per-value
                    candidate tables for spread-aware interleaving
                    (TKv is derived from the WINDOW width TK, not
                    n_extract, so the interleave matches the unfused
                    kernel exactly).

    counters: n_feas [Gp] i32, n_exh [Gp] i32, grp_any [Gp] bool,
    dim_exh [Gp, R] i32 — the per-wave explainability reductions.

    Layouts (module docstring): `feas`, `pen` and `blocked` are
    GROUP-bitpacked int32 words [ceil(Gp/32), Np]
    (masks.pack_groups_i32); `used_t`/`avail_t`/`reserved_t` are the
    node planes transposed to [R, Np].  `spread` packs (sp_vnode
    [S,Gp,Np] i32, sp_des [S,Gp,Np], sp_used [Gp,S,V], sp_weight
    [Gp,S], sp_targeted [Gp,S], sp_has [Gp,S], minc [Gp,S], maxc
    [Gp,S], anyp [Gp,S]); `dev` packs (dev_used_t [D,Np], dev_cap_t
    [D,Np], dev_ask [Gp,D]).  The small [Gp, S] flag arrays may be any
    dtype; they enter the kernel as int32.
    """
    Gp = aff.shape[0]
    R, Np = used_t.shape
    Gw = feas.shape[0]
    has_devices = dev is not None
    has_spread = spread is not None
    has_blocked = blocked is not None
    T = pick_tile(Np, Gp)
    n_tiles = Np // T
    NE = n_extract or TK
    TKt = min(NE, T)
    want_tables = mode == "topk" and tables_v > 0
    Vs = tables_v
    TKv = -(-TK // (Vs + 1)) if want_tables else 0
    TKvt = min(TKv, T) if want_tables else 0
    # lane-dense widths of the per-tile partial outputs
    TKp = _lane_pad(TKt)
    TKvp = _lane_pad(TKvt) if want_tables else 0
    CNT = 3 + R

    if has_spread:
        (sp_vnode, sp_des, sp_used, sp_weight, sp_targeted, sp_has,
         minc, maxc, anyp) = spread
        S = sp_vnode.shape[0]
        V = sp_used.shape[2]
    else:
        S = V = 0
    if has_devices:
        dev_used_t, dev_cap_t, dev_ask = dev
        D = dev_cap_t.shape[0]
    else:
        D = 0

    # ---- assemble inputs + block specs (order matters: the kernel
    # unpacks positionally) ----
    tile = lambda i: (0, i)              # [X, Np] planes     # noqa: E731
    full = lambda i: (0, 0)              # whole small arrays # noqa: E731
    i32 = jnp.int32
    inputs = [feas, pen, aff, jitter, coll]
    in_specs = [_vmem((Gw, T), tile)] * 2 + [_vmem((Gp, T), tile)] * 3
    if has_blocked:
        inputs.append(blocked)
        in_specs.append(_vmem((Gw, T), tile))
    inputs += [used_t, avail_t, reserved_t, ask_res,
               ask_desired.reshape(Gp, 1),
               jnp.asarray(seed, i32).reshape(1, 1)]
    in_specs += [_vmem((R, T), tile)] * 3 + [
        _vmem((Gp, R), full), _vmem((Gp, 1), full),
        pl.BlockSpec((1, 1), full, memory_space=pltpu.SMEM)]
    if has_devices:
        inputs += [dev_used_t, dev_cap_t, dev_ask]
        in_specs += [_vmem((D, T), tile), _vmem((D, T), tile),
                     _vmem((Gp, D), full)]
    if has_spread:
        s_tile = lambda i: (0, 0, i)     # noqa: E731
        inputs += [sp_vnode.astype(i32), sp_des,
                   sp_used.reshape(Gp, S * V), sp_weight,
                   sp_targeted.astype(i32), sp_has.astype(i32), minc,
                   maxc, anyp.astype(i32)]
        in_specs += [_vmem((S, Gp, T), s_tile),
                     _vmem((S, Gp, T), s_tile),
                     _vmem((Gp, S * V), full)] \
            + [_vmem((Gp, S), full)] * 6

    # ---- outputs ----
    out_shapes = []
    out_specs = []
    if mode == "score":
        out_shapes.append(jax.ShapeDtypeStruct((Gp, Np), jnp.float32))
        out_specs.append(_vmem((Gp, T), tile))
    else:
        out_shapes += [
            jax.ShapeDtypeStruct((Gp, n_tiles * TKp), jnp.float32),
            jax.ShapeDtypeStruct((Gp, n_tiles * TKp), i32)]
        out_specs += [_vmem((Gp, TKp), tile)] * 2
        if want_tables:
            out_shapes += [
                jax.ShapeDtypeStruct((Vs + 1, Gp, n_tiles * TKvp),
                                     jnp.float32),
                jax.ShapeDtypeStruct((Vs + 1, Gp, n_tiles * TKvp), i32)]
            vtile = lambda i: (0, 0, i)  # noqa: E731
            out_specs += [_vmem((Vs + 1, Gp, TKvp), vtile)] * 2
    out_shapes.append(jax.ShapeDtypeStruct((n_tiles, Gp, _LANES),
                                           jnp.float32))
    out_specs.append(_vmem((1, Gp, _LANES), lambda i: (i, 0, 0)))

    kernel = functools.partial(
        _wave_tile_kernel, mode=mode, Gp=Gp, T=T, R=R, D=D, S=S, V=V,
        TKt=TKt, TKp=TKp, Vs=Vs, TKvt=TKvt, TKvp=TKvp,
        has_devices=has_devices, has_spread=has_spread,
        has_blocked=has_blocked, want_tables=want_tables)

    outs = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=in_specs,
        out_shape=tuple(out_shapes),
        out_specs=tuple(out_specs),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name=f"nomad_wave_{mode}",
    )(*inputs)

    # ---- merge per-tile partials (the "small reduction") ----
    def _strip(x, w, wp):
        """[..., n_tiles * wp] lane-padded partials -> [..., n_tiles * w]"""
        if w == wp:
            return x
        lead = x.shape[:-1]
        return x.reshape(lead + (n_tiles, wp))[..., :w].reshape(
            lead + (n_tiles * w,))

    res = {}
    oi = 0
    if mode == "score":
        res["score"] = outs[oi]
        oi += 1
    else:
        ts_all = _strip(outs[oi], TKt, TKp)
        ti_all = _strip(outs[oi + 1], TKt, TKp)
        oi += 2
        mTK = min(NE, n_tiles * TKt)
        ms, pos = lax.top_k(ts_all, mTK)
        mi = jnp.take_along_axis(ti_all, pos, axis=1)
        if mTK < NE:                 # tiny problems: pad like top_k of
            pad = NE - mTK           # a row narrower than k never is —
            ms = jnp.concatenate(    # callers clamp TK <= Np upstream
                [ms, jnp.full((Gp, pad), NEG_INF, jnp.float32)], axis=1)
            mi = jnp.concatenate(
                [mi, jnp.zeros((Gp, pad), jnp.int32)], axis=1)
        res["top_score"], res["top_idx"] = ms, mi
        if want_tables:
            vts = _strip(outs[oi], TKvt, TKvp)
            vti = _strip(outs[oi + 1], TKvt, TKvp)
            oi += 2
            mv = min(TKv, n_tiles * TKvt)
            tab_s, vpos = lax.top_k(
                vts.transpose(1, 0, 2), mv)          # [Gp, Vs+1, mv]
            tab_i = jnp.take_along_axis(vti.transpose(1, 0, 2), vpos,
                                        axis=2)
            if mv < TKv:
                padv = TKv - mv
                tab_s = jnp.concatenate(
                    [tab_s, jnp.full((Gp, Vs + 1, padv), NEG_INF,
                                     jnp.float32)], axis=2)
                tab_i = jnp.concatenate(
                    [tab_i, jnp.zeros((Gp, Vs + 1, padv), jnp.int32)],
                    axis=2)
            res["tab_s"], res["tab_i"] = tab_s, tab_i
    cnt = outs[oi][:, :, :CNT].sum(axis=0)            # [Gp, CNT]
    res["n_feas"] = cnt[:, 0].astype(jnp.int32)
    res["n_exh"] = cnt[:, 1].astype(jnp.int32)
    res["grp_any"] = cnt[:, 2] > 0
    res["dim_exh"] = cnt[:, 3:3 + R].astype(jnp.int32)
    return res


def _unpack_groups(words, Gp, T):
    """[ceil(Gp/32), T] group-packed int32 words -> [Gp, T] bool: row g
    reads bit g % 32 of word row g // 32 (a sublane broadcast and a
    per-row shift — no lane movement)."""
    rows = [jnp.broadcast_to(words[k:k + 1, :], (min(32, Gp - 32 * k), T))
            for k in range(words.shape[0])]
    w = rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=0)
    bit = lax.broadcasted_iota(jnp.int32, (Gp, T), 0) & 31
    return (lax.shift_right_logical(w, bit) & 1) != 0


def _extract_topk(sc, col_ids, n_out, width, base):
    """Iteratively pop the row-wise max `n_out` times, ties broken by
    LOWER column (lax.top_k's order).  Returns ([Gp, width] scores,
    [Gp, width] node ids = column + base); slot j holds the j-th pop,
    slots >= n_out stay at the _EXTRACTED filler.  The outputs are
    carried as lane-dense values and filled by iota select."""
    Gp = sc.shape[0]
    slot = lax.broadcasted_iota(jnp.int32, (Gp, width), 1)

    def body(j, carry):
        sc, out_s, out_i = carry
        m = jnp.max(sc, axis=1, keepdims=True)             # [Gp, 1]
        am = jnp.min(jnp.where(sc == m, col_ids, jnp.int32(1 << 30)),
                     axis=1, keepdims=True)                # [Gp, 1]
        hit = slot == j
        out_s = jnp.where(hit, m, out_s)
        out_i = jnp.where(hit, am + base, out_i)
        sc = jnp.where(col_ids == am, jnp.float32(_EXTRACTED), sc)
        return sc, out_s, out_i

    _, out_s, out_i = lax.fori_loop(
        0, n_out, body,
        (sc, jnp.full((Gp, width), _EXTRACTED, jnp.float32),
         jnp.zeros((Gp, width), jnp.int32)))
    return out_s, out_i


def _wave_tile_kernel(*refs, mode, Gp, T, R, D, S, V, TKt, TKp, Vs,
                      TKvt, TKvp, has_devices, has_spread, has_blocked,
                      want_tables):
    """The fused per-tile pass.  Positional refs mirror fused_wave's
    input/output assembly exactly."""
    it = iter(refs)
    feas_ref = next(it)          # group-packed i32 words
    pen_ref = next(it)           # group-packed i32 words
    aff_ref = next(it)
    jitter_ref = next(it)
    coll_ref = next(it)
    blocked_ref = next(it) if has_blocked else None   # packed i32
    used_ref = next(it)          # [R, T]
    avail_ref = next(it)
    reserved_ref = next(it)
    ask_res_ref = next(it)
    ask_desired_ref = next(it)
    seed_ref = next(it)
    if has_devices:
        dev_used_ref, dev_cap_ref, dev_ask_ref = (next(it), next(it),
                                                  next(it))
    if has_spread:
        (sp_vnode_ref, sp_des_ref, sp_used_ref, sp_w_ref, sp_t_ref,
         sp_has_ref, minc_ref, maxc_ref, anyp_ref) = (
            next(it), next(it), next(it), next(it), next(it), next(it),
            next(it), next(it), next(it))
    if mode == "score":
        score_ref = next(it)
    else:
        ts_ref = next(it)
        ti_ref = next(it)
        if want_tables:
            vts_ref = next(it)
            vti_ref = next(it)
    cnt_ref = next(it)

    i = pl.program_id(0)
    f32 = jnp.float32

    feas_b = _unpack_groups(feas_ref[...], Gp, T)      # [Gp, T]
    if has_blocked:
        feas_b &= ~_unpack_groups(blocked_ref[...], Gp, T)

    # ---- resource fit + bin-pack, one static unroll over R ----
    # node rows are (1, T) lane-dense slices, ask columns (Gp, 1)
    fit = jnp.ones((Gp, T), bool)
    dim_fail = []
    util_cpu = util_mem = None
    denom_cpu = denom_mem = None
    for r in range(R):
        avail_r = avail_ref[r:r + 1, :]
        after_r = used_ref[r:r + 1, :] + ask_res_ref[:, r:r + 1]
        fit_r = after_r <= avail_r
        fit &= fit_r
        dim_fail.append(jnp.sum((feas_b & ~fit_r).astype(f32), axis=1,
                                keepdims=True))
        if r == _R_CPU:
            util_cpu = after_r + reserved_ref[r:r + 1, :]
            denom_cpu = avail_r
        elif r == _R_MEM:
            util_mem = after_r + reserved_ref[r:r + 1, :]
            denom_mem = avail_r

    dev_fit = jnp.ones((Gp, T), bool)
    if has_devices:
        for d in range(D):
            dev_fit &= ((dev_used_ref[d:d + 1, :]
                         + dev_ask_ref[:, d:d + 1])
                        <= dev_cap_ref[d:d + 1, :])

    placeable = feas_b & fit & dev_fit

    ok_denoms = (denom_cpu > 0) & (denom_mem > 0)
    free_cpu = f32(1.0) - util_cpu / jnp.maximum(denom_cpu, f32(1.0))
    free_mem = f32(1.0) - util_mem / jnp.maximum(denom_mem, f32(1.0))
    raw = f32(20.0) - (f32(10.0) ** free_cpu + f32(10.0) ** free_mem)
    binpack = jnp.where(ok_denoms,
                        jnp.clip(raw, f32(0.0), f32(18.0)) / f32(18.0),
                        f32(0.0))

    # ---- anti-affinity (collocation) ----
    coll = coll_ref[...]
    anti = jnp.where(coll > 0,
                     -(coll + f32(1.0)) / ask_desired_ref[...],
                     f32(0.0))
    anti_counts = (coll > 0).astype(f32)

    # ---- spread (targeted + even), select-sum over the value vocab ----
    if has_spread:
        spread_total = jnp.zeros((Gp, T), f32)
        for s in range(S):
            has = sp_has_ref[:, s:s + 1] != 0          # [Gp, 1]
            v = sp_vnode_ref[s]                        # [Gp, T]
            has_v = v >= 0
            cur = jnp.zeros((Gp, T), f32)
            for val in range(V):
                k = s * V + val
                cur = cur + jnp.where(v == val,
                                      sp_used_ref[:, k:k + 1],
                                      f32(0.0))
            desired = sp_des_ref[s]                    # [Gp, T]
            boost = ((desired - (cur + f32(1.0)))
                     / jnp.maximum(desired, f32(1e-9))
                     ) * sp_w_ref[:, s:s + 1]
            targeted = jnp.where(~has_v, f32(-1.0),
                                 jnp.where(desired <= 0, f32(-1.0),
                                           boost))
            minc = minc_ref[:, s:s + 1]
            maxc = maxc_ref[:, s:s + 1]
            anyp = anyp_ref[:, s:s + 1] != 0
            delta_boost = (minc - cur) / jnp.maximum(minc, f32(1e-9))
            even = jnp.where(cur != minc, delta_boost,
                             jnp.where(minc == maxc, f32(-1.0),
                                       (maxc - minc)
                                       / jnp.maximum(minc, f32(1e-9))))
            even = jnp.where(~has_v, f32(-1.0), even)
            even = jnp.where(anyp, even, f32(0.0))
            contrib = jnp.where(sp_t_ref[:, s:s + 1] != 0, targeted,
                                even)
            spread_total = spread_total + jnp.where(has, contrib,
                                                    f32(0.0))
        spread_counts = (spread_total != 0.0).astype(f32)
    else:
        spread_total = f32(0.0)
        spread_counts = f32(0.0)

    # ---- normalize + seeded binning + jitter + mask ----
    # EXACT float summation order of kernel.group_scores: f32 addition
    # is not associative, and the pallas path must be bitwise the
    # kernel/host twin's score for placement-identity to hold
    pen_counts = _unpack_groups(pen_ref[...], Gp, T)
    pen_score = jnp.where(pen_counts, f32(-1.0), f32(0.0))
    aff_sc = aff_ref[...]
    aff_counts = aff_sc != 0.0
    n_scorers = (1.0 + anti_counts + pen_counts.astype(f32)
                 + aff_counts.astype(f32) + spread_counts)
    total = (binpack + anti + pen_score + aff_sc
             + spread_total) / n_scorers
    seed = seed_ref[0, 0]
    total = jnp.where(seed == 0, total,
                      jnp.floor(total / f32(SCORE_BIN)) * f32(SCORE_BIN))
    total = total + jitter_ref[...]
    score = jnp.where(placeable, total, f32(NEG_INF))

    # ---- explainability counters for this tile: (Gp, 1) reductions
    # laid into one lane-dense (Gp, 128) block by iota select ----
    n_feas_t = jnp.sum(feas_b.astype(f32), axis=1, keepdims=True)
    n_exh_t = jnp.sum((feas_b & ~(fit & dev_fit)).astype(f32), axis=1,
                      keepdims=True)
    any_t = jnp.max(placeable.astype(f32), axis=1, keepdims=True)
    lane = lax.broadcasted_iota(jnp.int32, (Gp, _LANES), 1)
    cnt = jnp.zeros((Gp, _LANES), f32)
    for k, col in enumerate([n_feas_t, n_exh_t, any_t] + dim_fail):
        cnt = jnp.where(lane == k, col, cnt)
    cnt_ref[0] = cnt

    if mode == "score":
        score_ref[...] = score
        return

    # ---- in-kernel per-tile top-K extraction ----
    local_cols = lax.broadcasted_iota(jnp.int32, (Gp, T), 1)
    base = i * T
    ts_ref[...], ti_ref[...] = _extract_topk(score, local_cols, TKt,
                                             TKp, base)

    if want_tables:
        vnode0 = sp_vnode_ref[0]                       # [Gp, T]
        for vv in range(Vs + 1):
            vmask = (vnode0 == vv) if vv < Vs else (vnode0 < 0)
            sv = jnp.where(vmask, score, f32(NEG_INF))
            vts_ref[vv], vti_ref[vv] = _extract_topk(
                sv, local_cols, TKvt, TKvp, base)
