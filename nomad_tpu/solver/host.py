"""Host (numpy) mirror of the wave-solve kernel, for latency mode.

An interactive singleton eval (one job, a small cluster) finishes its
entire solve in well under a millisecond of arithmetic — less than one
dispatch and fetch of a device program costs, whatever the transport —
so small problems are cheaper in-process.  SURVEY §7.3 prescribes a
host fallback for exactly this regime (reference analog:
the in-process Go solve, scheduler/generic_sched.go:427) — the worker
picks the path by batch/cluster size, and the semantics MUST be the
kernel's: this module is a line-for-line numpy port of
`kernel.solve_kernel` (same wave loop, same scoring formulas, same
tie-breaks), differential-tested to produce identical placements.

Scope: exact only where the device kernel is exact — the dispatch
gate (`prefer_host`) excludes padded node counts that would take the
device's `approx_max_k` path, so host argsort and device top_k agree.

Shortlist note (ISSUE 4): the device kernel's contention waves may
re-rank a carried top-C shortlist instead of re-scoring all N
(kernel.py `shortlist_c`).  This twin deliberately stays FULL-RESCORE
on every wave: it is the semantic reference the shortlist path must
equal bit-for-bit — the kernel only takes a shortlist wave when its
validity triggers PROVE the result identical to this full rescore,
and escapes back to a full-N wave otherwise.  tests/test_shortlist.py
pins that contract; `n_rescore == n_waves` here by construction.
"""
from __future__ import annotations

import numpy as np

from . import score_spec as _score_spec
from .kernel import (EV_PRIORITY_DELTA, MAX_WAVES, MERGED_GP_MAX, NEG_INF,
                     TOP_K, WAVE_K, _APPROX_MIN_NP, _MERGED_W_CAP,
                     _SELECT_SUM_MAX_V, _WIDE_W_CAP, SolveResult)
from .tensorize import (OP_EQ, OP_GE, OP_GT, OP_IS_SET, OP_LE, OP_LT,
                        OP_NE, OP_NOT_SET, R_CPU, R_MEM)

#: spec-driver shim: every scoring float op this twin executes comes
#: from solver/score_spec.py through these numpy ops
_NP_OPS = _score_spec.NumpyOps()

# dispatch gate defaults: the host path wins whenever the numpy wave
# loop (microseconds per wave at these sizes) beats one transport
# round trip.  Above these sizes the device's fused throughput takes
# over; at/above _APPROX_MIN_NP the device kernel switches to
# approx_max_k and exactness would be lost anyway.
HOST_MAX_PLACE = 1024
HOST_MAX_CELLS = 1 << 18         # Gp * Np budget per wave


def prefer_host(n_nodes_padded: int, n_asks: int, n_place: int) -> bool:
    """Should this problem solve on host?  (The worker's path pick —
    reference: the always-in-process scheduler, nomad/worker.go.)"""
    return (n_nodes_padded < _APPROX_MIN_NP
            and n_place <= HOST_MAX_PLACE
            and n_nodes_padded * max(n_asks, 1) <= HOST_MAX_CELLS)


def _top_k(score: np.ndarray, k: int):
    """Exact descending top-k per row, ties broken by LOWER index first
    — lax.top_k's documented order."""
    order = np.argsort(-score, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(score, order, axis=1), order.astype(np.int32)


def _static_program(avail, valid, node_dc, attr_rank, dc_ok,
                    host_ok, c_op, c_col, c_rank, a_op, a_col, a_rank,
                    a_weight, a_host, sp_col, sp_desired, sp_implicit,
                    has_spread, cache=None):
    """The wave-invariant tensors: static feasibility + per-constraint
    filtered counts, affinity scores, hoisted spread lookups.  These
    depend only on the ask programs and the node template, so repeated
    evals with identical programs (the steady-state service workload)
    hit `cache` instead of recomputing — the host path's analog of the
    kernel's one-compile-many-calls amortization."""
    f32 = np.float32
    key = None
    if cache is not None:
        # the bytes themselves key the dict (equality-checked) — a
        # 64-bit pre-hash could silently collide two programs
        key = (c_op.tobytes(), c_col.tobytes(), c_rank.tobytes(),
               a_op.tobytes(), a_col.tobytes(), a_rank.tobytes(),
               a_weight.tobytes(), a_host.tobytes(),
               dc_ok.tobytes(), host_ok.tobytes(),
               sp_col.tobytes(), sp_desired.tobytes(),
               sp_implicit.tobytes(), bool(has_spread))
        hit = cache.get(key)
        if hit is not None:
            return hit
    Np = avail.shape[0]
    Gp = c_op.shape[0]
    S = sp_col.shape[1]
    V = sp_desired.shape[2]

    # vals3[g, n, c] = attr_rank[n, c_col[g, c]] — one gather for all
    # groups (the per-group loop dominated the solve cost)
    vals3 = attr_rank[:, c_col].transpose(1, 0, 2)       # [Gp, Np, C]
    ok3 = _op_eval3(vals3, c_op, c_rank)
    base = valid[None, :] & dc_ok[:, node_dc] & host_ok
    passed_prev = np.cumprod(
        np.concatenate([np.ones((Gp, Np, 1), bool), ok3[:, :, :-1]],
                       axis=2), axis=2).astype(bool)
    first_fail = base[:, :, None] & passed_prev & ~ok3
    cons_filtered = first_fail.sum(axis=1).astype(np.int32)  # [Gp, C]
    feas = base & ok3.all(axis=2)

    avals3 = attr_rank[:, a_col].transpose(1, 0, 2)
    match3 = _op_eval3(avals3, a_op, a_rank)
    aff_score = ((match3 * a_weight[:, None, :]).sum(axis=2)
                 + np.asarray(a_host, f32)).astype(f32)

    if has_spread:
        sp_vnode = np.full((S, Gp, Np), -1, np.int32)
        sp_des = np.zeros((S, Gp, Np), f32)
        for s in range(S):
            col = sp_col[:, s]
            has = col >= 0
            v = attr_rank[:, np.maximum(col, 0)].T.astype(np.int32)
            v = np.where(has[:, None], v, -1)
            # XLA gather semantics: out-of-range indices CLAMP
            desired = np.take_along_axis(
                np.asarray(sp_desired[:, s], f32),
                np.clip(v, 0, V - 1), axis=1)
            desired = np.where(v >= 0, desired, f32(-1.0))
            desired = np.where(desired < 0,
                               np.asarray(sp_implicit[:, s],
                                          f32)[:, None], desired)
            sp_vnode[s] = v
            sp_des[s] = desired
    else:
        sp_vnode = sp_des = None

    out = (feas, cons_filtered, aff_score, sp_vnode, sp_des)
    if cache is not None:
        if len(cache) > 256:
            cache.clear()
        cache[key] = out
    return out


def _op_eval3(vals: np.ndarray, op: np.ndarray, rank: np.ndarray
              ) -> np.ndarray:
    """[Gp, Np, C] variant of _op_eval (same semantics, one pass)."""
    found = vals >= 0
    rk = rank[:, None, :]
    eq = found & (vals == rk)
    res = np.ones_like(found)
    opb = op[:, None, :]
    res = np.where(opb == OP_EQ, eq, res)
    res = np.where(opb == OP_NE, ~eq, res)
    res = np.where(opb == OP_LT, found & (vals < rk), res)
    res = np.where(opb == OP_LE, found & (vals <= rk), res)
    res = np.where(opb == OP_GT, found & (vals > rk), res)
    res = np.where(opb == OP_GE, found & (vals >= rk), res)
    res = np.where(opb == OP_IS_SET, found, res)
    res = np.where(opb == OP_NOT_SET, ~found, res)
    return res


def host_solve_kernel(avail, reserved, used0, valid, node_dc, attr_rank,
                      ask_res, ask_desired, distinct, dc_ok, host_ok,
                      coll0, penalty,
                      c_op, c_col, c_rank, a_op, a_col, a_rank, a_weight,
                      a_host, sp_col, sp_weight, sp_targeted, sp_desired,
                      sp_implicit, sp_used0, dev_cap, dev_used0, dev_ask,
                      p_ask, n_place, seed=0, *, has_spread=True,
                      group_count_hint=0, max_waves=0,
                      stack_commit=False,
                      static_cache=None, has_preempt=False,
                      ev_res=None, ev_prio=None,
                      ask_prio=None, learned=None,
                      region_bias=None, pl_slot=None, pl_col=None,
                      pl_left=None) -> SolveResult:
    """Numpy port of kernel.solve_kernel — see that docstring for the
    wave semantics.  Every formula, window size, and tie-break matches;
    tests/test_host_solver.py asserts bitwise-equal placements.

    Scoring is spec-DRIVEN: this twin assembles the plane context and
    calls score_spec.evaluate_wave — the float ops live in ONE place
    (solver/score_spec.py) shared with the jit kernel.  `learned` is
    the optional precomputed [Gp, Np] learned-head plane (score_spec's
    reserved slot) and `region_bias` the cross-region placement
    affinity plane (ISSUE 13); None leaves the scorer byte-identical
    to a spec without the term."""
    f32 = np.float32
    avail = np.asarray(avail, f32)
    reserved = np.asarray(reserved, f32)
    used = np.array(used0, f32)
    ask_res = np.asarray(ask_res, f32)
    dev_cap = np.asarray(dev_cap, f32)
    dev_used = np.array(dev_used0, f32)
    dev_ask = np.asarray(dev_ask, f32)
    sp_used = np.array(sp_used0, f32)
    max_waves = max_waves or MAX_WAVES

    Np = avail.shape[0]
    Gp = ask_res.shape[0]
    S = sp_col.shape[1]
    R = avail.shape[1]
    K = p_ask.shape[0]
    per_group = group_count_hint if group_count_hint > 0 else K // 8
    w_cap = _MERGED_W_CAP if Gp <= MERGED_GP_MAX else _WIDE_W_CAP
    TK = min(max(WAVE_K, min(2 * per_group, w_cap)) + TOP_K, Np)
    W = max(TK - TOP_K, 1)
    ks = np.arange(K)
    gs = np.arange(Gp)
    g_idx = np.asarray(p_ask, np.int64)

    # ---------- wave-invariant program (cached across evals) ----------
    V = sp_desired.shape[2]
    feas, cons_filtered, aff_score, sp_vnode, sp_des = _static_program(
        avail, valid, node_dc, attr_rank, dc_ok, host_ok,
        c_op, c_col, c_rank, a_op, a_col, a_rank, a_weight, a_host,
        sp_col, sp_desired, sp_implicit, has_spread, cache=static_cache)
    pen_score, pen_counts = _score_spec.static_terms(_NP_OPS, penalty)

    # tie-break jitter (kernel's uint32 hash, bit-exact)
    u32 = np.uint32
    with np.errstate(over="ignore"):
        h = (np.arange(Np, dtype=u32)[None, :] * u32(2654435761)
             + (gs.astype(u32)[:, None] * u32(7919)
                + u32(seed)) * u32(40503))
        h = (h ^ (h >> u32(16))) * u32(2246822519)
    SCORE_BIN = _score_spec.SCORE_BIN
    jitter = (np.zeros((Gp, Np), f32) if seed == 0 else
              (h & u32(1023)).astype(f32) * f32(SCORE_BIN / 1023.0))

    def group_scores(used, dev_used, coll, sp_used, blocked):
        """Spec-driven scoring: assembles the plane context and defers
        every float op to score_spec.evaluate_wave (nomadlint SCORE6xx
        flags scoring arithmetic hand-added back here)."""
        ctx = dict(
            used=used, dev_used=dev_used, coll=coll, sp_used=sp_used,
            blocked=blocked, avail=avail, reserved=reserved,
            ask_res=ask_res, ask_desired=ask_desired, dev_cap=dev_cap,
            dev_ask=dev_ask, feas=feas, pen_score=pen_score,
            pen_counts=pen_counts, aff_score=aff_score,
            has_devices=True, has_spread=has_spread, sp_col=sp_col,
            sp_weight=sp_weight, sp_targeted=sp_targeted,
            vnode=sp_vnode, des=sp_des, S=S, V=V, shape=(Gp, Np),
            seed=seed, jitter=jitter, learned=learned,
            region_bias=region_bias)
        return _score_spec.evaluate_wave(_NP_OPS, ctx)

    # ---------- distinct_property slots (kernel.py twin) ----------
    has_property = pl_slot is not None
    if has_property:
        pl_slot = np.asarray(pl_slot, np.int64)
        pl_left = np.asarray(pl_left, f32)
        PS, VL = pl_left.shape
        J = pl_slot.shape[1]
        pl_vnode = np.asarray(attr_rank, np.int64)[
            :, np.asarray(pl_col, np.int64)].T             # [PS, Np]
        pl_member = np.zeros((Gp, PS), bool)
        for j in range(J):
            np.logical_or.at(pl_member, (gs, np.maximum(pl_slot[:, j], 0)),
                             pl_slot[:, j] >= 0)
        pl_missing = (pl_member[:, :, None]
                      & (pl_vnode < 0)[None, :, :]).any(axis=1)

        def pl_charged(mask, nodes):
            sl = pl_slot[g_idx]
            slc = np.maximum(sl, 0)
            v = pl_vnode[slc, nodes[:, None]]
            ok = mask[:, None] & (sl >= 0) & (v >= 0)
            out = np.zeros((PS, VL), f32)
            np.add.at(out, (slc, np.maximum(v, 0)), ok.astype(f32))
            return out

        def pl_blocked(left):
            full_n = np.take_along_axis(left <= 0, np.maximum(pl_vnode, 0),
                                        axis=1) & (pl_vnode >= 0)
            return (pl_member[:, :, None] & full_n[None, :, :]
                    ).any(axis=1) | pl_missing

    # ---------- in-kernel preemption planes (kernel.py twin) ----------
    if has_preempt:
        EVW = ev_prio.shape[1]
        ev_prio_i = np.asarray(ev_prio, np.int32)
        ev_res_f = np.asarray(ev_res, f32)
        ask_prio_i = np.asarray(ask_prio, np.int32)
        ev_slot_ok = ((ev_prio_i[None, :, :] >= 0)
                      & (ask_prio_i[:, None, None] - ev_prio_i[None, :, :]
                         >= EV_PRIORITY_DELTA))       # [Gp, Np, E]
        EVT = np.zeros((Np, EVW), bool)
        out_evict = np.zeros((K, EVW), bool)
    else:
        out_evict = None

    # ---------- wave loop state ----------
    done = np.zeros(K, bool)
    out_idx = np.zeros((K, TOP_K), np.int32)
    out_ok = np.zeros((K, TOP_K), bool)
    out_score = np.full((K, TOP_K), NEG_INF, f32)
    out_nfeas = np.zeros(K, np.int32)
    out_nexh = np.zeros(K, np.int32)
    out_dimexh = np.zeros((K, R), np.int32)
    out_wave = np.full(K, -1, np.int32)
    wave = 0
    Vs = sp_desired.shape[2]

    while wave < max_waves:
        active = ~done & (ks < n_place)
        if not active.any():
            break

        committed = done & out_ok[:, 0]
        chosen = np.where(committed, out_idx[:, 0], 0).astype(np.int64)
        coll = coll0.astype(f32).copy()
        np.add.at(coll, (g_idx, chosen), committed.astype(f32))
        dg_all = np.asarray(distinct)[g_idx]
        hit = np.zeros((Gp, Np), np.int32)
        np.add.at(hit, (np.maximum(dg_all, 0), chosen),
                  (committed & (dg_all >= 0)).astype(np.int32))
        hit = hit > 0
        blocked = (hit[np.maximum(distinct, 0)]
                   & (distinct >= 0)[:, None])
        if has_property:
            pl_left_w = pl_left - pl_charged(committed, chosen)
            blocked = blocked | pl_blocked(pl_left_w)

        score, placeable, feas_b, fit, fit_dims, dev_fit = group_scores(
            used, dev_used, coll, sp_used, blocked)
        top_score, top_idx = _top_k(score, TK)

        # spread-aware candidate interleaving (kernel's slot-0 path;
        # bypassed in stack mode — see kernel.py)
        if has_spread and Vs <= 8 and not stack_commit:
            has0 = sp_col[:, 0] >= 0
            vnode = sp_vnode[0]
            TKv = -(-TK // (Vs + 1))
            tabs_i, tabs_s = [], []
            for v in range(Vs + 1):
                vmask = (vnode == v) if v < Vs else (vnode < 0)
                sv = np.where(vmask, score, f32(NEG_INF))
                ts, ti = _top_k(sv, TKv)
                tabs_i.append(ti)
                tabs_s.append(ts)
            tab_i = np.stack(tabs_i, axis=1)
            tab_s = np.stack(tabs_s, axis=1)
            vord = np.argsort(-tab_s[:, :, 0], axis=1,
                              kind="stable").astype(np.int64)
            j = np.arange(TK)
            vj = vord[:, j % (Vs + 1)]
            inter_i = tab_i[gs[:, None], vj, (j // (Vs + 1))[None, :]]
            inter_s = tab_s[gs[:, None], vj, (j // (Vs + 1))[None, :]]
            order = np.argsort((inter_s <= NEG_INF / 2).astype(np.int32),
                               axis=1, kind="stable")
            inter_i = np.take_along_axis(inter_i, order, axis=1)
            inter_s = np.take_along_axis(inter_s, order, axis=1)
            top_idx = np.where(has0[:, None], inter_i, top_idx)
            top_score = np.where(has0[:, None], inter_s, top_score)

        grp_any = placeable.any(axis=1)

        n_feas_g = (feas_b & valid[None, :]).sum(axis=1)
        n_exh_g = (feas_b & valid[None, :] & ~(fit & dev_fit)).sum(axis=1)
        dim_exh_g = (feas_b[:, :, None] & valid[None, :, None]
                     & ~fit_dims).sum(axis=1)

        grp_onehot = ((g_idx[None, :] == gs[:, None])
                      & active[None, :]).astype(np.int32)
        act_g = grp_onehot.sum(axis=1)
        rank = (np.cumsum(grp_onehot, axis=1) - grp_onehot)[g_idx, ks]
        n_cand = (top_score > NEG_INF / 2).sum(axis=1)
        M = np.clip(np.minimum(n_cand, W), 1, W)
        with np.errstate(over="ignore"):
            g_hash = ((gs.astype(u32) * u32(2654435761))
                      ^ (u32(seed) * u32(2246822519)))
        g_off = (np.zeros(Gp, np.int32) if seed == 0 else
                 ((g_hash >> u32(8)) % u32(W)).astype(np.int32))
        rot = 0 if seed == 0 else wave
        if stack_commit:
            # serial-fidelity commits (kernel.py stack_commit note)
            cr = np.zeros_like(rank)
        else:
            cr = (rank + g_off[g_idx] + rot) % M[g_idx]
        cand = top_idx[g_idx, cr].astype(np.int64)
        cand_score = top_score[g_idx, cr]
        cand_ok = active & (cand_score > NEG_INF / 2)

        fail_now = active & ~grp_any[g_idx]

        # -- same-wave conflict checks (exact serial accumulation) --
        def prior_sum_node(vals):
            out = np.zeros_like(vals)
            acc = {}
            for p in range(K):
                if not cand_ok[p]:
                    continue
                key = int(cand[p])
                prev = acc.get(key)
                if prev is not None:
                    out[p] = prev
                acc[key] = (prev if prev is not None
                            else np.zeros(vals.shape[1], vals.dtype)
                            ) + vals[p]
            return out

        def prior_rank(key, member):
            out = np.zeros(K, np.int32)
            counts = {}
            m = member & cand_ok
            for p in range(K):
                if not m[p]:
                    continue
                kk = int(key[p])
                out[p] = counts.get(kk, 0)
                counts[kk] = out[p] + 1
            return out

        res_k = ask_res[g_idx] * cand_ok[:, None]
        prior = prior_sum_node(res_k)
        fits = ((used[cand] + prior + ask_res[g_idx])
                <= avail[cand]).all(axis=-1)
        dev_k = dev_ask[g_idx] * cand_ok[:, None]
        prior_dev = prior_sum_node(dev_k)
        dev_fits = ((dev_used[cand] + prior_dev + dev_ask[g_idx])
                    <= dev_cap[cand]).all(axis=-1)

        dg = np.asarray(distinct)[g_idx]
        dg_key = cand * np.int64(Gp) + np.maximum(dg, 0)
        dg_ok = prior_rank(dg_key, dg >= 0) == 0

        # distinct_property: earlier candidates on each (slot, value)
        # of a candidate's group, counted in candidate order
        pl_ok = np.ones(K, bool)
        if has_property:
            seen: dict = {}
            for p in range(K):
                if not cand_ok[p]:
                    continue
                mine = []
                for s_ in pl_slot[g_idx[p]]:
                    if s_ < 0:
                        continue
                    v_ = int(pl_vnode[s_, cand[p]])
                    key_ = (int(s_), max(v_, 0))
                    if v_ < 0 or seen.get(key_, 0) >= pl_left_w[key_]:
                        pl_ok[p] = False
                    mine.append(key_)
                for key_ in mine:
                    seen[key_] = seen.get(key_, 0) + 1

        sp_ok = np.ones(K, bool)
        for s in (range(S) if has_spread else range(0)):
            cols = sp_col[g_idx, s]
            vs = attr_rank[cand, np.maximum(cols, 0)]
            has_s = (cols >= 0) & (vs >= 0)
            vsc = np.maximum(vs, 0).astype(np.int64)
            des_s = np.asarray(sp_desired[:, s], f32)
            use_s = sp_used[:, s]
            des_eff = np.where(
                des_s < 0, np.asarray(sp_implicit[:, s], f32)[:, None],
                des_s)
            present = use_s > 0
            # hi_cnt/lo_cnt: the occupancy band the quota levels
            # against (NOT the spread scorer's minc/maxc — those live
            # in score_spec.term_spread; alias-distinct names keep the
            # driven-backend fingerprint empty)
            hi_cnt = np.max(np.where(present, use_s, f32(0.0)),
                            axis=1)[:, None]
            lo_cnt = np.min(np.where(present, use_s,
                                     np.where(present.any(axis=1)[:, None],
                                              np.inf, 0.0)),
                            axis=1)[:, None]
            lo_cnt = np.where(np.isfinite(lo_cnt), lo_cnt,
                              0.0).astype(f32)
            # even-spread quota for the first half of the wave budget
            # only (kernel.py quota block note)
            share = np.ceil(act_g.astype(f32) / V)[:, None]
            level = np.maximum(hi_cnt, lo_cnt + share)
            even_q = (np.maximum(f32(1.0), level - use_s)
                      if wave < max(max_waves // 2, 1)
                      else np.full_like(use_s, np.inf))
            quota = np.where(
                np.asarray(sp_targeted[:, s])[:, None],
                np.maximum(f32(1.0), des_eff - use_s),
                even_q)
            gv_key = (g_idx * np.int64(V) + vsc) * np.int64(2) + 1
            gv_rank = prior_rank(gv_key, has_s).astype(f32)
            # gather clamps (XLA OOB semantics) — the key stays exact
            sp_ok &= ~has_s | (gv_rank
                               < quota[g_idx, np.minimum(vsc, V - 1)])

        commit = cand_ok & fits & dev_fits & dg_ok & sp_ok & pl_ok
        cm = commit[:, None]

        np.add.at(used, cand, ask_res[g_idx] * cm)
        np.add.at(dev_used, cand, dev_ask[g_idx] * cm)
        if has_spread:
            svals = attr_rank[cand[:, None],
                              np.maximum(sp_col[g_idx], 0)]
            # XLA scatter semantics: out-of-range updates are DROPPED
            okslot = ((sp_col[g_idx] >= 0) & (svals >= 0)
                      & (svals < V) & cm)
            np.add.at(sp_used,
                      (g_idx[:, None], np.arange(S)[None, :],
                       np.clip(svals, 0, V - 1)),
                      okslot.astype(f32))

        # ---------- preemption wave pass (kernel.py twin) ----------
        ev_commit = np.zeros(K, bool)
        if has_preempt:
            want = active & ~commit & ~grp_any[g_idx]
            want_g = np.zeros(Gp, bool)
            np.logical_or.at(want_g, g_idx, want)
            win_s = np.full(Gp, NEG_INF, f32)
            win_i = np.zeros(Gp, np.int32)
            sel_freed = np.zeros((Gp, R), f32)
            sel_mask = np.zeros((Gp, EVW), bool)
            if want.any():
                es = np.arange(EVW)
                base_short = (used[None, :, :] + ask_res[:, None, :]
                              - avail[None, :, :])     # [Gp, Np, R]
                slot_free = ev_slot_ok & ~EVT[None, :, :]
                freed = np.zeros((Gp, Np, R), f32)
                picked = np.zeros((Gp, Np, EVW), bool)
                prank = np.full((Gp, Np, EVW), EVW, np.int32)
                for t in range(EVW):
                    s = np.maximum(base_short - freed, f32(0.0))
                    covered = (s <= 0.0).all(axis=-1)
                    norm = np.maximum(s, f32(1.0))
                    diff = ((s[:, :, None, :] - ev_res_f[None, :, :, :])
                            / norm[:, :, None, :])
                    d2 = diff * diff
                    dist = np.sqrt(((d2[..., 0] + d2[..., 1])
                                    + d2[..., 2]) + d2[..., 3])
                    cand_e = slot_free & ~picked
                    dist = np.where(cand_e, dist, f32(1e30))
                    e_star = np.argmin(dist, axis=-1)  # first min wins
                    take = cand_e.any(axis=-1) & ~covered
                    oh = ((es[None, None, :] == e_star[..., None])
                          & take[..., None])
                    picked = picked | oh
                    prank = np.where(oh, np.int32(t), prank)
                    freed = freed + (ev_res_f[None, :, :, :]
                                     * oh[..., None]).sum(axis=2,
                                                          dtype=f32)
                key = np.where(
                    picked,
                    (np.int32(32768) - ev_prio_i[None, :, :])
                    * np.int32(EVW + 1) + prank,
                    np.int32(2 ** 30))
                seq = np.argsort(key, axis=-1, kind="stable")
                for t in range(EVW):
                    e_t = seq[..., t]
                    oh = es[None, None, :] == e_t[..., None]
                    is_p = (picked & oh).any(axis=-1)
                    vec = (ev_res_f[None, :, :, :]
                           * oh[..., None]).sum(axis=2, dtype=f32)
                    trial = freed - vec
                    still = ((base_short - trial) <= 0.0).all(axis=-1)
                    drop = is_p & still
                    picked = picked & ~(oh & drop[..., None])
                    freed = np.where(drop[..., None], trial, freed)

                covered_f = ((base_short - freed) <= 0.0).all(axis=-1)
                dev_fit_ev = (dev_used[None, :, :] + dev_ask[:, None, :]
                              <= dev_cap[None, :, :]).all(axis=-1)
                ok_node = (covered_f & picked.any(axis=-1) & feas
                           & dev_fit_ev & want_g[:, None])
                after = (used[None, :, :] + ask_res[:, None, :]
                         - freed)
                binpack = _score_spec.rescore_binpack(
                    _NP_OPS, after, avail, reserved)
                ev_score = np.where(ok_node, binpack, f32(NEG_INF))
                wv_s, wv_i = _top_k(ev_score, 1)
                win_s, win_i = wv_s[:, 0], wv_i[:, 0].astype(np.int32)
                sel_freed = freed[gs, win_i]
                sel_mask = picked[gs, win_i]
            ev_any_g = win_s > NEG_INF / 2

            e_cand = win_i[g_idx].astype(np.int64)
            p_ok = want & ev_any_g[g_idx]
            # first member per node wins (prior_rank_any == 0 twin)
            seen_nodes: set = set()
            for p in range(K):
                if not p_ok[p]:
                    continue
                n = int(e_cand[p])
                if n not in seen_nodes:
                    ev_commit[p] = True
                    seen_nodes.add(n)
            ecm = ev_commit[:, None]
            np.add.at(used, e_cand,
                      (ask_res[g_idx] - sel_freed[g_idx]) * ecm)
            np.add.at(dev_used, e_cand, dev_ask[g_idx] * ecm)
            em = sel_mask[g_idx] & ecm
            np.logical_or.at(EVT, e_cand, em)
            if has_spread:
                evals_ = attr_rank[e_cand[:, None],
                                   np.maximum(sp_col[g_idx], 0)]
                ok_es = ((sp_col[g_idx] >= 0) & (evals_ >= 0)
                         & (evals_ < V) & ecm)
                np.add.at(sp_used,
                          (g_idx[:, None], np.arange(S)[None, :],
                           np.clip(evals_, 0, V - 1)),
                          ok_es.astype(f32))
            fail_now = fail_now & ~ev_any_g[g_idx]

        offs = cr[:, None] + np.arange(TOP_K)[None, :]
        pk_idx = top_idx[g_idx[:, None], offs]
        pk_score = top_score[g_idx[:, None], offs]
        pk_ok = pk_score > NEG_INF / 2
        ok_row = pk_ok & cm
        if has_preempt:
            ecol = np.arange(TOP_K)[None, :] == 0
            pk_idx = np.where(ecm, np.where(ecol, e_cand[:, None], 0),
                              pk_idx).astype(np.int32)
            pk_score = np.where(
                ecm, np.where(ecol, win_s[g_idx][:, None], f32(NEG_INF)),
                pk_score)
            ok_row = np.where(ecm, ecol, ok_row)
        newly = commit | ev_commit | fail_now
        upd = newly[:, None]
        out_idx = np.where(upd, pk_idx, out_idx)
        out_score = np.where(upd, pk_score, out_score)
        out_ok = np.where(upd, ok_row, out_ok)
        if has_preempt:
            out_evict = np.where(upd, em & ecm, out_evict)
        out_wave = np.where(commit | ev_commit, wave, out_wave)
        out_nfeas = np.where(newly, n_feas_g[g_idx], out_nfeas)
        out_nexh = np.where(newly, n_exh_g[g_idx], out_nexh)
        out_dimexh = np.where(newly[:, None], dim_exh_g[g_idx],
                              out_dimexh)
        done = done | newly
        wave += 1

    unfinished = ~done & (ks < n_place)
    return SolveResult(
        choice=out_idx, choice_ok=out_ok, score=out_score,
        n_feasible=out_nfeas, n_exhausted=out_nexh,
        dim_exhausted=out_dimexh, feas=feas,
        cons_filtered=cons_filtered, used_final=used,
        dev_used_final=dev_used, n_waves=np.int32(wave),
        unfinished=unfinished, n_rescore=np.int32(wave),
        evict=out_evict,
        commit_wave=(out_wave if has_preempt else None))


class HostResidentSolver:
    """Host twin of resident.ResidentSolver for the interactive path:
    same pack-once / stream-asks surface and the same carried-usage
    semantics, but every solve runs the numpy kernel in-process — one
    singleton eval costs microseconds of arithmetic instead of a
    transport round trip.  Differential-tested batch-for-batch against
    the device stream (tests/test_host_solver.py)."""

    def __init__(self, nodes, probe_asks, allocs_by_node=None,
                 gp=None, kp=None, max_waves: int = 0,
                 stack_commit: bool = False, use_native: bool = True,
                 device_parity: bool = False):
        #: device_parity pins the wave-width hint to the device
        #: kernel's (compile-variant-floored) sizing so a stream solved
        #: here is BITWISE identical to the device stream.  The default
        #: sizes the window to the real per-group demand instead —
        #: ~2x faster per eval; placements remain a valid wave solve
        #: (the width is a scheduling parameter, like the reference's
        #: per-worker shuffled node order), just not bit-matched.
        self.device_parity = device_parity
        from .tensorize import Tensorizer
        self.nodes = list(nodes)
        self.max_waves = max_waves
        self.stack_commit = stack_commit
        self._tz = Tensorizer()
        self.template = self._tz.pack(nodes, probe_asks, allocs_by_node)
        self.gp = gp or self.template.ask_res.shape[0]
        self.kp = kp or self.template.p_ask.shape[0]
        self._drv_cache = {}
        self._row_cache = {}
        # program cache for _static_program: sound because the node
        # template is fixed for this solver's lifetime
        self._static_cache = {}
        # whole-eval PackedBatch cache (stateless asks only): repeated
        # evals with the same job shape — the steady-state service
        # workload — skip repack entirely
        self._eval_cache = {}
        # native (C++) wave kernel: bitwise-same placements as the
        # numpy twin (tests/test_native_solver.py), ~20x less per-eval
        # overhead — the production interactive path (solve_stream's
        # PreparedRun branch; the numpy kernel is the fallback)
        from . import native as native_mod
        self._native = use_native and native_mod.available()
        self._kernel = host_solve_kernel
        t = self.template
        if self._native:
            # carried usage lives in the prepared template's buffers so
            # the C kernel can update it in place (no per-call copies);
            # self._used ALIASES them for the whole solver lifetime
            self._tp = native_mod.PreparedTemplate(t)
            self._preps = {}
            self._used = self._tp.used
            self._dev_used = self._tp.dev_used
        else:
            self._used = np.array(t.used0, np.float32)
            self._dev_used = np.array(t.dev_used0, np.float32)

    def pack_batch(self, asks, job_keys=None):
        pb = self._tz.repack_asks(self.nodes, asks, self.template,
                                  gp=self.gp, kp=self.kp,
                                  drv_cache=self._drv_cache,
                                  row_cache=self._row_cache)
        if pb is not None:
            pb.job_keys = (job_keys if job_keys is not None else
                           {(a.job.namespace, a.job.id) for a in asks})
        return pb

    def pack_batch_cached(self, asks, job_keys=None):
        from .resident import pack_batch_cached
        return pack_batch_cached(self, asks, job_keys)

    def reset_usage(self, used0=None, dev_used0=None) -> None:
        t = self.template
        if self._native:
            self._tp.reset_usage(
                t.used0 if used0 is None else used0,
                t.dev_used0 if dev_used0 is None else dev_used0)
            return
        self._used = np.array(
            t.used0 if used0 is None else used0, np.float32)
        self._dev_used = np.array(
            t.dev_used0 if dev_used0 is None else dev_used0, np.float32)

    def usage(self):
        return self._used.copy(), self._dev_used.copy()

    @staticmethod
    def _host_hint(batches) -> int:
        """Wave-width hint for the in-process path.  The device hint
        floors at 64 purely to bound COMPILED variants; host solves
        have no compile, so the window tracks the real per-group
        demand — a 10-count group sorts ~36 candidates per wave, not
        132."""
        from .resident import ResidentSolver
        return ResidentSolver._group_count_hint(batches, floor=3)

    def solve_stream(self, batches, seeds=None):
        """Same contract as ResidentSolver.solve_stream: returns
        (choice [B, K, TOP_K], ok, score, status [B, K]); usage carries
        batch to batch and across calls."""
        # STATUS_* live in resident.py; import here to avoid a cycle
        from .resident import (STATUS_COMMITTED, STATUS_FAILED,
                               STATUS_RETRY, ResidentSolver)
        hint = (ResidentSolver._group_count_hint(batches)
                if self.device_parity else self._host_hint(batches))
        t = self.template
        B = len(batches)
        K = self.kp
        choice = np.zeros((B, K, TOP_K), np.int32)
        ok = np.zeros((B, K, TOP_K), bool)
        score = np.full((B, K, TOP_K), NEG_INF, np.float32)
        status = np.zeros((B, K), np.int32)
        has_spread = bool(any((pb.sp_col[:, 0] >= 0).any()
                              for pb in batches))
        for b, pb in enumerate(batches):
            seed = 0 if seeds is None else int(seeds[b])
            if self._native:
                # prepared-run fast path: args marshaled once per
                # batch, usage mutates in place in the tp buffers
                from . import native as native_mod
                pkey = (id(pb), hint, has_spread)
                ent = self._preps.get(pkey)
                if ent is None or ent[0] is not pb:
                    if len(self._preps) > 1024:
                        self._preps.clear()
                    pr = native_mod.PreparedRun(
                        self._tp, pb, has_spread, hint,
                        self.max_waves, self.stack_commit)
                    self._preps[pkey] = (pb, pr)
                else:
                    pr = ent[1]
                pr.run(seed)
                choice[b] = pr.out_idx
                score[b] = pr.out_score
                ok[b] = pr.out_score > NEG_INF / 2
                status[b] = np.where(
                    pr.out_ok[:, 0].astype(bool), STATUS_COMMITTED,
                    np.where(pr.out_unfin.astype(bool), STATUS_RETRY,
                             STATUS_FAILED))
                continue
            res = self._kernel(
                t.avail, t.reserved, self._used, t.valid, t.node_dc,
                t.attr_rank, pb.ask_res, pb.ask_desired, pb.distinct,
                pb.dc_ok, pb.host_ok, pb.coll0, pb.penalty, pb.c_op,
                pb.c_col, pb.c_rank, pb.a_op, pb.a_col, pb.a_rank,
                pb.a_weight, pb.a_host, pb.sp_col, pb.sp_weight,
                pb.sp_targeted, pb.sp_desired, pb.sp_implicit,
                pb.sp_used0, t.dev_cap, self._dev_used, pb.dev_ask,
                pb.p_ask, pb.n_place, seed, has_spread=has_spread,
                group_count_hint=hint, max_waves=self.max_waves,
                stack_commit=self.stack_commit,
                static_cache=self._static_cache)
            self._used = res.used_final
            self._dev_used = res.dev_used_final
            choice[b] = res.choice
            score[b] = res.score
            ok[b] = res.score > NEG_INF / 2
            status[b] = np.where(
                res.choice_ok[:, 0], STATUS_COMMITTED,
                np.where(res.unfinished, STATUS_RETRY, STATUS_FAILED))
        return choice, ok, score, status
