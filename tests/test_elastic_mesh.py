"""Elastic two-tier mesh (ISSUE 8).

Three layers of guarantees:

  * the TWO-TIER ("hosts", "chips") hierarchical candidate exchange —
    ICI merge per host, host-winner keys over DCN — must be
    bit-identical to the single-device host twin, placements AND every
    explainability counter, across pallas modes and shortlist on/off;
  * the ELASTIC tile remap (node axis owned in shard-tiles routed by
    an owner table) must be invisible to the solve: any
    reshard/fail/rejoin interleaving ends bit-identical to a
    from-scratch pack at the final topology;
  * the DCN-tier byte model must price the tiered exchange at <= 1/4
    of the flat single-tier exchange's cross-host bytes at 8 shards on
    4 hosts at config-3 scale (the acceptance figure), and a
    grow-by-one-tile reshard must ship only the moved tile's rows
    (measured, not modeled).

Runs on the conftest-forced 8-device virtual CPU mesh.
"""
import numpy as np
import pytest

import jax
from jax.sharding import Mesh, PartitionSpec as P

from nomad_tpu.parallel.sharded import (_ARG_SPECS,
                                        ElasticMeshSupervisor,
                                        ElasticShardedResidentSolver,
                                        ShardedResidentSolver,
                                        kernel_args, make_node_mesh,
                                        make_two_tier_mesh,
                                        mesh_node_axes,
                                        model_ici_bytes,
                                        model_ici_dcn_bytes)
from nomad_tpu.solver.host import host_solve_kernel
from nomad_tpu.solver.kernel import solve_kernel
from nomad_tpu.solver.resident import ResidentSolver
from nomad_tpu.solver.tensorize import (ClusterDelta, TileLayout,
                                        alloc_usage_vector,
                                        pick_tile_np)
from tests.test_sharded_resident import (assert_counters_identical,
                                         contended_problem, make_alloc,
                                         make_ask, make_node,
                                         spread_problem)

AX2 = ("hosts", "chips")


def _spec2(spec: P) -> P:
    """_ARG_SPECS entry with the "nodes" axis split over both tiers."""
    return P(*[AX2 if s == "nodes" else s for s in spec])


def mesh_solve_two_tier(args, n_hosts, n_chips, **kw):
    """solve_kernel under a ("hosts", "chips") shard_map — the node
    dimension splits over BOTH axes; the kernel merges candidates per
    host over ICI and only host winners cross the DCN tier."""
    mesh = Mesh(np.array(jax.devices()[:n_hosts * n_chips]).reshape(
        n_hosts, n_chips), AX2)
    in_specs = tuple(_spec2(s) for s in _ARG_SPECS)

    def body(*a):
        return solve_kernel(*a, mesh_axis=AX2,
                            mesh_shards=n_hosts * n_chips,
                            mesh_hosts=n_hosts, **kw)

    shape = jax.eval_shape(lambda *a: solve_kernel(*a, **kw), *args)
    out_specs = jax.tree_util.tree_map(lambda _: P(), shape)
    out_specs = out_specs._replace(feas=P(None, AX2),
                                   used_final=P(AX2, None),
                                   dev_used_final=P(AX2, None))
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False))
    return f(*args)


# ------------------------------------------------------------------
# two-tier hierarchical exchange: bit-identical to the host twin
# ------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["off", "score", "topk"])
@pytest.mark.parametrize("shortlist_c", [-1, 0])
def test_two_tier_kernel_contended_matches_host(mode, shortlist_c):
    pb = contended_problem()
    args = kernel_args(pb)
    host = host_solve_kernel(*args)
    res = mesh_solve_two_tier(args, 4, 2, pallas_mode=mode,
                              shortlist_c=shortlist_c)
    assert_counters_identical(res, host)


@pytest.mark.parametrize("grid", [(2, 4), (4, 2), (8, 1), (1, 8),
                                  (2, 2)])
def test_two_tier_equivalent_across_host_groupings(grid):
    """The SAME problem must place identically no matter how the eight
    shards group into hosts — the tiered merge is order-exact."""
    pb = contended_problem()
    args = kernel_args(pb)
    host = host_solve_kernel(*args)
    res = mesh_solve_two_tier(args, *grid)
    assert_counters_identical(res, host)


@pytest.mark.parametrize("mode", ["off", "score"])
def test_two_tier_spread_interleave_matches_host(mode):
    pb = spread_problem()
    args = kernel_args(pb)
    host = host_solve_kernel(*args)
    res = mesh_solve_two_tier(args, 4, 2, pallas_mode=mode)
    assert_counters_identical(res, host)


def test_two_tier_seeded_jitter_matches_flat_mesh():
    """Seeded tie-break jitter hashes GLOBAL node ids, so the two-tier
    grouping must not move a single placement vs the flat mesh."""
    from tests.test_sharded_resident import mesh_solve
    pb = contended_problem()
    args = kernel_args(pb)
    flat = mesh_solve(args, 8, seed=11)
    two = mesh_solve_two_tier(args, 4, 2, seed=11)
    assert_counters_identical(two, flat)


# ------------------------------------------------------------------
# elastic tile remap at the kernel level: scrambled ownership is
# invisible — counters included
# ------------------------------------------------------------------
def _elastic_kernel_args(args, layout: TileLayout):
    """Permute every node-axis operand of `args` into the tile
    device layout (dead slack rows get their pad fill) and build the
    kernel's gid/owner/slot tables."""
    NT = args[0].shape[0]
    src = layout.dev_src()
    take = np.clip(src, 0, NT - 1)
    dead = src < 0
    fills = {3: False, 5: -1}            # valid, attr_rank
    out = []
    for i, (a, spec) in enumerate(zip(args, _ARG_SPECS)):
        parts = list(spec)
        if "nodes" not in parts:
            out.append(a)
            continue
        ax = parts.index("nodes")
        if ax == 0:
            b = np.ascontiguousarray(np.asarray(a)[take])
            b[dead] = fills.get(i, 0)
        else:
            b = np.ascontiguousarray(np.asarray(a)[..., take])
            b[..., dead] = fills.get(i, 0)
        out.append(b)
    gid = layout.node_gid(NT)
    om, sm = layout.tables()
    return tuple(out), gid, om, sm, src


def _scrambled_layout(NT, n_shards, moves=3, seed=5):
    tile = pick_tile_np(NT, n_shards)
    lay = TileLayout(NT // tile, n_shards, tile)
    rng = np.random.default_rng(seed)
    for _ in range(moves):
        t = int(rng.integers(lay.n_tiles))
        dsts = [s for s in range(n_shards)
                if s != lay.owner[t] and lay.free_slots(s) > 0]
        if not dsts:
            continue
        lay.release(t)
        lay.assign(t, dsts[int(rng.integers(len(dsts)))])
    return lay


@pytest.mark.parametrize("mode", ["off", "score", "topk"])
@pytest.mark.parametrize("two_tier", [False, True])
def test_elastic_remap_kernel_matches_host(mode, two_tier):
    """solve_kernel with tile_np + a SCRAMBLED owner table (tiles
    moved off the contiguous block layout) must match the host twin
    bit-for-bit — candidate keys carry stable global ids and both the
    extraction and the merge order by (score desc, gid asc), so where
    a tile physically lives cannot matter.  (Under the remap the fused
    'topk' extraction falls back to the exact gid-ordered lex sort —
    the mode still exercises the fused scoring pass.)"""
    pb = contended_problem()
    args = kernel_args(pb)
    host = host_solve_kernel(*args)
    n_shards = 8
    lay = _scrambled_layout(args[0].shape[0], n_shards)
    ek_args, gid, om, sm, src = _elastic_kernel_args(args, lay)
    NT = args[0].shape[0]
    axes = AX2 if two_tier else "nodes"
    mesh = (Mesh(np.array(jax.devices()[:8]).reshape(4, 2), AX2)
            if two_tier else
            Mesh(np.array(jax.devices()[:8]), ("nodes",)))
    in_specs = tuple((_spec2(s) if two_tier else s)
                     for s in _ARG_SPECS)
    gid_spec = P(AX2) if two_tier else P("nodes")

    def body(*a):
        return solve_kernel(*a[:-3], mesh_axis=axes, mesh_shards=8,
                            mesh_hosts=4 if two_tier else 0,
                            mesh_nt=NT, tile_np=lay.tile_np,
                            node_gid=a[-3], owner_map=a[-2],
                            slot_map=a[-1], pallas_mode=mode)

    shape = jax.eval_shape(
        lambda *a: solve_kernel(*a, pallas_mode=mode), *args)
    out_specs = jax.tree_util.tree_map(lambda _: P(), shape)
    nspec = AX2 if two_tier else "nodes"
    out_specs = out_specs._replace(feas=P(None, nspec),
                                   used_final=P(nspec, None),
                                   dev_used_final=P(nspec, None))
    f = jax.jit(jax.shard_map(body, mesh=mesh,
                          in_specs=in_specs + (gid_spec, P(), P()),
                          out_specs=out_specs, check_vma=False))
    res = f(*ek_args, gid, om, sm)

    # scalar/per-ask outputs compare directly; plane outputs compare
    # through the device-layout permutation
    ok = np.asarray(res.choice_ok)
    np.testing.assert_array_equal(ok, host.choice_ok)
    np.testing.assert_array_equal(
        np.where(ok, np.asarray(res.choice), -1),
        np.where(host.choice_ok, host.choice, -1))
    np.testing.assert_array_equal(
        np.where(ok, np.asarray(res.score), 0.0),
        np.where(host.choice_ok, host.score, 0.0))
    np.testing.assert_array_equal(np.asarray(res.unfinished),
                                  host.unfinished)
    np.testing.assert_array_equal(np.asarray(res.n_feasible),
                                  host.n_feasible)
    np.testing.assert_array_equal(np.asarray(res.n_exhausted),
                                  host.n_exhausted)
    np.testing.assert_array_equal(np.asarray(res.dim_exhausted),
                                  host.dim_exhausted)
    np.testing.assert_array_equal(np.asarray(res.cons_filtered),
                                  host.cons_filtered)
    live = src >= 0
    np.testing.assert_array_equal(
        np.asarray(res.feas)[:, live][:, np.argsort(src[live])],
        host.feas)
    np.testing.assert_array_equal(
        np.asarray(res.used_final)[live][np.argsort(src[live])],
        host.used_final)


# ------------------------------------------------------------------
# solver level: reshard/fail/rejoin interleavings vs from-scratch
# ------------------------------------------------------------------
def _mirror_used(solver, live):
    used = np.zeros_like(solver.template.used0)
    for aid, (nid, alloc) in live.items():
        i = solver.node_index.get(nid)
        if i is not None:
            used[i] += alloc_usage_vector(alloc)
    return used


def _solve_ids(solver, pb):
    choice, ok, score, status = solver.solve_stream([pb])
    n = pb.n_place
    ids = [solver.template.node_ids[int(choice[0, p, 0])]
           if ok[0, p, 0] else None for p in range(n)]
    return ids, score[0, :n, 0].copy(), status[0, :n].copy()


def _lost_node_ids(es):
    out = set()
    tile = es.tile_np
    for t in es._lost_tiles:
        for i in range(t * tile, (t + 1) * tile):
            if i < len(es.template.node_ids) and es.template.valid[i]:
                out.add(es.template.node_ids[i])
    return out


@pytest.mark.parametrize("pallas", ["off", "score", "topk"])
@pytest.mark.parametrize("shortlist_c", [-1, 0])
@pytest.mark.parametrize("seed", [3, 11, 29])
def test_random_reshard_fail_rejoin_matches_from_scratch(
        pallas, shortlist_c, seed):
    """THE ISSUE-8 property test: random grow/shrink/kill/rejoin/move
    reshard ops interleaved with place/stop/drain/join deltas must
    leave the elastic mesh bit-identical — placements, scores,
    statuses, and carried usage by node id — to a FROM-SCRATCH pack at
    whatever topology each round reaches.  During a degraded round the
    reference is a from-scratch pack of the SURVIVING nodes (the lost
    tiles' nodes are out of the solve but the survivors never leave
    the device fast path)."""
    rng = np.random.default_rng(seed)
    probe = [make_ask(spread=True), make_ask()]
    nodes = [make_node(i) for i in range(24)]
    es = ElasticShardedResidentSolver(
        nodes, probe, gp=4, kp=16, pallas=pallas,
        shortlist_c=shortlist_c,
        mesh=make_two_tier_mesh(4, 8))

    live = {}
    cluster = {n.id: n for n in nodes}
    join_seq = [n.id for n in nodes]
    next_i = len(nodes)

    for round_ in range(6):
        # ---- one random delta ----
        delta = ClusterDelta()
        for _ in range(int(rng.integers(1, 4))):
            op = rng.choice(["place", "stop", "drain", "join"])
            if op == "place" and join_seq:
                nid = join_seq[int(rng.integers(len(join_seq)))]
                a = make_alloc(cpu=int(rng.integers(100, 400)))
                delta.place.append((nid, a))
                live[a.id] = (nid, a)
            elif op == "stop" and live:
                aid = list(live)[int(rng.integers(len(live)))]
                nid, a = live.pop(aid)
                delta.stop.append((nid, a))
            elif op == "drain" and len(join_seq) > 8:
                nid = join_seq.pop(int(rng.integers(len(join_seq))))
                cluster.pop(nid)
                delta.remove_node_ids.append(nid)
                for aid in [aid for aid, (n2, _) in live.items()
                            if n2 == nid]:
                    del live[aid]
            elif op == "join":
                n = make_node(next_i)
                next_i += 1
                delta.upsert_nodes.append(n)
                cluster[n.id] = n
                join_seq.append(n.id)
        es.apply_delta(delta)

        # ---- one random reshard op ----
        rop = rng.choice(["none", "grow", "shrink", "move", "kill",
                          "rejoin"])
        if rop == "grow" and es.mesh_state == "healthy":
            try:
                es.grow_tiles(1)
            except ValueError:
                pass                      # slack exhausted: fine
        elif rop == "shrink":
            es.shrink_tiles(1)
        elif rop == "move":
            lay = es._layout
            owned = [t for t in range(lay.n_tiles)
                     if lay.owner[t] >= 0]
            if owned:
                t = owned[int(rng.integers(len(owned)))]
                dsts = [s for s in range(lay.n_shards)
                        if s != lay.owner[t] and lay.free_slots(s) > 0]
                if dsts:
                    es.move_tile(t, dsts[int(rng.integers(len(dsts)))])
        elif rop == "kill" and es.mesh_state == "healthy":
            es.fail_shard(int(rng.integers(es.n_shards)))
        elif rop == "rejoin" and es.mesh_state == "degraded":
            es.recover()

        # ---- compare vs a from-scratch pack at this topology ----
        lost_ids = _lost_node_ids(es)
        cur_ids = [nid for nid in join_seq if nid not in lost_ids]
        cur_nodes = [cluster[nid] for nid in cur_ids]
        ref = ResidentSolver(cur_nodes, probe, gp=4, kp=16,
                             pallas=pallas, shortlist_c=shortlist_c)
        vis_live = {aid: (nid, a) for aid, (nid, a) in live.items()
                    if nid not in lost_ids}
        es.reset_usage(used0=_mirror_used(es, live))
        ref.reset_usage(used0=_mirror_used(ref, vis_live))

        asks = [make_ask(count=3, cpu=int(300 + 100 * (round_ % 3)),
                         spread=bool(round_ % 2))]
        pb_e = es.pack_batch(asks)
        pb_r = ref.pack_batch(asks)
        assert pb_e is not None and pb_r is not None
        ids_e, sc_e, st_e = _solve_ids(es, pb_e)
        ids_r, sc_r, st_r = _solve_ids(ref, pb_r)
        assert ids_e == ids_r, (
            f"seed {seed} round {round_} ({rop}): placements diverged")
        np.testing.assert_array_equal(st_e, st_r)
        np.testing.assert_array_equal(sc_e, sc_r)
        # carried usage stays in lockstep by node id
        u_e, _ = es.usage()
        by_id_e = {es.template.node_ids[i]: u_e[i]
                   for i in range(len(es.template.node_ids))
                   if es.template.valid[i]}
        u_r, _ = ref.usage()
        for i, nid in enumerate(ref.template.node_ids):
            if ref.template.valid[i]:
                np.testing.assert_array_equal(
                    by_id_e[nid], u_r[i],
                    err_msg=f"round {round_} usage for {nid}")
    # end in a recovered state at least once per seed
    if es.mesh_state == "degraded":
        es.recover()
        assert es.mesh_state == "healthy"


# ------------------------------------------------------------------
# measured reshard bytes + recovery fast path
# ------------------------------------------------------------------
def test_grow_ships_only_the_new_tile():
    """Acceptance: a grow-by-one-tile reshard ships ONLY the moved
    tile's plane rows (measured through the scatter payloads) — orders
    of magnitude under the full node-side re-put."""
    nodes = [make_node(i) for i in range(40)]
    probe = [make_ask()]
    es = ElasticShardedResidentSolver(nodes, probe, gp=4, kp=16,
                                      mesh=make_two_tier_mesh(4, 8))
    full_bytes = (es.template.avail.nbytes + es.template.reserved.nbytes
                  + es.template.valid.nbytes + es.template.node_dc.nbytes
                  + es.template.attr_rank.nbytes
                  + es.template.dev_cap.nbytes + es.template.used0.nbytes
                  + es.template.dev_used0.nbytes)
    es.grow_tiles(1)
    grew = es.reshard_counters["last_reshard_bytes"]
    assert 0 < grew < full_bytes / 4, (grew, full_bytes)
    # the shipped payload is tile-sized: planes + usage + tables
    tile_frac = es.tile_np / es.template.avail.shape[0]
    assert grew <= full_bytes * tile_frac + 4096

    # a move ships the same order of bytes, not the world
    lay = es._layout
    t = next(t for t in range(lay.n_tiles) if lay.owner[t] >= 0)
    dst = next(s for s in range(lay.n_shards)
               if s != lay.owner[t] and lay.free_slots(s) > 0)
    moved = es.move_tile(t, dst)
    assert 0 < moved < full_bytes / 4


def test_kill_recover_stays_on_device_fast_path():
    """A killed shard recovers and rejoins while the surviving shards
    never leave the device fast path: degraded solves still run
    through the sharded stream kernel (counted), placements during
    degradation match a fresh pack of the survivors, and recovery
    restores full-width placements."""
    nodes = [make_node(i) for i in range(40)]
    probe = [make_ask()]
    es = ElasticShardedResidentSolver(nodes, probe, gp=4, kp=16,
                                      mesh=make_two_tier_mesh(4, 8))
    ref_full = ResidentSolver(nodes, probe, gp=4, kp=16)
    asks = [make_ask(count=4, cpu=300)]
    pb = es.pack_batch(asks)
    ids0, _, _ = _solve_ids(es, pb)
    es.reset_usage()
    lost = es.fail_shard(2)
    assert lost and es.mesh_state == "degraded"
    lost_ids = _lost_node_ids(es)
    assert lost_ids, "the failed shard owned live nodes"
    survivors = [n for n in nodes if n.id not in lost_ids]
    ref_deg = ResidentSolver(survivors, probe, gp=4, kp=16)
    ids_d, _, _ = _solve_ids(es, es.pack_batch(asks))
    ids_r, _, _ = _solve_ids(ref_deg, ref_deg.pack_batch(asks))
    assert ids_d == ids_r, "degraded solve != fresh pack of survivors"
    assert not (set(i for i in ids_d if i) & lost_ids)
    assert es.reshard_counters["degraded_solves"] == 1
    es.reset_usage()
    rec = es.recover()
    assert rec > 0 and es.mesh_state == "healthy"
    assert es.reshard_counters["recoveries"] == 1
    assert es.reshard_counters["last_recovery_s"] > 0
    ids1, _, _ = _solve_ids(es, es.pack_batch(asks))
    ids_f, _, _ = _solve_ids(ref_full, ref_full.pack_batch(asks))
    assert ids1 == ids_f, "post-recovery solve != full fresh pack"


# ------------------------------------------------------------------
# DCN-tier byte model: the acceptance bound
# ------------------------------------------------------------------
def test_dcn_byte_model_quarter_of_flat_at_config3_scale():
    """Acceptance: modeled cross-host (DCN-tier) bytes/wave of the
    hierarchical exchange <= 1/4 of the flat single-tier exchange's
    cross-host bytes at 8 shards on 4 hosts at config-3 scale
    (G=64 groups, K=512 asks, spread tables on)."""
    m = model_ici_dcn_bytes(Gp=64, K=512, A=24, R=6, TK=132, TKl=132,
                            n_shards=8, n_hosts=4, want_tables=True,
                            V=8, TKv=132, TW=132, has_spread=True)
    assert m["dcn_cut_vs_flat"] <= 0.25, m
    assert m["bytes_dcn_total_per_wave"] > 0
    assert m["flat_dcn_total_per_wave"] > m["bytes_dcn_total_per_wave"]


def test_dcn_byte_model_scales_with_hosts():
    """More chips per host -> deeper ICI reduction -> bigger DCN cut;
    one host -> no DCN bytes at all; the model is pure."""
    kw = dict(Gp=32, K=128, A=16, R=6, TK=132, TKl=132,
              want_tables=False, V=0, TKv=0, TW=0, has_spread=False)
    one = model_ici_dcn_bytes(n_shards=8, n_hosts=1, **kw)
    assert one["bytes_dcn_total_per_wave"] == 0
    two = model_ici_dcn_bytes(n_shards=8, n_hosts=2, **kw)
    four = model_ici_dcn_bytes(n_shards=8, n_hosts=4, **kw)
    assert two["dcn_cut_vs_flat"] <= four["dcn_cut_vs_flat"] * 1.5
    a = model_ici_dcn_bytes(n_shards=8, n_hosts=4, **kw)
    b = model_ici_dcn_bytes(n_shards=8, n_hosts=4, **kw)
    assert a == b


def test_wave_traffic_reports_dcn_tier():
    """ShardedResidentSolver.wave_traffic grows the dcn block on a
    two-tier mesh (and the elastic solver always carries it)."""
    nodes = [make_node(i) for i in range(40)]
    probe = [make_ask()]
    rs = ShardedResidentSolver(nodes, probe, gp=4, kp=16,
                               mesh=make_two_tier_mesh(4, 8))
    pb = rs.pack_batch([make_ask(count=4)])
    rs.solve_stream([pb])
    wt = rs.wave_traffic([pb])
    assert wt["dcn"]["n_hosts"] == 4
    assert wt["bytes_dcn_per_wave"] == \
        wt["dcn"]["bytes_dcn_total_per_wave"]
    assert wt["measured"]["modeled_bytes_dcn_total"] > 0
    assert wt["measured"]["modeled_bytes_dcn_flat_total"] >= \
        wt["measured"]["modeled_bytes_dcn_total"]
    # flat mesh: no dcn block
    rs_flat = ShardedResidentSolver(nodes, probe, gp=4, kp=16,
                                    mesh=make_node_mesh(8))
    pb2 = rs_flat.pack_batch([make_ask(count=4)])
    assert "dcn" not in rs_flat.wave_traffic([pb2])


# ------------------------------------------------------------------
# recovery trigger: serf-plane and scheduler-plane events
# ------------------------------------------------------------------
def test_supervisor_gossip_and_node_event_triggers():
    nodes = [make_node(i) for i in range(24)]
    probe = [make_ask()]
    es = ElasticShardedResidentSolver(nodes, probe, gp=4, kp=16,
                                      mesh=make_two_tier_mesh(4, 8))
    sup = ElasticMeshSupervisor(es)
    sup.register_host("host-a", 1)

    class FakeMember:
        def __init__(self, mid):
            self.id = mid

    sup.on_fail(FakeMember("host-unknown"))      # unregistered: no-op
    assert es.mesh_state == "healthy"
    sup.on_fail(FakeMember("host-a"))
    assert es.mesh_state == "degraded"
    sup.on_fail(FakeMember("host-a"))            # idempotent
    assert es.mesh_state == "degraded"
    sup.on_join(FakeMember("host-a"))
    assert es.mesh_state == "healthy"
    assert sup.events == [("fail", "host-a"), ("recover", "host-a")]
    # scheduler-plane spelling
    from nomad_tpu.structs.consts import (NODE_STATUS_DOWN,
                                          NODE_STATUS_READY)
    sup.register_host("node-7", 0)
    sup.note_node_event("node-7", NODE_STATUS_DOWN)
    assert es.mesh_state == "degraded"
    sup.note_node_event("node-7", NODE_STATUS_READY)
    assert es.mesh_state == "healthy"


def test_supervisor_callbacks_fit_gossip_agent():
    """The supervisor's callbacks plug straight into GossipAgent's
    on_fail/on_join slots (construction only — no network)."""
    from nomad_tpu.membership.gossip import GossipAgent, Member

    class _R:
        def register(self, *_a, **_k):
            pass

    nodes = [make_node(i) for i in range(24)]
    es = ElasticShardedResidentSolver(nodes, [make_ask()], gp=4, kp=16,
                                      mesh=make_two_tier_mesh(4, 8))
    sup = ElasticMeshSupervisor(es)
    sup.register_host("m1", 0)
    agent = GossipAgent(
        Member(id="me", region="global", addr=("127.0.0.1", 0)),
        _R(), on_join=sup.on_join, on_fail=sup.on_fail)
    agent.on_fail(Member(id="m1", region="global",
                         addr=("127.0.0.1", 1)))
    assert es.mesh_state == "degraded"
    agent.on_join(Member(id="m1", region="global",
                         addr=("127.0.0.1", 1)))
    assert es.mesh_state == "healthy"


def test_worker_node_update_eval_feeds_mesh_supervisor():
    """Scheduler-plane wiring: a node-update eval flowing through the
    worker forwards the observed node status to the attached mesh
    supervisor BEFORE the solve (the recovery trigger off node
    events)."""
    from nomad_tpu import mock
    from nomad_tpu.server.server import Server
    from nomad_tpu.server.worker import Worker
    from nomad_tpu.structs import NODE_STATUS_DOWN

    server = Server(num_workers=0)
    server.start()
    try:
        node = mock.node()
        server.register_node(node)
        job = mock.job()
        job.task_groups[0].count = 1
        server.register_job(job)
        w = Worker(server, ["service"])
        batch = server.broker.dequeue_batch(["service"], 8, 1.0)
        for ev, token in batch:
            w._process(ev, token)
        events = []

        class _Rec:
            def note_node_event(self, nid, status):
                events.append((nid, status))

        w.mesh_supervisor = _Rec()
        server.update_node_status(node.id, NODE_STATUS_DOWN)
        batch = server.broker.dequeue_batch(["service"], 8, 1.0)
        assert batch, "node-down must create a node-update eval"
        for ev, token in batch:
            w._process(ev, token)
        assert (node.id, NODE_STATUS_DOWN) in events
    finally:
        server.stop()


def test_repack_fallback_while_degraded_recovers_first():
    """A repack-triggering delta (past the delta threshold) landing
    while the mesh is DEGRADED must first recover — the rebuilt world
    is full-width, the state machine is consistent, and the lost
    tiles' plan-fed usage survives (a straight repack would fold their
    zeroed device rows into used0)."""
    nodes = [make_node(i) for i in range(24)]
    probe = [make_ask()]
    es = ElasticShardedResidentSolver(nodes, probe, gp=4, kp=16,
                                      mesh=make_two_tier_mesh(4, 8),
                                      delta_threshold=0.25)
    ss = ResidentSolver(nodes, probe, gp=4, kp=16,
                        delta_threshold=0.25)
    # pin usage on a node the failed shard owns
    lost_preview = es._layout.tiles_of(2)
    tile = es.tile_np
    pinned_row = lost_preview[0] * tile
    pinned_id = es.template.node_ids[pinned_row]
    a = make_alloc(cpu=333)
    d0 = ClusterDelta()
    d0.place.append((pinned_id, a))
    assert es.apply_delta(d0) == "delta"
    assert ss.apply_delta(d0) == "delta"
    es.fail_shard(2)
    assert es.mesh_state == "degraded"
    # a wide delta: touches > threshold of the real slots -> repack
    import copy
    d1 = ClusterDelta()
    for i in range(12, 24):
        n2 = copy.copy(nodes[i])
        n2.node_resources = copy.deepcopy(n2.node_resources)
        n2.node_resources.cpu += 500
        d1.upsert_nodes.append(n2)
    assert es.apply_delta(d1) == "repack"
    assert ss.apply_delta(d1) == "repack"
    assert es.mesh_state == "healthy"
    assert es.reshard_counters["recoveries"] == 1
    # the pinned alloc's usage survived the degraded repack
    u_e, _ = es.usage()
    u_s, _ = ss.usage()
    i_e = es.node_index[pinned_id]
    i_s = ss.node_index[pinned_id]
    np.testing.assert_array_equal(u_e[i_e], u_s[i_s])
    assert u_e[i_e].any()
    # and the rebuilt mesh solves in lockstep with the single-device
    # reference
    asks = [make_ask(count=3, cpu=300)]
    pb_e = es.pack_batch(asks)
    pb_s = ss.pack_batch(asks)
    ids_e, sc_e, st_e = _solve_ids(es, pb_e)
    ids_s, sc_s, st_s = _solve_ids(ss, pb_s)
    assert ids_e == ids_s
    np.testing.assert_array_equal(st_e, st_s)
