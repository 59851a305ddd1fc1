"""Three-tier WAN federation (ISSUE 13).

Four layers of guarantees:

  * the THREE-TIER ("regions", "hosts", "chips") hierarchical
    candidate exchange — ICI merge per host, host winners over DCN,
    region winners over WAN — must be bit-identical to the
    single-device host twin, placements AND every explainability
    counter, across pallas modes, shortlist on/off, grid shapes, and
    seeded jitter;
  * CrossRegionResidentSolver (cross-region SCHEDULING over the union
    fleet) must match a flat single-mesh ResidentSolver oracle at the
    stream level — including carried usage and a region-degraded
    (shard-loss) round against a from-scratch pack of the survivors;
  * FederatedResidentSolver accepts RAGGED region universes (pad to
    the max padded node axis with dead rows) and stays bit-identical
    to the regions' independent solvers, while non-paddable universe
    mismatches fail loudly naming the offending region; the federated
    stream jit must not recompile across same-shape steps;
  * the WAN admission tier: SpilloverRouter routes to the cheapest
    region meeting SLO, overflows to a sibling when the home brownout
    watermark trips, parks in the shed lane (never drops) only when
    every region is browned out, and serf WAN-gossip join/leave
    events drive the federation membership table.

Runs on the conftest-forced 8-device virtual CPU mesh.
"""
import numpy as np
import pytest

import jax
from jax.sharding import Mesh, PartitionSpec as P

from nomad_tpu.parallel.federated import (CrossRegionResidentSolver,
                                          FederatedResidentSolver,
                                          RegionDirectory)
from nomad_tpu.parallel.sharded import (_ARG_SPECS,
                                        ElasticShardedResidentSolver,
                                        ShardedResidentSolver,
                                        kernel_args,
                                        make_three_tier_mesh,
                                        mesh_region_count,
                                        model_ici_dcn_wan_bytes)
from nomad_tpu.server.serving import SpilloverRouter
from nomad_tpu.solver.host import host_solve_kernel
from nomad_tpu.solver.kernel import solve_kernel
from nomad_tpu.solver.resident import ResidentSolver
from nomad_tpu.utils.tracing import MeshEventLog
from tests.test_elastic_mesh import _lost_node_ids, _solve_ids
from tests.test_sharded_resident import (assert_counters_identical,
                                         contended_problem, make_ask,
                                         make_node, spread_problem)

AX3 = ("regions", "hosts", "chips")


def _spec3(spec: P) -> P:
    """_ARG_SPECS entry with the "nodes" axis split over all tiers."""
    return P(*[AX3 if s == "nodes" else s for s in spec])


def mesh_solve_three_tier(args, n_regions, n_hosts, n_chips, **kw):
    """solve_kernel under a ("regions", "hosts", "chips") shard_map —
    the node dimension splits over ALL THREE axes; candidates merge
    per host over ICI, host winners per region over DCN, and only
    region winners cross the WAN tier."""
    n = n_regions * n_hosts * n_chips
    mesh = Mesh(np.array(jax.devices()[:n]).reshape(
        n_regions, n_hosts, n_chips), AX3)
    in_specs = tuple(_spec3(s) for s in _ARG_SPECS)

    def body(*a):
        return solve_kernel(*a, mesh_axis=AX3, mesh_shards=n,
                            mesh_hosts=n_hosts,
                            mesh_regions=n_regions, **kw)

    shape = jax.eval_shape(lambda *a: solve_kernel(*a, **kw), *args)
    out_specs = jax.tree_util.tree_map(lambda _: P(), shape)
    out_specs = out_specs._replace(feas=P(None, AX3),
                                   used_final=P(AX3, None),
                                   dev_used_final=P(AX3, None))
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False))
    return f(*args)


# ------------------------------------------------------------------
# three-tier hierarchical exchange: bit-identical to the host twin
# ------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["off", "score", "topk"])
@pytest.mark.parametrize("shortlist_c", [-1, 0])
def test_three_tier_kernel_contended_matches_host(mode, shortlist_c):
    pb = contended_problem()
    args = kernel_args(pb)
    host = host_solve_kernel(*args)
    res = mesh_solve_three_tier(args, 2, 2, 2, pallas_mode=mode,
                                shortlist_c=shortlist_c)
    assert_counters_identical(res, host)


@pytest.mark.parametrize("grid", [(2, 2, 2), (4, 1, 2), (4, 2, 1),
                                  (2, 1, 4), (8, 1, 1), (1, 4, 2)])
def test_three_tier_equivalent_across_region_groupings(grid):
    """The SAME problem must place identically no matter how the eight
    shards factor into regions x hosts x chips — the WAN merge keeps
    the (score desc, id asc) lex order exact, and the degenerate
    grids collapse onto the two-tier/flat paths."""
    pb = contended_problem()
    args = kernel_args(pb)
    host = host_solve_kernel(*args)
    res = mesh_solve_three_tier(args, *grid)
    assert_counters_identical(res, host)


@pytest.mark.parametrize("mode", ["off", "score"])
def test_three_tier_spread_interleave_matches_host(mode):
    pb = spread_problem()
    args = kernel_args(pb)
    host = host_solve_kernel(*args)
    res = mesh_solve_three_tier(args, 2, 2, 2, pallas_mode=mode)
    assert_counters_identical(res, host)


def test_three_tier_seeded_jitter_matches_flat_mesh():
    """Seeded tie-break jitter hashes GLOBAL node ids, so the region
    grouping must not move a single placement vs the flat mesh."""
    from tests.test_sharded_resident import mesh_solve
    pb = contended_problem()
    args = kernel_args(pb)
    flat = mesh_solve(args, 8, seed=11)
    three = mesh_solve_three_tier(args, 2, 2, 2, seed=11)
    assert_counters_identical(three, flat)


# ------------------------------------------------------------------
# resident stream + wave_traffic wan block + elastic round trip
# ------------------------------------------------------------------
def test_three_tier_resident_stream_matches_flat():
    nodes = [make_node(i) for i in range(40)]
    probe = [make_ask()]
    ref = ResidentSolver(nodes, probe, gp=4, kp=16)
    rs = ShardedResidentSolver(nodes, probe, gp=4, kp=16,
                               mesh=make_three_tier_mesh(2, 2, 8))
    assert rs.n_regions == 2 and rs.n_hosts == 2
    assert rs.chips_per_host == 2 and rs.three_tier
    assert mesh_region_count(rs._mesh) == 2
    pb_r = ref.pack_batch([make_ask(count=4)])
    pb_s = rs.pack_batch([make_ask(count=4)])
    o_r = ref.solve_stream([pb_r])
    o_s = rs.solve_stream([pb_s])
    for a, b in zip(o_r, o_s):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_wave_traffic_reports_wan_tier():
    """The wan block carries the three-entry byte model with measured
    wave/rescore counters — no null fields (the bench acceptance
    record is built from exactly these keys)."""
    nodes = [make_node(i) for i in range(40)]
    rs = ShardedResidentSolver(nodes, [make_ask()], gp=4, kp=16,
                               mesh=make_three_tier_mesh(2, 2, 8))
    pb = rs.pack_batch([make_ask(count=4)])
    rs.solve_stream([pb])
    wt = rs.wave_traffic([pb])
    wan = wt["wan"]
    assert wan["n_regions"] == 2
    assert wan["shards_per_region"] == 4
    assert wt["dcn"]["n_hosts"] == 2          # hosts PER REGION
    assert wt["bytes_wan_per_wave"] == wan["bytes_wan_total_per_wave"]
    assert all(v is not None for v in wan.values())
    assert wan["bytes_wan_total_per_wave"] == (
        wan["bytes_wan_window_per_wave"]
        + wan["bytes_wan_commit_per_wave"])
    m = wt["measured"]
    assert m["waves_total"] > 0
    assert m["modeled_bytes_wan_total"] == (
        wan["bytes_wan_total_per_wave"] * m["waves_total"])
    assert m["modeled_bytes_wan_flat_total"] >= (
        m["modeled_bytes_wan_total"])


def test_model_wan_bytes_pure():
    """Byte model purity (no device work) + the acceptance shape: at
    config-3 scale (TKl saturated at TK) four regions cut WAN bytes
    to <= 1/4 of the flat all-to-all exchange."""
    kw = dict(Gp=32, K=128, A=16, R=6, TK=132, TKl=132, n_shards=8,
              n_regions=4, n_hosts=1, want_tables=False, V=1, TKv=0,
              TW=0, has_spread=False)
    out = model_ici_dcn_wan_bytes(**kw)
    assert out["n_regions"] == 4 and out["shards_per_region"] == 2
    assert out["tk_region"] == min(132, 132 * 2)
    # ONE commit vector crosses WAN per region, not one per host
    assert out["bytes_wan_commit_per_wave"] < (
        out["flat_wan_total_per_wave"] - out["flat_wan_window_per_wave"])
    assert out["wan_cut_vs_flat"] <= 0.25
    assert out["bytes_wan_total_per_wave"] < (
        out["flat_wan_total_per_wave"])
    # toy scale (Npl < TK): tk_region widens to TKl * SPR — the cut
    # degrades gracefully instead of lying
    toy = model_ici_dcn_wan_bytes(**{**kw, "TK": 132, "TKl": 16})
    assert toy["tk_region"] == 32
    assert toy["wan_cut_vs_flat"] > out["wan_cut_vs_flat"]


def test_elastic_three_tier_fail_recover_roundtrip():
    """fail_shard rebinds survivors onto a flat mesh; recover restores
    the ORIGINAL three-tier topology (regions/hosts intact)."""
    nodes = [make_node(i) for i in range(40)]
    probe = [make_ask()]
    ref = ResidentSolver(nodes, probe, gp=4, kp=16)
    es = ElasticShardedResidentSolver(nodes, probe, gp=4, kp=16,
                                      mesh=make_three_tier_mesh(2, 2, 8))
    o_r = ref.solve_stream([ref.pack_batch([make_ask(count=4)])])
    o_e = es.solve_stream([es.pack_batch([make_ask(count=4)])])
    np.testing.assert_array_equal(np.asarray(o_r[0]),
                                  np.asarray(o_e[0]))
    es.fail_shard(3)
    assert es.mesh_state == "degraded"
    es.solve_stream([es.pack_batch([make_ask(count=2)])])
    es.recover()
    assert es.mesh_state == "healthy"
    assert es.n_regions == 2 and es.three_tier


# ------------------------------------------------------------------
# THE ISSUE-13 property test: cross-region scheduling == flat oracle
# ------------------------------------------------------------------
@pytest.mark.parametrize("pallas", ["off", "score", "topk"])
@pytest.mark.parametrize("shortlist_c", [-1, 0])
@pytest.mark.parametrize("seed", [3, 11])
def test_cross_region_matches_flat_oracle(pallas, shortlist_c, seed):
    """A 4-region federated solve must be bit-identical — placements,
    scores, statuses, carried usage — to a single flat-mesh
    ResidentSolver over the union fleet, including a region-degraded
    (shard-loss) round compared against a from-scratch pack of the
    surviving nodes."""
    nodes = [make_node(i) for i in range(48)]
    probe = [make_ask(spread=True), make_ask()]
    cr = CrossRegionResidentSolver(
        [nodes[r * 12:(r + 1) * 12] for r in range(4)], probe,
        gp=4, kp=16, pallas=pallas, shortlist_c=shortlist_c)
    assert mesh_region_count(cr.solver._mesh) == 4
    ref = ResidentSolver(nodes, probe, gp=4, kp=16, pallas=pallas,
                         shortlist_c=shortlist_c)
    asks = [make_ask(count=4), make_ask(count=3, cpu=600, spread=True)]
    # two carried-usage rounds, seeded jitter
    for step in range(2):
        o_c = cr.solve_stream([cr.pack_batch(asks)],
                              seeds=[seed + step])
        o_r = ref.solve_stream([ref.pack_batch(asks)],
                               seeds=[seed + step])
        for a, b in zip(o_c, o_r):
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(b))
    u_c, _ = cr.solver.usage()
    u_r, _ = ref.usage()
    np.testing.assert_array_equal(u_c[:len(u_r)], u_r)

    # region-degraded round: lose a shard inside region 2 — its tiles'
    # nodes leave every solve fleet-wide; oracle = from-scratch pack
    # of the survivors
    lost = cr.fail_region_shard(cr.region_names[2])
    assert lost and cr.solver.mesh_state == "degraded"
    lost_ids = _lost_node_ids(cr.solver)
    assert lost_ids
    survivors = [n for n in nodes if n.id not in lost_ids]
    ref2 = ResidentSolver(survivors, probe, gp=4, kp=16,
                          pallas=pallas, shortlist_c=shortlist_c)
    cr.reset_usage()
    ids_c, sc_c, st_c = _solve_ids(cr, cr.pack_batch(asks))
    ids_r, sc_r, st_r = _solve_ids(ref2, ref2.pack_batch(asks))
    assert ids_c == ids_r
    np.testing.assert_array_equal(st_c, st_r)
    np.testing.assert_array_equal(sc_c, sc_r)

    # recover: back on the three-tier mesh, flat parity again
    cr.recover_region()
    assert cr.solver.mesh_state == "healthy"
    cr.reset_usage()
    ref.reset_usage()
    o_c = cr.solve_stream([cr.pack_batch(asks)], seeds=[seed])
    o_r = ref.solve_stream([ref.pack_batch(asks)], seeds=[seed])
    for a, b in zip(o_c, o_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_region_affinity_term_prefers_home_region():
    """The score_spec `region` term: a home-region bias plane flips
    ties toward home nodes, device and host twins stay bit-identical,
    and zero bias is a no-op vs the plane-less solve."""
    pb = contended_problem()
    args = kernel_args(pb)
    Gp = args[7].shape[0]          # ask_res [Gp, R]
    Np = args[0].shape[0]          # avail [Np, R]
    bias = np.zeros((Gp, Np), np.float32)
    bias[:, Np // 2:] = 0.25       # "home" = the back half of the fleet
    host = host_solve_kernel(*args, region_bias=bias)
    dev = jax.jit(
        lambda *a: solve_kernel(*a, region_bias=bias))(*args)
    assert_counters_identical(dev, host)
    base = host_solve_kernel(*args)
    chosen_b = np.asarray(base.choice)[np.asarray(base.choice_ok)]
    chosen_h = np.asarray(host.choice)[np.asarray(host.choice_ok)]
    assert (chosen_h >= Np // 2).sum() >= (chosen_b >= Np // 2).sum()
    assert (chosen_h >= Np // 2).any()
    zero = host_solve_kernel(*args,
                             region_bias=np.zeros((Gp, Np),
                                                  np.float32))
    assert_counters_identical(zero, base)


def test_cross_region_bias_plane_and_directory():
    nodes = [make_node(i) for i in range(32)]
    log = MeshEventLog()
    d = RegionDirectory(event_log=log)
    cr = CrossRegionResidentSolver(
        [nodes[r * 8:(r + 1) * 8] for r in range(4)], [make_ask()],
        region_names=["us", "eu", "ap", "sa"], gp=4, kp=16,
        directory=d)
    assert cr.region_of[nodes[9].id] == "eu"
    plane = cr.region_bias_plane(4, "eu", weight=2.0)
    Np = cr.template.avail.shape[0]
    assert plane.shape == (4, Np)
    lo, hi = cr._region_slices["eu"]
    assert (plane[:, lo:hi] == 2.0).all()
    assert plane.sum() == 4 * (hi - lo) * 2.0
    # join events landed in the solver's mesh event log (global —
    # other regions may have been recorded by earlier tests)
    table = cr.event_log.region_table()
    assert {"us", "eu", "ap", "sa"} <= set(table)
    assert all(table[r]["state"] == "up"
               for r in ("us", "eu", "ap", "sa"))


# ------------------------------------------------------------------
# federated vmap path: ragged regions, loud mismatches, compile cache
# ------------------------------------------------------------------
def test_federated_ragged_regions_pad_and_match():
    """30- and 70-node regions pad to one stacked node axis with dead
    rows and solve bit-identically to each region's own independent
    ResidentSolver."""
    small = [make_node(i) for i in range(30)]
    big = [make_node(100 + i) for i in range(70)]
    probe = [make_ask()]
    fed = FederatedResidentSolver([small, big], probe, gp=4, kp=16)
    np0 = fed.solvers[0].template.avail.shape[0]
    np1 = fed.solvers[1].template.avail.shape[0]
    assert np0 == np1                     # padded to the max
    assert fed.solvers[0].template.n_real == 30
    asks = [make_ask(count=4)]
    pbs = [fed.pack_batch(r, asks) for r in range(2)]
    c, o, s, st = fed.solve_stream([[pbs[0]], [pbs[1]]])
    for r, region_nodes in enumerate((small, big)):
        ref = ResidentSolver(region_nodes, probe, gp=4, kp=16)
        rc, ro, rs_, rst = ref.solve_stream([ref.pack_batch(asks)])
        np.testing.assert_array_equal(o[r], ro)
        np.testing.assert_array_equal(st[r], rst)
        np.testing.assert_array_equal(np.where(o[r], c[r], -1),
                                      np.where(ro, rc, -1))
        np.testing.assert_array_equal(np.where(o[r], s[r], 0.0),
                                      np.where(ro, rs_, 0.0))


def test_federated_universe_mismatch_names_region():
    """Non-paddable universe disagreement (a datacenter only region 1
    carries widens its interned dc axis) fails loudly naming the
    offending region — node COUNTS may differ, universes may not."""
    a = [make_node(i) for i in range(8)]
    b = []
    for i in range(8):
        nd = make_node(50 + i)
        if i % 3 == 2:
            nd.datacenter = "dc2"
        b.append(nd)
    with pytest.raises(ValueError,
                       match=r"region 1 disagrees on dc_ok"):
        FederatedResidentSolver([a, b], [make_ask()], gp=4, kp=16)


def test_federated_stream_zero_recompile():
    """Same-shape federated steps must hit one traced computation; a
    third region (new stacked [B, R, ...] shapes) costs exactly one
    new cache entry (mirrors tests/test_resident.py's guard)."""
    nodes = [make_node(i) for i in range(16)]
    probe = [make_ask()]
    fed = FederatedResidentSolver([nodes] * 2, probe, gp=4, kp=16)
    asks = [make_ask(count=3)]
    pb = fed.pack_batch(0, asks)
    fed.solve_stream([[pb], [pb]])
    c0 = FederatedResidentSolver.compile_count()
    if c0 < 0:
        pytest.skip("runtime does not expose the jit cache size")
    for seed in (7, 8):
        pb2 = fed.pack_batch(0, [make_ask(count=3, cpu=700)])
        fed.solve_stream([[pb2], [pb2]], seeds=[[seed], [seed]])
    assert FederatedResidentSolver.compile_count() == c0
    fed3 = FederatedResidentSolver([nodes] * 3, probe, gp=4, kp=16)
    pb3 = fed3.pack_batch(0, asks)
    fed3.solve_stream([[pb3], [pb3], [pb3]])
    assert FederatedResidentSolver.compile_count() == c0 + 1


# ------------------------------------------------------------------
# membership: serf WAN gossip drives the federation table
# ------------------------------------------------------------------
def test_gossip_region_join_leave_drives_directory():
    """RegionDirectory's callbacks plug straight into GossipAgent's
    on_join/on_fail slots; join/leave replay through the mesh event
    log's region_table."""
    from nomad_tpu.membership.gossip import GossipAgent, Member

    class _R:
        def register(self, *_a, **_k):
            pass

    log = MeshEventLog()
    d = RegionDirectory(event_log=log)
    agent = GossipAgent(
        Member(id="me", region="us", addr=("127.0.0.1", 0)), _R(),
        on_join=d.on_join, on_fail=d.on_fail)
    agent.on_join(Member(id="us-1", region="us",
                         addr=("127.0.0.1", 1)))
    agent.on_join(Member(id="us-2", region="us",
                         addr=("127.0.0.1", 2)))
    agent.on_join(Member(id="eu-1", region="eu",
                         addr=("127.0.0.1", 3)))
    assert d.regions() == ["eu", "us"]
    assert d.members_of("us") == ["us-1", "us-2"]
    agent.on_fail(Member(id="eu-1", region="eu",
                         addr=("127.0.0.1", 3)))
    assert d.regions() == ["us"]          # last member gone -> left
    table = log.region_table()
    assert table["us"]["state"] == "up"
    assert table["eu"]["state"] == "left"
    assert table["eu"]["members"] == []


# ------------------------------------------------------------------
# admission-tier spillover: cheapest-at-SLO, brownout overflow, shed
# ------------------------------------------------------------------
def _seeded_router(**overrides):
    log = MeshEventLog()
    d = RegionDirectory(event_log=log)
    r = SpilloverRouter(regions={"us": 1.0, "eu": 2.0, "ap": 3.0},
                        overrides={"slo_budget_s": 0.1,
                                   "spill_margin": 1.0, **overrides},
                        directory=d, event_log=log)
    for name in ("us", "eu", "ap"):
        r.note_solve(name, 8, 0.01)
        r.note_solve(name, 16, 0.02)
    return r, log


def _brown(rs):
    rs.note_ready(int(rs.admission.brownout_high
                      * rs.admission.max_pending) + 1)


def test_spillover_prefers_healthy_home_then_cheapest():
    r, _log = _seeded_router()
    ev = object()
    assert r.route(ev, home="eu") == ("eu", "home")
    # no home: cheapest region meeting SLO wins
    assert r.route(ev) == ("us", "cheapest")
    assert r.stats()["routed"]["home"] == 1


def test_spillover_overflows_on_home_brownout():
    """Home saturated -> the cheapest sibling admits (the brownout
    watermark trips BEFORE the controller latches — the router must
    not keep feeding a saturating region)."""
    r, log = _seeded_router()
    _brown(r.region("eu"))
    assert r.route(object(), home="eu") == ("us", "spillover")
    assert any(e["kind"] == "region.spill" for e in log.events())


def test_spillover_slo_miss_admits_late_not_parked():
    r, _log = _seeded_router()
    _brown(r.region("eu"))
    for name in ("us", "ap"):
        rs = r.region(name)
        rs.model.observe(8, 5.0)       # hopeless latency at depth
        rs.model.observe(16, 9.0)
        rs.note_ready(10)
    reg, cause = r.route(object(), home="eu")
    assert cause == "slo_miss" and reg in ("us", "ap")


def test_spillover_all_browned_sheds_then_readmits():
    """Every region browned out -> shed lane (never dropped); the
    parked eval readmits as soon as one region drains, and the
    accounting stays intact."""
    r, log = _seeded_router()
    for name in ("us", "eu", "ap"):
        _brown(r.region(name))
    ev = object()
    assert r.route(ev, home="eu") == (None, "shed")
    assert r.shed_depth() == 1
    assert any(e["kind"] == "region.shed" for e in log.events())
    r.region("ap").note_ready(0)
    got = r.drain_shed()
    assert got == [(ev, "ap")]
    assert r.shed_depth() == 0
    s = r.stats()
    assert s["routed"]["shed"] == 1 and s["routed"]["readmitted"] == 1
    assert s["shed_lane_depth"] == 0


def test_spillover_membership_follows_gossip():
    """Region join/leave over the serf WAN pool adds/removes routing
    targets; with no live region the eval parks rather than drops."""
    class M:
        def __init__(self, mid, region):
            self.id, self.region = mid, region

    log = MeshEventLog()
    r = SpilloverRouter(directory=RegionDirectory(event_log=log),
                        event_log=log,
                        overrides={"slo_budget_s": 0.1})
    r.on_join(M("s1", "us"))
    r.on_join(M("s2", "eu"))
    assert r.regions() == ["eu", "us"]
    r.note_solve("us", 8, 0.001)
    r.note_solve("eu", 8, 0.001)
    # equal default cost -> (cost, name) order picks "eu"
    assert r.route(object())[0] == "eu"
    r.on_fail(M("s2", "eu"))
    assert r.regions() == ["us"]
    assert r.route(object())[0] == "us"
    r.on_fail(M("s1", "us"))
    assert r.regions() == []
    assert r.route(object()) == (None, "shed")
    assert r.shed_depth() == 1


def test_spillover_knobs_env_and_overrides(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_SPILL_MARGIN", "0.5")
    monkeypatch.setenv("NOMAD_TPU_MAX_PENDING", "128")
    r = SpilloverRouter(regions={"us": 1.0})
    assert r.spill_margin == 0.5
    assert r.max_pending == 128
    assert r.region("us").admission.max_pending == 128
    r2 = SpilloverRouter(regions={"us": 1.0},
                         overrides={"spill_margin": 0.9})
    assert r2.spill_margin == 0.9          # overrides > env


# ------------------------------------------------------------------
# bench phase smoke: the multiregion phase cannot silently skip
# ------------------------------------------------------------------
@pytest.mark.slow
def test_bench_multiregion_phase_cannot_silently_skip():
    """ISSUE 13 satellite: the bench multiregion phase self-provisions
    the virtual platform and reports BOTH acceptance figures — the
    WAN byte cut with flat-placement parity, and the spillover p99
    bar with zero evals lost — at a smoke-sized shape."""
    import bench
    out = bench.run_multiregion(n_devices=8, n_regions=4,
                                n_nodes=2048, n_evals=8, count=16,
                                evals_per_call=2, write_detail=False)
    assert not out["skipped"]
    assert out["n_regions"] == 4
    wan = out["wan"]
    assert wan["placements_match_flat"]
    assert wan["wan_within_quarter"]
    assert wan["wan_cut_vs_flat"] <= 0.25
    assert wan["measured"]["waves_total"] > 0
    assert all(v is not None for v in wan["model"].values())
    assert all(v is not None for v in wan["measured"].values())
    assert "warm_start" in wan["compile_cache"]
    sp = out["spillover"]
    assert sp["isolated_browned_regions"]       # stock leg browns out
    assert sp["p99_spillover_s"] <= 2 * sp["p99_balanced_s"]
    assert sp["evals_lost"] == 0
    assert sp["shed_accounting_intact"]
    assert sp["spill_ok"]
    assert out["ok"]
