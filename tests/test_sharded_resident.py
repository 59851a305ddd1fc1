"""Mesh-resident sharded solve (ISSUE 5): the shard_map wave loop with
candidate-only ICI traffic must produce placements AND explainability
counters bit-identical to the single-device host twin, across pallas
modes, shortlist on/off, mesh widths, and random delta interleavings.

Runs on the conftest-forced 8-device virtual CPU mesh.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from nomad_tpu import mock
from nomad_tpu.parallel.federated import FederatedResidentSolver
from nomad_tpu.parallel.sharded import (_ARG_SPECS,
                                        _kernel_positional_count,
                                        ShardedResidentSolver,
                                        kernel_args, make_node_mesh,
                                        model_ici_bytes)
from nomad_tpu.solver.host import HostResidentSolver, host_solve_kernel
from nomad_tpu.solver.kernel import solve_kernel
from nomad_tpu.solver.resident import ResidentSolver
from nomad_tpu.solver.tensorize import (ClusterDelta, PlacementAsk,
                                        Tensorizer, alloc_usage_vector)
from nomad_tpu.structs import Spread


# ------------------------------------------------------------------
# direct-kernel harness: solve_kernel under shard_map, _ARG_SPECS
# as the in_specs (so a spec drift breaks these tests too)
# ------------------------------------------------------------------
def mesh_solve(args, n_shards, **kw):
    mesh = Mesh(np.array(jax.devices()[:n_shards]), ("nodes",))
    in_specs = tuple(_ARG_SPECS)

    def body(*a):
        return solve_kernel(*a, mesh_axis="nodes",
                            mesh_shards=n_shards, **kw)

    shape = jax.eval_shape(lambda *a: solve_kernel(*a, **kw), *args)
    out_specs = jax.tree_util.tree_map(lambda _: P(), shape)
    out_specs = out_specs._replace(feas=P(None, "nodes"),
                                   used_final=P("nodes", None),
                                   dev_used_final=P("nodes", None))
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False))
    return f(*args)


def contended_problem(n_big=6, n_small=58, n_groups=4, count=12):
    nodes = []
    for i in range(n_big + n_small):
        n = mock.node()
        n.node_resources.cpu = 4000 if i < n_big else 600
        n.node_resources.memory_mb = 8192
        n.compute_class()
        nodes.append(n)
    asks = []
    for g in range(n_groups):
        j = mock.job()
        j.id = f"job-{g}"
        tg = j.task_groups[0]
        tg.count = count
        tg.tasks[0].resources.networks = []
        tg.tasks[0].resources.cpu = 500
        tg.tasks[0].resources.memory_mb = 128
        asks.append(PlacementAsk(job=j, tg=tg, count=count))
    return Tensorizer().pack(nodes, asks)


def spread_problem():
    nodes = []
    for i in range(48):
        n = mock.node(datacenter=f"dc{i % 3}")
        n.node_resources.cpu = 2200
        n.node_resources.memory_mb = 4096
        n.compute_class()
        nodes.append(n)
    asks = []
    for g in range(3):
        j = mock.job()
        j.id = f"job-{g}"
        j.datacenters = ["dc0", "dc1", "dc2"]
        j.spreads = [Spread(attribute="${node.datacenter}", weight=100)]
        tg = j.task_groups[0]
        tg.count = 8
        tg.tasks[0].resources.networks = []
        tg.tasks[0].resources.cpu = 400
        tg.tasks[0].resources.memory_mb = 256
        asks.append(PlacementAsk(job=j, tg=tg, count=8))
    return Tensorizer().pack(nodes, asks)


def assert_counters_identical(res, host):
    """Placements + every explainability counter, bitwise."""
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
    np.testing.assert_array_equal(np.asarray(res.feas), host.feas)
    np.testing.assert_array_equal(np.asarray(res.cons_filtered),
                                  host.cons_filtered)
    np.testing.assert_array_equal(np.asarray(res.used_final),
                                  host.used_final)


@pytest.mark.parametrize("mode", ["off", "score", "topk"])
@pytest.mark.parametrize("shortlist_c", [-1, 0])
def test_mesh_kernel_contended_matches_host(mode, shortlist_c):
    """Contended shape (shortlists drain, escapes fire) across pallas
    modes x shortlist on/off, 8 shards, counters bitwise."""
    pb = contended_problem()
    args = kernel_args(pb)
    host = host_solve_kernel(*args, 0, has_spread=False)
    res = mesh_solve(args, 8, has_spread=False, has_distinct=False,
                     pallas_mode=mode, shortlist_c=shortlist_c)
    assert_counters_identical(res, host)


@pytest.mark.parametrize("n_shards", [2, 4, 8])
def test_mesh_kernel_equivalent_across_mesh_widths(n_shards):
    pb = contended_problem()
    args = kernel_args(pb)
    host = host_solve_kernel(*args, 0, has_spread=False)
    res = mesh_solve(args, n_shards, has_spread=False,
                     has_distinct=False)
    assert_counters_identical(res, host)


@pytest.mark.parametrize("mode", ["off", "score", "topk"])
def test_mesh_kernel_spread_interleave_matches_host(mode):
    """Spread groups ride the merged per-value tables: the post-merge
    interleave must reproduce the host twin bit-for-bit."""
    pb = spread_problem()
    args = kernel_args(pb)
    host = host_solve_kernel(*args, 0, has_spread=True)
    res = mesh_solve(args, 8, has_spread=True, has_distinct=False,
                     pallas_mode=mode, shortlist_c=0)
    assert_counters_identical(res, host)


def test_mesh_kernel_seeded_jitter_matches_single_device():
    """seed != 0 hashes GLOBAL node ids: the seeded tie-break fan-out
    must be invariant to how the node axis is split.  Compared BITWISE
    against the single-device kernel (the host twin's seeded scores sit
    1 ulp off the XLA float chain, as in test_shortlist)."""
    pb = contended_problem()
    args = kernel_args(pb)
    single = solve_kernel(*args, 3, has_spread=False,
                          has_distinct=False)
    res = mesh_solve(args, 8, seed=3, has_spread=False,
                     has_distinct=False)
    for fld in ("choice", "choice_ok", "score", "n_feasible",
                "n_exhausted", "dim_exhausted", "unfinished", "feas",
                "cons_filtered", "used_final"):
        np.testing.assert_array_equal(
            np.asarray(getattr(single, fld)),
            np.asarray(getattr(res, fld)), err_msg=fld)


def test_mesh_shortlist_waves_engage():
    """The sharded shortlist path must actually serve waves: per-shard
    full passes (n_rescore) stay below waves x shards."""
    pb = contended_problem()
    args = kernel_args(pb)
    res = mesh_solve(args, 2, has_spread=False, has_distinct=False,
                     shortlist_c=0)
    waves, resc = int(res.n_waves), int(res.n_rescore)
    assert waves >= 2
    assert resc < waves * 2, (waves, resc)
    off = mesh_solve(args, 2, has_spread=False, has_distinct=False,
                     shortlist_c=-1)
    assert int(off.n_rescore) == int(off.n_waves) * 2


# ------------------------------------------------------------------
# solver level: resident stream + deltas
# ------------------------------------------------------------------
def make_node(i, cpu=4000):
    nd = mock.node(datacenter=f"dc{i % 2}")
    nd.attributes["rack"] = f"r{i % 4}"
    nd.node_resources.cpu = cpu
    nd.node_resources.memory_mb = 16384
    nd.node_resources.disk_mb = 100_000
    nd.compute_class()
    return nd


def make_ask(count=3, cpu=500, spread=False):
    job = mock.job()
    job.datacenters = ["dc0", "dc1"]
    tg = job.task_groups[0]
    tg.count = count
    tg.tasks[0].resources.networks = []
    tg.tasks[0].resources.cpu = cpu
    if spread:
        job.spreads = [Spread(attribute="${node.datacenter}",
                              weight=100)]
    return PlacementAsk(job=job, tg=tg, count=count)


def make_alloc(cpu=300, mem=256):
    a = mock.alloc()
    tr = a.allocated_resources.tasks["web"]
    tr.cpu = cpu
    tr.memory_mb = mem
    tr.networks = []
    a.allocated_resources.shared.networks = []
    a.allocated_resources.shared.disk_mb = 100
    return a


def test_sharded_stream_matches_host_twin():
    """Multi-step stream with carried usage vs the device-parity host
    twin: per-step placements, score bits, and status identical."""
    nodes = [make_node(i) for i in range(40)]
    probe = [make_ask()]
    rs = ShardedResidentSolver(nodes, probe, gp=4, kp=16, pallas="off")
    host = HostResidentSolver(nodes, probe, gp=4, kp=16,
                              use_native=False, device_parity=True)
    assert rs.n_shards == 8
    for step in range(4):
        asks = [make_ask(count=4, cpu=300 + 100 * step)]
        pb, pbh = rs.pack_batch(asks), host.pack_batch(asks)
        c, o, s, st = rs.solve_stream([pb])
        ch, oh, sh, sth = host.solve_stream([pbh])
        np.testing.assert_array_equal(o, oh, err_msg=f"step {step}")
        np.testing.assert_array_equal(st, sth, err_msg=f"step {step}")
        np.testing.assert_array_equal(
            np.where(o, c, -1), np.where(oh, ch, -1),
            err_msg=f"step {step}")
    u, du = rs.usage()
    uh, duh = host.usage()
    np.testing.assert_array_equal(u, uh)
    np.testing.assert_array_equal(du, duh)


@pytest.mark.parametrize("pallas", ["off", "score"])
@pytest.mark.parametrize("shortlist_c", [-1, 0])
def test_random_delta_interleavings_sharded_matches_single_device(
        pallas, shortlist_c):
    """Random place/stop/drain/join interleavings applied through
    apply_delta on the MESH must stay bit-identical (by node id) to a
    single-device ResidentSolver fed the same deltas — the sharded
    scatter routing cannot corrupt resident state."""
    rng = np.random.default_rng(11)
    probe = [make_ask(spread=True), make_ask()]
    nodes = [make_node(i) for i in range(24)]
    rs = ShardedResidentSolver(nodes, probe, gp=4, kp=16,
                               pallas=pallas, shortlist_c=shortlist_c)
    ss = ResidentSolver(nodes, probe, gp=4, kp=16, pallas=pallas,
                        shortlist_c=shortlist_c)
    live = {}
    join_seq = [n.id for n in nodes]
    next_i = len(nodes)

    for round_ in range(5):
        delta = ClusterDelta()
        for _ in range(int(rng.integers(1, 4))):
            op = rng.choice(["place", "stop", "drain", "join"])
            if op == "place":
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
                delta.remove_node_ids.append(nid)
                for aid in [aid for aid, (n2, _) in live.items()
                            if n2 == nid]:
                    del live[aid]
            elif op == "join":
                n = make_node(next_i)
                next_i += 1
                delta.upsert_nodes.append(n)
                join_seq.append(n.id)
        k_s = rs.apply_delta(delta)
        k_1 = ss.apply_delta(delta)
        assert k_s == k_1, f"round {round_}: {k_s} != {k_1}"

        asks = [make_ask(count=3, cpu=int(rng.integers(200, 600)),
                         spread=bool(round_ % 2))]
        pb_s = rs.pack_batch(asks)
        pb_1 = ss.pack_batch(asks)
        c_s, o_s, s_s, st_s = rs.solve_stream([pb_s])
        c_1, o_1, s_1, st_1 = ss.solve_stream([pb_1])
        np.testing.assert_array_equal(o_s, o_1, err_msg=f"r{round_}")
        np.testing.assert_array_equal(st_s, st_1, err_msg=f"r{round_}")
        n = pb_s.n_place
        ids_s = [rs.template.node_ids[int(c_s[0, p, 0])]
                 if o_s[0, p, 0] else None for p in range(n)]
        ids_1 = [ss.template.node_ids[int(c_1[0, p, 0])]
                 if o_1[0, p, 0] else None for p in range(n)]
        assert ids_s == ids_1, f"round {round_}"
        np.testing.assert_array_equal(
            np.where(o_s, s_s, 0.0), np.where(o_1, s_1, 0.0),
            err_msg=f"round {round_}")
    # resident usage stayed in lockstep (by node id through slots)
    u_s, _ = rs.usage()
    u_1, _ = ss.usage()
    np.testing.assert_array_equal(u_s, u_1)


def test_sharded_repack_fallback_keeps_parity():
    """A delta past the threshold forces the repack path: the sharded
    solver must re-put the rebuilt template through the node sharding
    and keep solving in lockstep."""
    probe = [make_ask()]
    nodes = [make_node(i) for i in range(16)]
    rs = ShardedResidentSolver(nodes, probe, gp=4, kp=16, pallas="off",
                               delta_threshold=0.01)
    ss = ResidentSolver(nodes, probe, gp=4, kp=16, pallas="off",
                        delta_threshold=0.01)
    delta = ClusterDelta()
    for nid in [n.id for n in nodes[:8]]:
        delta.place.append((nid, make_alloc()))
    assert rs.apply_delta(delta) == "repack"
    assert ss.apply_delta(delta) == "repack"
    asks = [make_ask(count=4)]
    c_s, o_s, s_s, st_s = rs.solve_stream([rs.pack_batch(asks)])
    c_1, o_1, s_1, st_1 = ss.solve_stream([ss.pack_batch(asks)])
    np.testing.assert_array_equal(o_s, o_1)
    np.testing.assert_array_equal(np.where(o_s, c_s, -1),
                                  np.where(o_1, c_1, -1))
    np.testing.assert_array_equal(st_s, st_1)


def test_sharded_node_planes_actually_sharded():
    """The resident node planes must live under the nodes-axis
    NamedSharding (not replicated): each of the 8 shards owns Np/8
    rows."""
    nodes = [make_node(i) for i in range(40)]
    rs = ShardedResidentSolver(nodes, [make_ask()], gp=4, kp=16)
    Np = rs.template.avail.shape[0]
    for name, arr in rs._dev_node.items():
        shardings = list(arr.addressable_shards)
        assert len(shardings) == 8, name
        assert shardings[0].data.shape[0] == Np // 8, name
    assert rs._used.addressable_shards[0].data.shape[0] == Np // 8


def test_ici_byte_model_bound_and_measured():
    """wave_traffic grows the ICI tier; the modeled per-wave key bytes
    respect the candidate-keys bound and never carry a [G, N] term."""
    nodes = [make_node(i) for i in range(40)]
    probe = [make_ask()]
    rs = ShardedResidentSolver(nodes, probe, gp=4, kp=16, pallas="off")
    pb = rs.pack_batch([make_ask(count=4)])
    rs.solve_stream([pb])
    wt = rs.wave_traffic([pb])
    ici = wt["ici"]
    assert ici["devices"] == 8
    assert ici["bytes_ici_per_wave"] <= ici["bound_candidate_keys"]
    # candidate keys only: below shipping the [G, N] f32 plane to every
    # chip (the stateless wrapper's failure mode) even at this toy
    # scale; the production ratio is exercised in test_model_ici_bytes
    Np = rs.template.avail.shape[0]
    Gp = pb.ask_res.shape[0]
    assert ici["bytes_ici_per_wave"] < Gp * Np * 4 * ici["devices"]
    # at bench scale the candidate keys are orders of magnitude under
    # one plane (pure model — no device work)
    big = model_ici_bytes(Gp=16, K=2048, A=32, R=6, TKl=1028,
                          n_shards=8, want_tables=False, V=1, TW=0,
                          has_spread=False)
    # merged-mode 50k-node config: all shards' keys together stay under
    # ONE [G, N] f32 plane (vs 8 planes for a replicated-ask gather)
    assert big["bytes_ici_per_wave"] < 16 * 50_176 * 4
    m = wt["measured"]
    assert m["shard_waves_total"] == m["waves_total"] * 8
    assert m["shortlist_waves"] >= 0
    assert m["modeled_bytes_ici_total"] == (
        ici["bytes_ici_total_per_wave"] * m["waves_total"])
    assert wt["per_shard"]["np_local"] == Np // 8


def test_model_ici_bytes_pure():
    out = model_ici_bytes(Gp=4, K=16, A=8, R=6, TKl=32, n_shards=8,
                          want_tables=True, V=4, TW=8, has_spread=True)
    assert out["tk_local"] == 32 + 5 * 8
    assert out["bytes_ici_per_wave"] == out["bound_candidate_keys"]
    assert out["bytes_ici_total_per_wave"] > out["bytes_ici_per_wave"]


# ------------------------------------------------------------------
# satellites: _ARG_SPECS drift guard, federated cache coherence
# ------------------------------------------------------------------
def test_arg_specs_cover_kernel_signature():
    """The import-time guard's invariant, restated as a test (so a
    spec-count fix can't be 'solved' by deleting the assert), plus a
    shape audit: every 'nodes' entry must land on a dim of size Np."""
    assert len(_ARG_SPECS) == _kernel_positional_count()
    pb = contended_problem()
    args = kernel_args(pb)
    assert len(args) == len(_ARG_SPECS)
    Np = pb.avail.shape[0]
    for i, (arg, spec) in enumerate(zip(args, _ARG_SPECS)):
        shape = np.shape(arg)
        assert len(spec) <= max(len(shape), 1), i
        for d, axis_name in enumerate(spec):
            if axis_name == "nodes":
                assert shape[d] == Np, (
                    f"arg {i}: spec shards dim {d} (size {shape[d]}) "
                    f"on 'nodes' but Np={Np}")


@pytest.mark.slow
def test_bench_multichip_phase_cannot_silently_skip():
    """ISSUE 5 satellite: the bench multichip phase self-provisions an
    8-device platform (it must NOT skip when jax.device_count()==1 —
    the bench box has one TPU) and reports the ICI acceptance check at
    a smoke-sized shape."""
    import bench
    out = bench.run_multichip(n_devices=8, sizes=[512], n_evals=4,
                              count=16, evals_per_call=2,
                              write_detail=False)
    assert out["n_devices"] == 8
    assert not out["skipped"]
    assert jax.device_count() >= 8
    (rec,) = out["configs"]
    assert rec["ici_within_bound"]
    assert rec["mesh_resident_s"] > 0
    assert rec["stateless_wrapper_s"] > 0
    assert rec["measured"]["waves_total"] > 0
    # ISSUE 8: the dcn_tier leg + kill-one-shard recovery probe ride
    # the same phase (4-host simulated grouping on the CPU mesh)
    assert out["n_hosts"] == 4
    dcn = rec["dcn_tier"]
    assert dcn["placements_match_flat"]
    # the <= 1/4 acceptance holds at config-3 scale (see
    # tests/test_elastic_mesh.py and MULTICHIP_DETAIL.json's real
    # sizes); this smoke shape (512 nodes) is commit-psum dominated,
    # so only the ordering is asserted here
    assert dcn["bytes_dcn_per_wave"] < dcn["flat_dcn_per_wave"]
    assert dcn["dcn_cut_vs_flat"] < 0.5
    probe = rec["recovery_probe"]
    assert probe["degraded_on_fast_path"]
    assert probe["recovery_bytes"] > 0
    assert probe["recovery_s"] >= 0
    assert probe["grow_bytes_measured"] > 0


def test_federated_stack_cache_keyed_on_node_epoch():
    """ISSUE 5 satellite: the federated step-level stack cache must
    miss after a region's resident node epoch moves (delta applied
    between steps), and hit on a clean re-dispatch."""
    nodes_a = [make_node(i) for i in range(12)]
    nodes_b = [make_node(100 + i) for i in range(12)]
    probe = [make_ask()]
    fed = FederatedResidentSolver([nodes_a, nodes_b], probe,
                                  gp=4, kp=16)
    asks = [make_ask(count=2)]
    batches = [[fed.pack_batch(r, asks)] for r in range(2)]
    first = fed._stack_args(batches, 1)
    again = fed._stack_args(batches, 1)
    assert again is first, "clean re-dispatch must hit the step cache"
    # a node-touching delta on region 0 bumps its node epoch -> the
    # stale stack must miss (usage-only deltas keep the epoch, and the
    # cache: ask planes don't depend on usage)
    changed = make_node(0, cpu=9000)
    changed.id = nodes_a[0].id
    delta = ClusterDelta()
    delta.upsert_nodes.append(changed)
    fed.solvers[0].apply_delta(delta)
    after = fed._stack_args(batches, 1)
    assert after is not first, (
        "node epoch moved but the cached stack was served")


def test_federated_stack_cache_keyed_on_ev_epoch():
    """ISSUE 8 satellite: a pure alloc place/stop delta replays the
    PR-7 eviction-plane rows WITHOUT moving the node epoch — the
    federated step cache must still miss (it keys on the evict-plane
    epoch too), so no future ev plumbing can ever serve rows from
    before the replay."""
    nodes_a = [make_node(i) for i in range(12)]
    nodes_b = [make_node(100 + i) for i in range(12)]
    probe = [make_ask()]
    fed = FederatedResidentSolver([nodes_a, nodes_b], probe,
                                  gp=4, kp=16, evict_e=4)
    asks = [make_ask(count=2)]
    batches = [[fed.pack_batch(r, asks)] for r in range(2)]
    first = fed._stack_args(batches, 1)
    assert fed._stack_args(batches, 1) is first
    delta = ClusterDelta()
    delta.place.append((nodes_a[0].id, make_alloc(cpu=100)))
    node_ep = fed.solvers[0]._node_epoch
    ev_ep = fed.solvers[0]._ev_epoch
    fed.solvers[0].apply_delta(delta)
    # premise: the delta touched ev rows only, never the node planes
    assert fed.solvers[0]._node_epoch == node_ep
    assert fed.solvers[0]._ev_epoch == ev_ep + 1
    after = fed._stack_args(batches, 1)
    assert after is not first, (
        "evict-plane epoch moved but the cached stack was served")
