"""ResidentSolver / repack_asks: the streaming fast path must match the
full-pack path exactly (same kernel, same tensors up to padding), carry
usage across batches, and fall back cleanly outside its universe."""
import copy

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.solver.kernel import solve_kernel
from nomad_tpu.solver.resident import ResidentSolver
from nomad_tpu.solver.solve import Solver, _run_kernel
from nomad_tpu.solver.tensorize import PlacementAsk, Tensorizer
from nomad_tpu.structs import Constraint, Spread


def make_nodes(n):
    nodes = []
    for i in range(n):
        nd = mock.node(datacenter=f"dc{i % 2}")
        nd.attributes["rack"] = f"r{i % 4}"
        nd.attributes["ver"] = ["alpha", "gamma"][i % 2]
        nd.compute_class()
        nodes.append(nd)
    return nodes


def make_ask(count=2, cpu=500, rack=None, dc=None, spread=False,
             version_lt=None):
    job = mock.job()
    job.datacenters = [dc] if dc else ["dc0", "dc1"]
    tg = job.task_groups[0]
    tg.count = count
    tg.tasks[0].resources.networks = []
    tg.tasks[0].resources.cpu = cpu
    if rack:
        job.constraints = [Constraint("${attr.rack}", rack, "=")]
    if version_lt:
        job.constraints = [Constraint("${attr.ver}", version_lt, "<")]
    if spread:
        job.spreads = [Spread(attribute="${node.datacenter}", weight=100)]
    return PlacementAsk(job=job, tg=tg, count=count)


def test_repack_matches_full_pack():
    nodes = make_nodes(16)
    # two probes: one covers the rack constraint, one the mock job's
    # default ${attr.kernel.name} constraint
    probe = [make_ask(count=2, rack="r1", spread=True), make_ask(count=2)]
    tz = Tensorizer()
    template = tz.pack(nodes, probe, None)

    asks = [make_ask(count=3, rack="r2"), make_ask(count=2, spread=True)]
    repacked = tz.repack_asks(nodes, asks, template, gp=2, kp=8)
    assert repacked is not None
    full = Tensorizer().pack(nodes, asks, None)

    r1 = _run_kernel(repacked)
    r2 = _run_kernel(full)
    n = full.n_place
    np.testing.assert_array_equal(np.asarray(r1.choice_ok)[:n],
                                  np.asarray(r2.choice_ok)[:n])
    ok = np.asarray(r2.choice_ok)[:n]
    np.testing.assert_array_equal(np.asarray(r1.choice)[:n][ok],
                                  np.asarray(r2.choice)[:n][ok])


def test_repack_unseen_ordered_operand_is_exact():
    """'< beta' with 'beta' outside the interned universe must still
    split alpha/gamma exactly (insertion-rank rewrite)."""
    nodes = make_nodes(8)
    tz = Tensorizer()
    # the probe constraint puts ${attr.ver} in the universe; "beta" stays
    # outside it
    template = tz.pack(nodes, [make_ask(version_lt="alpha")], None)
    pb = tz.repack_asks(nodes, [make_ask(count=1, version_lt="beta")],
                        template, kp=4)
    assert pb is not None
    res = _run_kernel(pb)
    feas = np.asarray(res.feas)[0]
    for i, nd in enumerate(nodes):
        assert feas[i] == (nd.attributes["ver"] < "beta"), (i, nd.attributes)


def test_repack_falls_back_outside_universe():
    nodes = make_nodes(8)
    tz = Tensorizer()
    template = tz.pack(nodes, [make_ask()], None)
    ask = make_ask(count=1)
    ask.job.constraints = [Constraint("${attr.never.seen}", "x", "=")]
    assert tz.repack_asks(nodes, [ask], template) is None


def test_solve_stream_carries_usage_and_matches_sequential():
    nodes = make_nodes(8)
    for nd in nodes:
        nd.node_resources.cpu = 2000
        nd.node_resources.memory_mb = 8192
    rs = ResidentSolver(nodes, [make_ask(count=4)], gp=2, kp=8)

    batches = [rs.pack_batch([make_ask(count=4, cpu=900)]),
               rs.pack_batch([make_ask(count=4, cpu=900)]),
               rs.pack_batch([make_ask(count=4, cpu=900)])]
    assert all(b is not None for b in batches)
    choice, ok, score, status = rs.solve_stream(batches)
    assert choice.shape == (3, 8, 4)
    assert (status[:, :4] == 1).all()   # all real placements committed

    # sequential single-kernel reference with hand-threaded usage
    used = rs.template.used0
    dev_used = rs.template.dev_used0
    for b, pb in enumerate(batches):
        pb2 = copy.copy(pb)
        pb2.used0, pb2.dev_used0 = used, dev_used
        ref = _run_kernel(pb2)
        n = pb.n_place
        np.testing.assert_array_equal(ok[b, :n],
                                      np.asarray(ref.choice_ok)[:n])
        okm = ok[b, :n]
        np.testing.assert_array_equal(choice[b, :n][okm],
                                      np.asarray(ref.choice)[:n][okm])
        used = np.asarray(ref.used_final)
        dev_used = np.asarray(ref.dev_used_final)

    # 8 nodes x 2000 cpu, 12 placements x 900 cpu: only 2 fit per node,
    # so the third batch must have hit capacity pressure from the first
    # two -- verify carried usage is real
    final_used, _ = rs.usage()
    assert final_used[:, 0].sum() == pytest.approx(
        900 * ok[:, :4, 0].sum())
    assert ok[:2, :4, 0].all()          # first two batches place fully


def test_solve_stream_capacity_exhaustion_fails_late_batches():
    nodes = make_nodes(4)
    for nd in nodes:
        nd.node_resources.cpu = 1000
        nd.node_resources.memory_mb = 8192
    rs = ResidentSolver(nodes, [make_ask(count=4)], gp=2, kp=8)
    batches = [rs.pack_batch([make_ask(count=4, cpu=900)]),
               rs.pack_batch([make_ask(count=4, cpu=900)])]
    choice, ok, _, status = rs.solve_stream(batches)
    assert ok[0, :4, 0].all()
    assert not ok[1, :4, 0].any()       # cluster is full
    assert (status[1, :4] == 0).all()   # terminal failure, not retry


def test_merge_asks_semantics():
    """Throughput-mode dedup: identical fresh asks merge with summed
    counts and ALL job keys kept; stateful and distinct_hosts asks
    (even task-level) never merge."""
    from nomad_tpu import mock
    from nomad_tpu.solver.resident import ResidentSolver
    from nomad_tpu.solver.tensorize import PlacementAsk
    from nomad_tpu.structs import CONSTRAINT_DISTINCT_HOSTS, Constraint

    nodes = [mock.node() for _ in range(8)]
    def ask(job_id, count=2, task_distinct=False, stateful=False):
        j = mock.job()
        j.id = job_id
        tg = j.task_groups[0]
        tg.count = count
        tg.tasks[0].resources.networks = []
        if task_distinct:
            tg.tasks[0].constraints = [
                Constraint(operand=CONSTRAINT_DISTINCT_HOSTS)]
        kw = {}
        if stateful:
            kw["penalty_nodes"] = frozenset({nodes[0].id})
        return PlacementAsk(job=j, tg=tg, count=count, **kw)

    rs = ResidentSolver(nodes, [ask("probe")], gp=16, kp=64)
    merged, keys = rs.merge_asks([
        ask("j1"), ask("j2"), ask("j3", task_distinct=True),
        ask("j4", stateful=True)])
    # j1+j2 merged (count 4); distinct + stateful stay separate
    assert len(merged) == 3
    assert merged[0].count == 4
    assert keys == {("default", f"j{i}") for i in range(1, 5)}
    pb = rs.pack_batch(merged, job_keys=keys)
    assert pb.job_keys == keys


def test_steady_state_waves_zero_recompiles():
    """Retrace-count regression guard (ISSUE 3 satellite): after the
    first wave compiles the stream kernel, identical-shape steady-state
    waves must hit the jit cache — zero new compiled variants. A
    failure here means a dispatch argument stopped being
    shape/static-stable and every eval is paying a silent recompile."""
    nodes = make_nodes(16)
    probe = [make_ask(count=2, rack="r1", spread=True), make_ask(count=2)]
    rs = ResidentSolver(nodes, probe, pallas="off")
    asks = [make_ask(count=2)]
    pb = rs.pack_batch(asks)
    assert pb is not None
    rs.solve_stream([pb])            # warm-up: pays the one compile
    c0 = ResidentSolver.compile_count()
    if c0 < 0:
        pytest.skip("jit compile-cache probe unavailable in this jax")
    for _ in range(3):
        pb2 = rs.pack_batch(asks)    # fresh pack, same shapes
        rs.solve_stream([pb2])
    assert ResidentSolver.compile_count() == c0, \
        "steady-state waves triggered a recompile"


def test_pipelined_steady_state_zero_recompiles():
    """The double-buffered pipelined schedule must be as retrace-free
    as the plain stream: chunked waves over one resident universe
    reuse the single compiled variant."""
    nodes = make_nodes(16)
    probe = [make_ask(count=2, rack="r1", spread=True), make_ask(count=2)]
    rs = ResidentSolver(nodes, probe, pallas="off")
    chunks = [[make_ask(count=2)], [make_ask(count=2)]]
    rs.solve_stream_pipelined(chunks)    # warm-up
    c0 = ResidentSolver.compile_count()
    if c0 < 0:
        pytest.skip("jit compile-cache probe unavailable in this jax")
    rs.solve_stream_pipelined([[make_ask(count=2)], [make_ask(count=2)]])
    assert ResidentSolver.compile_count() == c0, \
        "pipelined steady-state waves triggered a recompile"
