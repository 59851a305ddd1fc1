"""The seeding recipe at a few hundred nodes on the CPU: the resident
allocs reach the store through raft entries, all of them, and the next
registration places in full."""
import contextlib
import time

import cluster
import load


@contextlib.contextmanager
def toy_server(n_nodes):
    """A started `Server` with a heartbeat pump, stopped on the way out."""
    from nomad_tpu.server.server import Server
    server = Server()
    server.start()
    pump = load.HeartbeatPump(server, n_nodes)
    pump.start()
    try:
        yield server, pump
    finally:
        pump.stop()
        server.stop()


def test_seed_through_raft_then_register():
    cfg = cluster.load_config("c3-affinity-spread-10k", rehearse=True)
    plain = cluster.make_plain_nodes(cfg, 2**31 + 11)
    with toy_server(len(plain)) as (server, pump):
        first = server.raft.log.last_index()
        out = cluster.seed_cluster(server, cfg, plain, 2**31 + 11,
                                   on_node=pump.node_ids.append,
                                   chunk_allocs=1000)
        assert out["resident_allocs"] == cfg["resident"]["allocs"]
        assert len(server.store.allocs()) == cfg["resident"]["allocs"]
        assert len(server.store.nodes()) == cfg["cluster"]["nodes"]
        # through the log: plan entries, not direct store writes
        types = [server.raft.log.get(i).etype
                 for i in range(first + 1, server.raft.log.last_index() + 1)]
        assert types.count("plan_results_batch") == out["resident_entries"]
        assert out["resident_entries"] >= 2
        for a in server.store.allocs()[:50]:
            assert a.create_index > 0 and a.job is not None
        # every node holds its round-robin share
        per_node = cfg["resident"]["allocs"] // cfg["cluster"]["nodes"]
        assert len(server.store.allocs_by_node(plain.ids[0])) == per_node

        gen = load.LoadGen(server, cfg, 7, load.load_traffic("closed1"))
        try:
            give_up = time.monotonic() + 120.0
            regs = gen.closed(1, give_up, give_up, jobs_per_client=1)
        finally:
            gen.close()
        assert len(regs) == 1 and regs[0].t_visible is not None
        live = server.store.allocs_by_job("default", regs[0].job_id)
        assert len(live) == cluster.job_count(cfg) == 64


def test_open_loop_times_from_due_and_reports_lateness():
    """The open loop (used by no cell yet) against a seeded toy server:
    registrations go out on the seed's schedule, are timed from when
    they were due, and none is dropped at the window's close."""
    cfg = cluster.load_config("c2-binpack-10k", rehearse=True)
    plain = cluster.make_plain_nodes(cfg, 5)
    traffic = load.load_traffic("open2")
    traffic.update(rate_per_s=8.0, senders=2, wait_timeout_s=30)
    with toy_server(len(plain)) as (server, pump):
        cluster.seed_cluster(server, cfg, plain, 5,
                             on_node=pump.node_ids.append)
        gen = load.LoadGen(server, cfg, 5, traffic)
        try:
            assert gen.warm_up(timeout_s=120.0) == 7
            win = gen.window(2.0)
        finally:
            gen.close()
    regs = win["regs"]
    assert 4 <= len(regs) <= 40            # poisson at 8/s for 2 s
    assert all(r.t_visible is not None for r in regs)
    due = [r.t_due - win["t_start"] for r in regs]
    assert due == sorted(due) and 0.0 <= due[0] and due[-1] < 2.0
    assert all(r.t_sent >= r.t_due for r in regs)      # never early
    assert all(r.t_visible - r.t_due > 0 for r in regs)
    assert win["allocs_at_close"] <= 64 * len(regs)


def test_every_seed_is_the_same_cluster_in_another_order():
    import numpy as np
    cfg = cluster.load_config("c2-binpack-10k")
    a = cluster.make_plain_nodes(cfg, 1)
    b = cluster.make_plain_nodes(cfg, 2**31 + 5)
    assert a.ids != b.ids
    key = lambda p: sorted(map(tuple, np.column_stack(   # noqa: E731
        [p.cols[t] for t in sorted(p.cols)] + [p.cap]).tolist()))
    assert key(a) == key(b)
