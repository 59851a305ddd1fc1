"""A probe, and nothing else: does the program's wave count a
`distinct_property` limit?

A configuration that names `distinct` holds every job to limits that
many nodes share (at most so many allocs of a group in a rack, of a job
in a zone).  A program whose wave does not count them offers a group's
placements to the best-ranked nodes, which bin-pack puts in a few racks;
its fixup then refuses every candidate in a full rack and leaves the
placement short, and the harness waits out its whole warm-up patience on
a job that never reaches its count (at rehearsal size a group of 16
under a rack limit of 1 placed 9 of 16; PERF.md section 4).  Named after
`distinct` under `rules`, this file says so in the first seconds of the
run instead.

It holds no data, numbers, controls or planes: the semantics are
`rules/distinct.py`'s.  Its one hook is `build_node`, which runs the
probe at the first node and changes no node.
"""
from __future__ import annotations

RACK = "${attr.rack}"


def require_property_limits_in_the_wave() -> None:
    """Raise unless the wave keeps two placements of a group under a
    rack limit of 1 in different racks.  Six nodes: four fuller ones
    (ranked first by bin-pack) in rack r0, two empty ones in r1; one ask
    of count 2 whose group may hold one alloc a rack, its limit keyed as
    the generic scheduler keys it.  A wave blind to the limit gives both
    placements an r0 node first."""
    from nomad_tpu import mock, structs
    from nomad_tpu.solver.solve import _run_kernel
    from nomad_tpu.solver.tensorize import PlacementAsk, Tensorizer
    nodes, by_node = [], {}
    for i in range(6):
        n = mock.node(datacenter="dc1")
        n.attributes["rack"] = "r0" if i < 4 else "r1"
        n.compute_class()
        nodes.append(n)
        if i < 4:
            by_node[n.id] = [structs.Allocation(
                id=f"holder-{i}", node_id=n.id, job_id="holders",
                task_group="r",
                allocated_resources=structs.AllocatedResources(
                    tasks={"web": structs.AllocatedTaskResources(
                        cpu=1000, memory_mb=2048)}),
                desired_status=structs.ALLOC_DESIRED_RUN,
                client_status=structs.ALLOC_CLIENT_RUNNING)]
    job = mock.job()
    job.datacenters = ["dc1"]
    job.constraints, job.affinities, job.spreads = [], [], []
    tg = job.task_groups[0]
    tg.count = 2
    tg.constraints, tg.affinities, tg.spreads = [], [], []
    tg.tasks[0].resources.networks = []
    tg.tasks[0].resources.devices = []
    tg.constraints.append(structs.Constraint(
        ltarget=RACK, rtarget="1",
        operand=structs.CONSTRAINT_DISTINCT_PROPERTY))
    key = (f"tg:{job.namespace}:{job.id}:{tg.name}", RACK)
    ask = PlacementAsk(job=job, tg=tg, count=2,
                       property_limits={key: (1, {})})
    # the wave alone, on the host twin: no counter of the served path
    # moves (a `Solver.solve` here would count one solve off the device)
    pb = Tensorizer().pack(nodes, [ask], by_node)
    res = _run_kernel(pb, host_mode="always")
    racks = [nodes[int(res.choice[p, 0])].attributes["rack"]
             if bool(res.choice_ok[p, 0]) else None for p in range(2)]
    if None in racks or racks[0] == racks[1]:
        raise RuntimeError(
            "this program does not count a distinct_property limit in the "
            "wave (one group of 2 under a rack limit of 1, six nodes, the "
            f"four best-ranked in rack r0: the wave's first choices lie in "
            f"racks {racks}, not in two racks); the configuration's jobs "
            "would never reach their count on it "
            "(benchmark/rules/distinct_in_wave.py)")


def build_node(node, plain, i: int, cfg: dict) -> None:
    if i == 0:
        require_property_limits_in_the_wave()
