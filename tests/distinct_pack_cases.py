"""Batches that carry no distinct_property limit, packed over 64
generator rows by the full pack and by the resident repack, and the
digest of every array of each `PackedBatch`: what
`tests/golden/distinct_pack_parent.json` holds for commit 7921479
(before the wave counted a property limit) and
`test_distinct_served.py` computes again.  Nothing here names anything
that commit lacks."""
import copy

from nomad_tpu import structs
from nomad_tpu.solver.tensorize import PlacementAsk, Tensorizer

import ports_pack_cases as base


def job(groups, count, distinct_hosts=False, job_scope=False):
    jb = base.job(groups, count, affinity=True)
    jb.constraints = [structs.Constraint("${attr.rack}", "r7", "!=")]
    hosts = structs.Constraint(operand=structs.CONSTRAINT_DISTINCT_HOSTS)
    if distinct_hosts and job_scope:
        jb.constraints.append(copy.deepcopy(hosts))
    elif distinct_hosts:
        for tg in jb.task_groups:
            tg.constraints.append(copy.deepcopy(hosts))
    return jb


CASES = {
    "c3_shape": dict(groups=4, count=16),
    "group_distinct_hosts": dict(groups=4, count=16, distinct_hosts=True),
    "job_distinct_hosts": dict(groups=2, count=8, distinct_hosts=True,
                               job_scope=True),
}


def batch(name):
    nodes = base.rows()
    allocs = {nd.id: [base.resident(nd, k) for k in range(5)]
              for nd in nodes}
    jb = job(**CASES[name])
    asks = [PlacementAsk(job=jb, tg=tg, count=tg.count,
                         distinct_hosts_blocked=frozenset(
                             [nodes[3].id] if g == 0 else []),
                         spread_seed={"${node.datacenter}": {"dc1": 2}})
            for g, tg in enumerate(jb.task_groups)]
    return nodes, allocs, asks


def pack(name):
    nodes, allocs, asks = batch(name)
    return Tensorizer().pack(nodes, asks, allocs)


def repack(name):
    nodes, allocs, asks = batch(name)
    tz = Tensorizer()
    template = tz.pack(nodes, asks, allocs)
    return tz.repack_asks(nodes, asks, template, gp=8, kp=128)


def digests() -> dict:
    return {f"{how}:{name}": base.digest(fn(name))
            for name in CASES for how, fn in (("pack", pack),
                                              ("repack", repack))}
