"""Functions that compute the least bytes a piece of work must move,
from the configuration's file and counts of work done: never from padded
shapes or from the program's own model of one implementation."""
from __future__ import annotations

import cluster

WORD = 4      # bytes: the program is 32-bit everywhere on the device

#: node-side planes any solve of any configuration here must read:
#: capacity, reserved and usage on cpu / memory / disk, validity, and
#: the datacenter column (every job names its datacenters)
BASE_PLANES = 3 * 3 + 1 + 1


def attribute_columns(cfg: dict) -> int:
    """Attribute columns the configuration's jobs name in a constraint,
    an affinity or a spread (the datacenter is in BASE_PLANES)."""
    j = cfg["job"]
    targets = {c[0] for c in j["constraints"]}
    targets |= {a[0] for a in j["affinities"]}
    targets |= {s[0] for s in j["spreads"]}
    targets.discard("${node.datacenter}")
    return len(targets)


def least_solve_bytes(cfg: dict, solves: float, placements: float) -> float:
    """Per solve, every node-side plane read once for the cluster's real
    node count (those above and each rule's `planes(cfg)`); per
    placement, the chosen node written.  A kernel that
    keeps the planes in VMEM across the waves of a solve still reads them
    once, so the share reads the same work whatever implements it."""
    nodes = int(cfg["cluster"]["nodes"])
    planes = BASE_PLANES + attribute_columns(cfg) \
        + sum(of_rule(cfg) for of_rule in cluster.hooks(cfg, "planes"))
    return solves * planes * nodes * WORD + placements * WORD
