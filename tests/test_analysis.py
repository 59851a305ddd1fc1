"""nomadlint (nomad_tpu.analysis): each pass must catch its synthetic
violation fixture, stay quiet on the clean twin, and the real package
must carry zero unsuppressed findings.

The fixtures are written as source files into a throwaway package —
the analyzer is pure AST and never imports them, so they can reference
jax freely without a device (and contain deliberate bugs without
runtime consequences).  The SHARD/ALIAS fixtures include seeded
reproductions of the three shipped historical bugs (PR-5 zero-copy
device_put aliasing, GSPMD double-applied scatter, PR-4 donated-carry
read) so the passes provably catch what we actually shipped."""
import functools
import os
import textwrap

import pytest

from nomad_tpu.analysis import (AnalysisConfig, BaselineError, analyze,
                                default_baseline_path, load_baseline)
from nomad_tpu.analysis.baseline import parse_baseline_text
from nomad_tpu.analysis.core import PackageIndex
from nomad_tpu.analysis.score_pass import (DEFAULT_SCORER_SITES,
                                           ScorerSite)


def write_fixture(tmp_path, files, pkg_name="fixpkg"):
    pkg = tmp_path / pkg_name
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    for name, src in files.items():
        (pkg / name).write_text(textwrap.dedent(src))
    return str(tmp_path)


FIX_STORE = """
    import time
    import uuid


    class FakeStore:
        def __init__(self):
            self._t = {"things": {}}

        def upsert_thing(self, index, p):      # clean mutator
            for key in sorted({("a", 1), ("b", 2)}):
                self._t["things"][key] = index

        def stamp_thing(self, index):
            self._t["things"]["ts"] = time.time()          # FSM101

        def tag_thing(self, index):
            self._t["things"]["id"] = str(uuid.uuid4())    # FSM102

        def shuffle_thing(self, index):
            for key in {("x", 1), ("y", 2)}:               # FSM103
                self._t["things"][key] = index
"""

FIX_FSM = """
    from .store import FakeStore


    class FSM:
        def __init__(self, store: FakeStore):
            self.store = store

        def apply(self, index, p):
            self._ap_upsert(index, p)

        def _ap_upsert(self, index, p):
            self.store.upsert_thing(index, p)
            self.store.stamp_thing(index)
            self.store.tag_thing(index)
            self.store.shuffle_thing(index)
"""

FIX_ROGUE = """
    from .store import FakeStore


    def sneak_write(store: FakeStore):
        store.upsert_thing(1, None)                        # FSM104


    def innocent_read(store: FakeStore):
        return store._t
"""

FIX_JIT = """
    import functools
    import logging

    import jax

    _log = logging.getLogger(__name__)
    _CACHE = {}


    @functools.partial(jax.jit, static_argnames=("mode",))
    def good_kernel(x, mode="a"):
        if mode == "a":          # static branch: fine
            return x + 1
        return x - 1


    @jax.jit
    def noisy_kernel(x):
        print("tracing")                                   # JIT201
        _log.info("traced")                                # JIT201
        return x


    @jax.jit
    def branchy_kernel(x, flag):
        if flag:                                           # JIT203
            return x
        return -x


    @jax.jit
    def leaky_kernel(x):
        _CACHE["k"] = x                                    # JIT202
        return x


    @functools.partial(jax.jit, donate_argnums=(0,))
    def donating_update(arr, rows):
        return arr.at[0].set(rows)


    def bad_caller(arr, rows):
        out = donating_update(arr, rows)
        return out + arr.sum()                             # JIT204


    def good_caller(arr, rows):
        arr = donating_update(arr, rows)
        return arr + 1                # rebound to the result: fine


    @jax.jit
    def loopy_kernel(x, n):
        for i in range(n):                                 # JIT203
            x = x + i
        return x


    @functools.partial(jax.jit, static_argnames=("n",))
    def loopy_static(x, n=4):
        for i in range(n):            # static bound: fine
            x = x + i
        return x


    @functools.partial(jax.jit, donate_argnums=(0,))
    def donating_carry(carry, x):
        return (carry[0] + x, carry[1])


    def bad_carry_reader(carry, x):
        out = donating_carry(carry, x)
        return out[0] + carry[1]                           # JIT204


    def good_carry_reader(carry, x):
        carry = donating_carry(carry, x)
        return carry[0]               # rebound carry: fine


    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def lane_scan_kernel(used, dev_used, stacked):
        return used + 1, dev_used + 1, stacked.sum()


    class LaneCarry:
        # the ISSUE-20 scan-of-vmap carry shape: the lane kernel
        # returns the donated usage carry as the LEADING elements of a
        # flat result tuple, rebound in one tuple-target assign
        def good_lane_solve(self, stacked):
            (self._used, self._dev_used, out) = lane_scan_kernel(
                self._used, self._dev_used, stacked)
            return out, self._used.sum()    # rebound via tuple: fine

        def bad_lane_solve(self, stacked):
            (used2, dev2, out) = lane_scan_kernel(
                self._used, self._dev_used, stacked)
            return out + self._used.sum()                  # JIT204


    class EvPlanes:
        # the ISSUE-7 eviction-plane carry pattern: node planes held in
        # a dict attribute, donated through a local alias
        def __init__(self):
            self._dev_node = {}

        def bad_ev_carry_reader(self, rows):
            dn = self._dev_node
            out = donating_update(dn["ev_prio"], rows)
            return out + self._dev_node["ev_prio"].sum()   # JIT204

        def good_ev_carry_reader(self, rows):
            dn = self._dev_node
            dn["ev_prio"] = donating_update(dn["ev_prio"], rows)
            return self._dev_node["ev_prio"].sum()  # rebound via alias


    @jax.jit
    def meshless_kernel(x):
        total = jax.lax.psum(x, "nodes")                   # JIT205
        return total + jax.lax.axis_index("nodes")         # JIT205


    def meshy_body(x):
        g = jax.lax.all_gather(x, "nodes", axis=0, tiled=True)
        return g + jax.lax.psum(x, "nodes")   # mesh root: fine


    def meshy_helper(x):
        # reachable FROM the shard_map body: fine
        return jax.lax.psum(x, "nodes")


    def meshy_partial_body(x, scale):
        return meshy_helper(x) * scale


    def run_meshy(mesh, x):
        from jax.experimental.shard_map import shard_map
        f = shard_map(meshy_body, mesh=mesh, in_specs=None,
                      out_specs=None)
        body = functools.partial(meshy_partial_body, scale=2)
        g = shard_map(body, mesh=mesh, in_specs=None, out_specs=None)
        return f(x) + g(x)


    HOST_AX = "hosts"


    def two_tier_body(x):
        # both axes bound by the enclosing ("hosts", "chips") mesh
        s = jax.lax.psum(x, "chips")
        return jax.lax.psum(s, HOST_AX)


    def wrong_axis_body(x):
        # the enclosing mesh binds hosts/chips, not the flat "nodes"
        return jax.lax.psum(x, "nodes")                    # JIT205


    def run_two_tier(devices, x):
        import numpy as np
        from jax.experimental.shard_map import shard_map
        from jax.sharding import Mesh
        mesh = Mesh(np.array(devices).reshape(2, 2),
                    ("hosts", "chips"))
        f = shard_map(two_tier_body, mesh=mesh, in_specs=None,
                      out_specs=None)
        g = shard_map(wrong_axis_body, mesh=mesh, in_specs=None,
                      out_specs=None)
        return f(x) + g(x)


    REGION_AX = "regions"


    def make_region_mesh(devices):
        # internal helper returning a three-tier Mesh: axes must
        # resolve through ONE return level (ISSUE 13)
        import numpy as np
        from jax.sharding import Mesh
        grid = np.array(devices).reshape(2, 2, 2)
        return Mesh(grid, (REGION_AX, HOST_AX, "chips"))


    def three_tier_body(x):
        # all three axes bound by the helper-built mesh: fine
        s = jax.lax.psum(x, "chips")
        s = jax.lax.psum(s, HOST_AX)
        return jax.lax.psum(s, REGION_AX)


    def inner_only_body(x):
        # also wrapped by the two-tier context in run_nested below,
        # where "regions" is NOT bound -> latent trace error there
        return jax.lax.psum(x, REGION_AX)                  # JIT205


    def run_three_tier(devices, x):
        from jax.experimental.shard_map import shard_map
        f = shard_map(three_tier_body, mesh=make_region_mesh(devices),
                      in_specs=None, out_specs=None)
        return f(x)


    def run_nested(devices, x):
        import numpy as np
        from jax.experimental.shard_map import shard_map
        from jax.sharding import Mesh
        inner = make_region_mesh(devices)
        outer = Mesh(np.array(devices).reshape(2, 4),
                     (HOST_AX, "chips"))
        f = shard_map(inner_only_body, mesh=inner, in_specs=None,
                      out_specs=None)
        g = shard_map(inner_only_body, mesh=outer, in_specs=None,
                      out_specs=None)
        return f(x) + g(x)
"""

FIX_LOCKS = """
    import threading

    _G = {}
    _G_LOCK = threading.Lock()


    def fill(k, v):
        _G[k] = v                                          # LOCK303


    def fill_safe(k, v):
        with _G_LOCK:
            _G[k] = v


    class Chatty:
        def __init__(self):
            self._lock = threading.Lock()
            self._state = {}
            self._worker = None
            self._enabled = False

        def start(self):
            self._worker = threading.Thread(target=self._run)  # LOCK301
            self._worker.start()

        def set_enabled(self, enabled):
            with self._lock:
                self._enabled = enabled

        @property
        def enabled(self):
            return self._enabled                           # LOCK302

        def _run(self):
            with self._lock:
                self._state["x"] = 1


    class Quiet:
        def __init__(self):
            self._lock = threading.Lock()
            self._state = {}
            self._worker = None

        def start(self):
            with self._lock:
                self._worker = threading.Thread(target=self._run)
                self._worker.start()

        @property
        def state(self):
            with self._lock:
                return dict(self._state)

        def _run(self):
            with self._lock:
                self._state["x"] = 1


    class TwoLocks:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()
            self._t = threading.Thread(target=self.one)

        def one(self):
            with self._a:
                with self._b:
                    pass

        def two(self):
            with self._b:
                with self._a:                              # LOCK304
                    pass


    class SharedModel:
        # never starts a thread itself: reached ONLY by composition
        # from the threaded Owner below (ISSUE 6 controller-state rule)
        def __init__(self):
            self._lock = threading.Lock()
            self._ewma = {}

        def observe(self, k, v):
            self._ewma[k] = v                      # LOCK301 (composition)


    class SharedModelClean:
        def __init__(self):
            self._lock = threading.Lock()
            self._ewma = {}

        def observe(self, k, v):
            with self._lock:
                self._ewma[k] = v


    class Standalone:
        # lock owner NOT reachable from any threaded class: single-
        # threaded use, the composition rule must stay quiet on it
        def __init__(self):
            self._lock = threading.Lock()
            self._cache = {}

        def fill(self, k, v):
            self._cache[k] = v


    class Owner:
        def __init__(self):
            self.model = SharedModel()
            self.clean = SharedModelClean()
            self._t = threading.Thread(target=self.tick)

        def tick(self):
            self.model.observe("a", 1)
            self.clean.observe("a", 1)


    class Shard:
        # per-shard lock owner held in a container (ISSUE 17)
        def __init__(self):
            self._lock = threading.Lock()
            self.depth = 0
            self._timer = None

        def start(self):
            with self._lock:
                self._timer = threading.Timer(1.0, self.tick)
                self._timer.start()

        def tick(self):
            with self._lock:
                self.depth += 1


    class ShardedOwner:
        # writes reaching a shard through the container index must hold
        # the ELEMENT's lock, not (only) any owner-level lock
        def __init__(self):
            self._shards = [Shard() for _ in range(4)]
            self._t = threading.Thread(target=self.poke)

        def poke(self):
            self._shards[0].depth = 9          # LOCK301 (sharded)

        def poke_safe(self, i):
            with self._shards[i]._lock:
                self._shards[i].depth = 9


    class Coordinator:
        # drain leader must not nest the queue lock inside the drain
        # lock while submit nests them the other way round — the
        # coordinator deadlock shape (ISSUE 17)
        def __init__(self):
            self._qlock = threading.Lock()
            self._drain_lock = threading.Lock()
            self._t = threading.Thread(target=self.submit)

        def submit(self):
            with self._qlock:
                with self._drain_lock:
                    pass

        def drain(self):
            with self._drain_lock:
                with self._qlock:                  # LOCK304
                    pass


    class CoordinatorClean:
        # clean twin: releases each lock before taking the other (the
        # submit path never waits while holding the queue lock)
        def __init__(self):
            self._qlock = threading.Lock()
            self._drain_lock = threading.Lock()
            self._t = threading.Thread(target=self.submit)

        def submit(self):
            with self._qlock:
                pass
            with self._drain_lock:
                pass

        def drain(self):
            with self._drain_lock:
                pass
            with self._qlock:
                pass
"""


FIX_SHARD = """
    import jax
    import jax.numpy as jnp
    from jax.experimental.shard_map import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


    @jax.jit
    def plain_scatter_add(arr, idx, rows):
        # generic single-device scatter helper: fine on plain buffers
        return arr.at[idx].add(rows)


    def shard_planes(mesh, arr):
        return jax.device_put(arr, NamedSharding(mesh, P("nodes")))


    class DoubleApply:
        # seeded GSPMD double-apply reproduction: node planes pinned
        # to a NamedSharding, but the delta path still routes through
        # the plain jit scatter (the exact shape of the historical
        # sharded-operand bug — GSPMD may replicate the update and
        # apply it once per shard)
        def __init__(self, mesh, plane):
            self._plane = shard_planes(mesh, plane)

        def apply_delta(self, idx, rows):
            self._plane = plain_scatter_add(self._plane, idx, rows)


    class OwnerRouted:
        # clean twin: same sharded planes, scatter under shard_map
        # with owner masking
        def __init__(self, mesh, plane):
            self._mesh = mesh
            self._plane = shard_planes(mesh, plane)

        def apply_delta(self, idx, rows):
            def body(a_l, idx_, rows_):
                off = jax.lax.axis_index("nodes") * a_l.shape[0]
                loc = idx_ - off
                loc = jnp.where((loc >= 0) & (loc < a_l.shape[0]),
                                loc, a_l.shape[0])
                return a_l.at[loc].add(rows_, mode="drop")
            fn = shard_map(body, mesh=self._mesh,
                           in_specs=(P("nodes"), P(), P()),
                           out_specs=P("nodes"))
            self._plane = fn(self._plane, idx, rows)


    def naked_scatter_body(a_l, idx_, rows_):
        # SHARD402: no ownership mask — negative locals wrap into
        # another shard's rows
        return a_l.at[idx_].add(rows_)


    def masked_scatter_body(a_l, idx_, rows_):
        loc = jnp.where((idx_ >= 0) & (idx_ < a_l.shape[0]), idx_,
                        a_l.shape[0])
        return a_l.at[loc].add(rows_, mode="drop")


    def block_owner_body(a_l, idx_, rows_):
        # SHARD403: contiguous-block owner arithmetic breaks under the
        # elastic TileLayout remap
        owner = idx_ // a_l.shape[0]
        loc = jnp.where(owner == jax.lax.axis_index("nodes"),
                        idx_ - owner * a_l.shape[0], a_l.shape[0])
        return a_l.at[loc].add(rows_, mode="drop")


    def table_routed_body(a_l, slot_map, idx_, rows_):
        # clean twin: global rows routed through the owner/slot table
        loc = slot_map[idx_]
        return a_l.at[loc].add(rows_, mode="drop")


    def run_bodies(mesh, plane, slot_map, idx, rows):
        f = shard_map(naked_scatter_body, mesh=mesh,
                      in_specs=(P("nodes"), P(), P()),
                      out_specs=P("nodes"))
        g = shard_map(block_owner_body, mesh=mesh,
                      in_specs=(P("nodes"), P(), P()),
                      out_specs=P("nodes"))
        h = shard_map(masked_scatter_body, mesh=mesh,
                      in_specs=(P("nodes"), P(), P()),
                      out_specs=P("nodes"))
        k = shard_map(table_routed_body, mesh=mesh,
                      in_specs=(P("nodes"), P(), P(), P()),
                      out_specs=P("nodes"))
        return (f(plane, idx, rows) + g(plane, idx, rows)
                + h(plane, idx, rows) + k(plane, slot_map, idx, rows))
"""

FIX_ALIAS = """
    import functools

    import jax
    import numpy as np


    @functools.partial(jax.jit, donate_argnums=(0,))
    def donating_set(arr, rows):
        return arr.at[0].set(rows)


    def layer_one(buf, rows):
        return donating_set(buf, rows)


    def layer_two(state, rows):
        return layer_one(state, rows)


    def deep_dead_read(state, rows):
        # seeded PR-4 donated-carry reproduction, two wrapper hops
        # deep: JIT204's direct/one-hop scan cannot see this
        out = layer_two(state, rows)
        return out + state.sum()                       # ALIAS502


    def deep_live_read(state, rows):
        state = layer_two(state, rows)
        return state.sum()            # rebound to the result: fine


    class Planes:
        # seeded PR-5 reproduction: template planes shipped to device
        # WITHOUT a copy (np.asarray is identity-preserving), then
        # mutated host-side in place — through a zero-copy alias the
        # device carry sees both writes (the usage double-charge)
        def __init__(self, template):
            self._template = template
            self._dev = jax.device_put(np.asarray(self._template))

        def host_apply(self, rows):
            self._template[: rows.shape[0]] += rows    # ALIAS501


    class PlanesCopied:
        # clean twin: copy severs the alias at the boundary
        def __init__(self, template):
            self._template = template
            self._dev = jax.device_put(np.array(self._template))

        def host_apply(self, rows):
            self._template[: rows.shape[0]] += rows


    def local_alias_mutation(t):
        dev = jax.device_put(t)
        t[0] = 7                                       # ALIAS501
        return dev


    def local_copy_mutation(t):
        dev = jax.device_put(t.copy())
        t[0] = 7              # the device buffer owns a copy: fine
        return dev


    class EscapedAlias:
        def reset(self, used0):
            self._used = jax.device_put(used0)         # ALIAS503


    class EscapedAliasCopied:
        def reset(self, used0):
            self._used = jax.device_put(np.array(used0))
"""

FIX_SCORE_HOST = """
    import numpy as np

    f32 = np.float32


    def host_scores(avail, used, reserved, coll, penalty, aff_score,
                    desired):
        util_cpu = used + reserved
        util_mem = used + reserved
        denom_cpu = avail
        denom_mem = avail
        ok_denoms = (denom_cpu > 0) & (denom_mem > 0)
        free_cpu = f32(1.0) - util_cpu / np.maximum(denom_cpu, f32(1.0))
        free_mem = f32(1.0) - util_mem / np.maximum(denom_mem, f32(1.0))
        raw = f32(20.0) - (f32(10.0) ** free_cpu + f32(10.0) ** free_mem)
        binpack = np.where(ok_denoms,
                           np.clip(raw, f32(0.0), f32(18.0)) / f32(18.0),
                           f32(0.0))
        anti = np.where(coll > 0, -(coll + f32(1.0)) / desired,
                        f32(0.0))
        anti_counts = coll > 0
        pen_score = np.where(penalty, f32(-1.0), f32(0.0))
        aff_counts = aff_score != 0.0
        n_scorers = (f32(1.0) + anti_counts + penalty
                     + aff_counts).astype(f32)
        total = (binpack + anti + pen_score + aff_score) / n_scorers
        return total
"""

FIX_SCORE_SL = """
    import jax.numpy as jnp


    def sl_scores(avail, used, reserved, coll, penalty, aff, desired):
        util_cpu = used + reserved
        util_mem = used + reserved
        denom_cpu = avail
        denom_mem = avail
        ok_denoms = (denom_cpu > 0) & (denom_mem > 0)
        free_cpu = 1.0 - util_cpu / jnp.maximum(denom_cpu, 1.0)
        free_mem = 1.0 - util_mem / jnp.maximum(denom_mem, 1.0)
        raw = 20.0 - (10.0 ** free_cpu + 10.0 ** free_mem)
        binpack = jnp.where(ok_denoms,
                            jnp.clip(raw, 0.0, 18.0) / 18.0, 0.0)
        anti = jnp.where(coll > 0, -(coll + 1.0) / desired, 0.0)
        anti_counts = coll > 0
        pen_sc = jnp.where(penalty, -1.0, 0.0)
        aff_counts = aff != 0.0
        n_scorers = (1.0 + anti_counts + penalty + aff_counts)
        total = (binpack + anti + pen_sc + aff) / n_scorers
        return total
"""

FIX_SCORE_ROGUE = """
    import numpy as np


    def sneaky_bonus(binpack, anti):
        # SCORE602: combining registered score terms outside the
        # registered sites — a term added here exists in one backend
        tweak = binpack + anti
        return tweak


    def fine_single_term(binpack):
        x = binpack * 2.0     # one term: plumbing, not scoring
        return x
"""

FIX_SCORE_CC = """\
// fixpkg native scorer twin (fixture)
void score_all(int n) {
  // ---------- batched scoring ----------
  for (int i = 0; i < n; ++i) {
    const float denom_cpu = avail[i];
    const float denom_mem = avail[i];
    const float util_cpu = used[i] + reserved[i];
    const float util_mem = used[i] + reserved[i];
    const bool ok = denom_cpu > 0 && denom_mem > 0;
    const float free_cpu = 1.0f - util_cpu / std::max(denom_cpu, 1.0f);
    const float free_mem = 1.0f - util_mem / std::max(denom_mem, 1.0f);
    float raw = 20.0f - (std::pow(10.0f, free_cpu)
                         + std::pow(10.0f, free_mem));
    float binpack = 0.0f;
    if (ok) {
      raw = std::min(std::max(raw, 0.0f), 18.0f);
      binpack = raw / 18.0f;
    }
    const float anti = cl > 0 ? -(cl + 1.0f) / adesired : 0.0f;
    const float pen = penalty[i] ? -1.0f : 0.0f;
    const float n_scorers = 1.0f + (anti_cnt ? 1.0f : 0.0f)
                            + (pen_cnt ? 1.0f : 0.0f)
                            + (aff_cnt ? 1.0f : 0.0f);
    float total = (binpack + anti + pen + af) / n_scorers;
    score[i] = total;
  }
  // ---------- per-group top-k ----------
}
"""

FIX_ROBUST = """
    import logging
    import socket

    _log = logging.getLogger(__name__)


    def bad_swallow(sock):
        try:
            sock.send(b"x")
        except Exception:
            pass


    def bad_bare(sock):
        try:
            sock.send(b"x")
        except:
            pass


    def good_narrow(sock):
        try:
            sock.close()
        except OSError:
            pass


    def good_logged(sock):
        try:
            sock.send(b"x")
        except Exception:
            _log.warning("send failed")


    def good_reraise(sock):
        try:
            sock.send(b"x")
        except Exception:
            raise


    def good_bound_use(sock, sink):
        try:
            sock.send(b"x")
        except Exception as e:
            sink.last_error = str(e)
"""

FIX_OBS = """
    class _Reg:
        def incr_counter(self, key, value=1.0):
            pass

        def set_gauge(self, key, value):
            pass

        def record(self, name, value):
            pass

    metrics = _Reg()
    series_store = _Reg()


    def good_counter():
        metrics.incr_counter("worker.good_counter")


    def good_series():
        series_store.record("broker.ready_depth", 1.0)


    def bad_namespace():
        metrics.incr_counter("rogue.counter")          # OBS801


    def bad_shape():
        metrics.set_gauge("WorkerLatency", 1.0)        # OBS801


    def bad_dynamic(ev):
        metrics.set_gauge(f"worker.by_{ev}", 1.0)      # OBS802


    def bad_dynamic_ns(ev):
        metrics.set_gauge(f"rogue.{ev}", 1.0)          # OBS801 + 802


    def bad_var(name):
        metrics.incr_counter(name)                     # OBS802


    def bad_series():
        series_store.record("Broker.Depth", 1.0)       # OBS801


    def unrelated_record(log):
        log.record("not a metric at all")              # quiet
"""

FIX_SCORER_SITES = (
    ScorerSite("host", "python", "fixpkg.score_host:host_scores"),
    ScorerSite("shortlist", "python", "fixpkg.score_sl:sl_scores"),
    ScorerSite("native", "native",
               os.path.join("fixpkg", "native_score.cc")),
)

FIX_FILES = {
    "store.py": FIX_STORE,
    "fsm.py": FIX_FSM,
    "rogue.py": FIX_ROGUE,
    "jitmod.py": FIX_JIT,
    "locks.py": FIX_LOCKS,
    "shardmod.py": FIX_SHARD,
    "aliasmod.py": FIX_ALIAS,
    "score_host.py": FIX_SCORE_HOST,
    "score_sl.py": FIX_SCORE_SL,
    "score_rogue.py": FIX_SCORE_ROGUE,
    "native_score.cc": FIX_SCORE_CC,
    "recov.py": FIX_ROBUST,
    "obsmod.py": FIX_OBS,
}

FIX_CFG = AnalysisConfig(
    fsm_roots=("fixpkg.fsm:FSM.apply", "fixpkg.fsm:FSM._ap_*"),
    store_module="fixpkg.store",
    store_class="FakeStore",
    lock_module_prefixes=("fixpkg",),
    scatter_helpers=(),
    scorer_sites=FIX_SCORER_SITES,
    robust_module_prefixes=("fixpkg",),
    obs_metric_prefixes=("worker", "broker"),
)


@pytest.fixture(scope="module")
def fixture_report(tmp_path_factory):
    root = write_fixture(tmp_path_factory.mktemp("lintfix"), FIX_FILES)
    return analyze(package_dir=root, package_name="fixpkg",
                   use_baseline=False, config=FIX_CFG)


def _keys(report, rule):
    return {f.key for f in report.findings if f.rule == rule}


# ---------------------------------------------------------- FSM pass
def test_fsm_wall_clock_detected(fixture_report):
    assert _keys(fixture_report, "FSM101") == {
        "FSM101:fixpkg.store:FakeStore.stamp_thing:time.time"}


def test_fsm_randomness_detected(fixture_report):
    assert _keys(fixture_report, "FSM102") == {
        "FSM102:fixpkg.store:FakeStore.tag_thing:uuid.uuid4"}


def test_fsm_set_iteration_detected_sorted_twin_clean(fixture_report):
    keys = _keys(fixture_report, "FSM103")
    assert any("shuffle_thing" in k for k in keys)
    # the sorted() twin in upsert_thing must NOT fire
    assert not any("upsert_thing" in k for k in keys)


def test_fsm_out_of_band_mutation_detected(fixture_report):
    keys = _keys(fixture_report, "FSM104")
    assert keys == {
        "FSM104:fixpkg.rogue:sneak_write:FakeStore.upsert_thing"}


# ---------------------------------------------------------- jit pass
def test_jit_host_effects_detected_clean_twin_quiet(fixture_report):
    keys = _keys(fixture_report, "JIT201")
    assert "JIT201:fixpkg.jitmod:noisy_kernel:print" in keys
    assert any(k.startswith("JIT201:fixpkg.jitmod:noisy_kernel:_log")
               for k in keys)
    assert not any(":good_kernel:" in k for k in keys)


def test_jit_global_mutation_detected(fixture_report):
    assert _keys(fixture_report, "JIT202") == {
        "JIT202:fixpkg.jitmod:leaky_kernel:_CACHE"}


def test_jit_retrace_hazard_detected_static_twin_quiet(fixture_report):
    keys = _keys(fixture_report, "JIT203")
    assert keys == {"JIT203:fixpkg.jitmod:branchy_kernel:flag",
                    "JIT203:fixpkg.jitmod:loopy_kernel:n"}


def test_jit_for_range_static_twin_quiet(fixture_report):
    """`for _ in range(n)` with n static (the shortlist_c pattern) must
    stay quiet; a traced bound fires (asserted above)."""
    keys = _keys(fixture_report, "JIT203")
    assert not any(":loopy_static:" in k for k in keys)


def test_jit_donated_read_detected_rebind_twin_quiet(fixture_report):
    keys = _keys(fixture_report, "JIT204")
    assert "JIT204:fixpkg.jitmod:bad_caller:arr" in keys
    assert "JIT204:fixpkg.jitmod:bad_carry_reader:carry" in keys
    # + the aliased eviction-plane carry + the unbound lane carry
    # (both donated usage planes of the lane twin fire)
    assert len(keys) == 5


def test_jit_donated_lane_carry_tuple_rebind_quiet(fixture_report):
    """ISSUE 20: the scan-of-vmap carry rebind — BOTH donated usage
    buffers rebound by one tuple-target assign from the lane kernel's
    flat result tuple — must stay quiet; the twin that binds the
    results to fresh names while the donated attributes are read
    again fires."""
    keys = _keys(fixture_report, "JIT204")
    assert not any(".good_lane_solve:" in k for k in keys)
    assert "JIT204:fixpkg.jitmod:LaneCarry.bad_lane_solve:self._used" \
        in keys


def test_jit_donated_alias_carry_detected_twin_quiet(fixture_report):
    """ISSUE 7: a buffer donated through a local alias of an attribute
    dict (`dn = self._dev_node; donating(dn["ev_prio"], ...)`) is dead
    through the attribute spelling too; the alias-rebind twin is
    quiet."""
    keys = _keys(fixture_report, "JIT204")
    assert any(".bad_ev_carry_reader:" in k for k in keys)
    assert not any(".good_ev_carry_reader:" in k for k in keys)


def test_jit_collective_outside_mesh_detected(fixture_report):
    """JIT205: collectives in a plain jit root are flagged; the
    shard_map body, a helper reachable from it, and a
    functools.partial-wrapped body are all exempt (ISSUE 5)."""
    keys = _keys(fixture_report, "JIT205")
    assert any(k.startswith("JIT205:fixpkg.jitmod:meshless_kernel:")
               for k in keys)
    assert all(":meshy_body:" not in k and ":meshy_helper:" not in k
               and ":meshy_partial_body:" not in k for k in keys)


def test_jit_collective_axis_not_bound_by_mesh_detected(fixture_report):
    """ISSUE 8: under a statically-resolvable ("hosts", "chips") mesh,
    a collective naming an axis the ENCLOSING context does not bind is
    flagged; literal and module-constant spellings of the bound axes
    are quiet, and a mesh passed in as a parameter (run_meshy) keeps
    the axis check silent rather than guessing."""
    keys = _keys(fixture_report, "JIT205")
    assert any(":wrong_axis_body:" in k for k in keys)
    assert all(":two_tier_body:" not in k for k in keys)


def test_jit_three_tier_helper_mesh_axes_resolved(fixture_report):
    """ISSUE 13: a mesh built by an internal helper
    (make_three_tier_mesh style — `mesh=make_region_mesh(devs)`)
    resolves one return level deep, so all three
    ("regions", "hosts", "chips") axes count as bound and the
    three-tier body stays quiet."""
    keys = _keys(fixture_report, "JIT205")
    assert all(":three_tier_body:" not in k for k in keys)
    assert all(":run_three_tier:" not in k for k in keys)


def test_jit_inner_only_axis_flagged(fixture_report):
    """ISSUE 13: a body wrapped by BOTH a three-tier context and a
    two-tier context only provably binds the intersection of their
    axes — its "regions" psum trace-fails on the outer path and is
    flagged even though the inner context binds it."""
    keys = _keys(fixture_report, "JIT205")
    assert any(":inner_only_body:" in k for k in keys)


def test_jit_donated_carry_subscript_detected(fixture_report):
    """Subscript reads through a donated carry name are dead-buffer
    reads too (the wave-loop carry shape); the rebind twin is quiet."""
    keys = _keys(fixture_report, "JIT204")
    assert "JIT204:fixpkg.jitmod:bad_carry_reader:carry" in keys
    assert not any(":good_carry_reader:" in k for k in keys)


# --------------------------------------------------------- lock pass
def test_lock_unguarded_write_detected_clean_twin_quiet(fixture_report):
    keys = _keys(fixture_report, "LOCK301")
    assert keys == {
        "LOCK301:fixpkg.locks:Chatty.start:_worker",
        "LOCK301:fixpkg.locks:SharedModel.observe:_ewma",
        "LOCK301:fixpkg.locks:ShardedOwner.poke:_shards[].depth",
    }


def test_lock_sharded_container_write_detected_locked_twin_quiet(
        fixture_report):
    """ISSUE 17: `self._shards[i].attr = v` in a thread-shared owner
    must hold the element Shard's own lock; the subscripted
    `with self._shards[i]._lock:` twin is quiet, and the shard's own
    locked methods stay quiet."""
    keys = _keys(fixture_report, "LOCK301")
    assert "LOCK301:fixpkg.locks:ShardedOwner.poke:_shards[].depth" \
        in keys
    assert not any(":ShardedOwner.poke_safe:" in k for k in keys)
    assert not any(":Shard." in k for k in keys)


def test_lock_composition_reaches_controller_state(fixture_report):
    """ISSUE 6: a lock-owning helper held by a threaded class carries
    LOCK301 even though it never starts a thread itself; the locked
    twin and the unreachable standalone owner stay quiet."""
    keys = _keys(fixture_report, "LOCK301")
    assert "LOCK301:fixpkg.locks:SharedModel.observe:_ewma" in keys
    assert not any(":SharedModelClean." in k for k in keys)
    assert not any(":Standalone." in k for k in keys)


def test_lock_racy_getter_detected(fixture_report):
    keys = _keys(fixture_report, "LOCK302")
    assert "LOCK302:fixpkg.locks:Chatty.enabled:_enabled" in keys
    assert not any(":Quiet." in k for k in keys)


def test_lock_global_mutation_detected_guarded_twin_quiet(
        fixture_report):
    keys = _keys(fixture_report, "LOCK303")
    assert "LOCK303:fixpkg.locks:fill:_G" in keys
    # the module-lock-guarded twin stays quiet
    assert not any(":fill_safe:" in k for k in keys)
    # (leaky_kernel's global write legitimately fires here too — a jit
    # closure mutating a module global is both a purity and a lock
    # problem)


def test_lock_ordering_cycle_detected(fixture_report):
    keys = _keys(fixture_report, "LOCK304")
    assert any("TwoLocks._a" in k for k in keys)


def test_lock_coordinator_order_cycle_detected_clean_twin_quiet(
        fixture_report):
    """ISSUE 17 coordinator shape: submit nests queue->drain while
    drain nests drain->queue — a deadlock the moment a drain leader
    waits while a submitter holds the queue lock.  The clean twin
    releases each lock before taking the other and stays quiet."""
    keys = _keys(fixture_report, "LOCK304")
    assert any("Coordinator._drain_lock" in k or
               "Coordinator._qlock" in k for k in keys)
    assert not any("CoordinatorClean." in k for k in keys)
    assert len(keys) == 2


# -------------------------------------------------------- shard pass
def test_shard_double_apply_detected_owner_routed_quiet(fixture_report):
    """Seeded GSPMD double-apply reproduction: NamedSharding-pinned
    planes updated through the plain jit scatter helper fire SHARD401;
    the owner-routed shard_map twin is quiet."""
    keys = _keys(fixture_report, "SHARD401")
    assert any(":DoubleApply.apply_delta:" in k for k in keys)
    assert not any(":OwnerRouted." in k for k in keys)


def test_shard_helper_itself_not_flagged(fixture_report):
    """The generic scatter helper is fine on plain buffers — only the
    sharded-operand CALL SITE is the bug."""
    keys = _keys(fixture_report, "SHARD401")
    assert not any(":plain_scatter_add:" in k for k in keys)


def test_shard_maskfree_scatter_detected_masked_quiet(fixture_report):
    keys = _keys(fixture_report, "SHARD402")
    assert any(":naked_scatter_body:" in k for k in keys)
    assert not any(":masked_scatter_body:" in k for k in keys)
    assert not any(":table_routed_body:" in k for k in keys)


def test_shard_block_arithmetic_detected_table_quiet(fixture_report):
    keys = _keys(fixture_report, "SHARD403")
    assert any(":block_owner_body:" in k for k in keys)
    assert not any(":table_routed_body:" in k for k in keys)
    assert not any(":masked_scatter_body:" in k for k in keys)


# -------------------------------------------------------- alias pass
def test_alias_uncopied_put_mutation_detected_copy_quiet(
        fixture_report):
    """Seeded PR-5 reproduction: template shipped via np.asarray
    (identity-preserving) then mutated in place fires ALIAS501 at the
    mutation site; the np.array twin is quiet."""
    keys = _keys(fixture_report, "ALIAS501")
    assert any(":Planes.host_apply:" in k for k in keys)
    assert not any(":PlanesCopied." in k for k in keys)


def test_alias_local_order_detected_copy_quiet(fixture_report):
    keys = _keys(fixture_report, "ALIAS501")
    assert any(":local_alias_mutation:" in k for k in keys)
    assert not any(":local_copy_mutation:" in k for k in keys)


def test_alias_deep_donated_read_detected_rebind_quiet(fixture_report):
    """Seeded PR-4 donated-carry reproduction, two wrapper hops deep:
    the dataflow donation fixpoint reaches it (JIT204 cannot), and the
    rebind twin is quiet."""
    a_keys = _keys(fixture_report, "ALIAS502")
    j_keys = _keys(fixture_report, "JIT204")
    assert any(":deep_dead_read:" in k for k in a_keys)
    assert not any(":deep_live_read:" in k for k in a_keys)
    # JIT204's direct scan does NOT see the two-hop chain...
    assert not any(":deep_dead_read:" in k for k in j_keys)
    # ...and ALIAS502 never re-reports what JIT204 already covers
    assert not any(":bad_caller:" in k or ":bad_carry_reader:" in k
                   for k in a_keys)


def test_alias_escaped_param_put_detected_copy_quiet(fixture_report):
    keys = _keys(fixture_report, "ALIAS503")
    assert any(":EscapedAlias.reset:" in k for k in keys)
    assert not any(":EscapedAliasCopied." in k for k in keys)


def test_alias_warn_tier():
    from nomad_tpu.analysis import severity_of
    assert severity_of("ALIAS503") == "warn"
    assert severity_of("ALIAS501") == "error"
    assert severity_of("SHARD401") == "error"


# -------------------------------------------------------- score pass
def test_score_backends_agree_on_clean_fixture(fixture_report):
    """The host / shortlist / native fixture twins are float-op
    identical after canonicalization: no drift findings."""
    assert _keys(fixture_report, "SCORE601") == set()
    assert _keys(fixture_report, "SCORE603") == set()


def test_score_rogue_arithmetic_detected_single_term_quiet(
        fixture_report):
    keys = _keys(fixture_report, "SCORE602")
    assert any(":sneaky_bonus:" in k for k in keys)
    assert not any(":fine_single_term:" in k for k in keys)


@pytest.mark.parametrize("mutation, desc", [
    (("18.0", "17.0"), "perturbed clip constant"),
    (("20.0 - ", "20.0 + "), "perturbed raw sign"),
    ((") / n_scorers", ") * n_scorers"), "perturbed normalization op"),
    (("-(coll + 1.0) / desired", "-(coll + 1.0) * desired"),
     "perturbed anti op"),
])
def test_score_perturbing_one_float_op_fails(tmp_path, mutation, desc):
    """Acceptance: deliberately perturbing ONE float op/constant in a
    single backend fixture makes the drift check fail."""
    old, new = mutation
    assert old in textwrap.dedent(FIX_SCORE_SL)
    files = dict(FIX_FILES)
    files["score_sl.py"] = FIX_SCORE_SL.replace(old, new)
    root = write_fixture(tmp_path, files)
    rep = analyze(package_dir=root, package_name="fixpkg",
                  use_baseline=False, config=FIX_CFG)
    keys = _keys(rep, "SCORE601")
    assert any(":shortlist:" in k for k in keys), desc


def test_score_perturbing_native_backend_fails(tmp_path):
    files = dict(FIX_FILES)
    files["native_score.cc"] = FIX_SCORE_CC.replace(
        "raw / 18.0f", "raw / 16.0f")
    root = write_fixture(tmp_path, files)
    rep = analyze(package_dir=root, package_name="fixpkg",
                  use_baseline=False, config=FIX_CFG)
    assert any(":native:" in k and ":binpack" in k
               for k in _keys(rep, "SCORE601"))


def test_score_stale_registry_site_reported(tmp_path):
    files = dict(FIX_FILES)
    root = write_fixture(tmp_path, files)
    cfg = AnalysisConfig(
        fsm_roots=FIX_CFG.fsm_roots, store_module="fixpkg.store",
        store_class="FakeStore", lock_module_prefixes=("fixpkg",),
        scatter_helpers=(),
        scorer_sites=FIX_SCORER_SITES + (
            ScorerSite("ghost", "python", "fixpkg.gone:no_such"),))
    rep = analyze(package_dir=root, package_name="fixpkg",
                  use_baseline=False, config=cfg)
    keys = _keys(rep, "SCORE603")
    assert any(k.endswith(":ghost") for k in keys)


# ----------------------------------------------------- baseline rules
# ------------------------------------------------------- robust pass
def test_robust_swallowed_exception_detected(fixture_report):
    keys = _keys(fixture_report, "ROBUST701")
    assert "ROBUST701:fixpkg.recov:bad_swallow:Exception" in keys
    assert "ROBUST701:fixpkg.recov:bad_bare:bare" in keys


def test_robust_handled_twins_quiet(fixture_report):
    """Narrow except, logged, re-raised and bound-and-used handlers
    must stay quiet — only silent broad catches fire."""
    keys = _keys(fixture_report, "ROBUST701")
    assert not any(":good_" in k for k in keys), keys


def test_robust_error_tier():
    from nomad_tpu.analysis import pass_of, severity_of
    assert severity_of("ROBUST701") == "error"
    assert pass_of("ROBUST701") == "robust"


@functools.lru_cache(maxsize=1)
def _repo_report():
    """analyze() over the real package, once per session: four gates
    below read the same read-only report (a full pass is ~12 s)."""
    return analyze()


def test_repo_robust_zero_unsuppressed():
    """The recovery-critical planes carry zero unsuppressed swallowed
    exceptions; deliberate probe/trace fallbacks are baselined with
    justifications."""
    rep = _repo_report()
    bad = [f for f in rep.findings if f.rule.startswith("ROBUST")]
    assert not bad, "\n".join(f.render() for f in bad)


# ---------------------------------------------------------- obs pass
def test_obs_literal_name_hygiene_detected(fixture_report):
    keys = _keys(fixture_report, "OBS801")
    assert "OBS801:fixpkg.obsmod:bad_namespace:rogue.counter" in keys
    assert "OBS801:fixpkg.obsmod:bad_shape:WorkerLatency" in keys
    assert "OBS801:fixpkg.obsmod:bad_series:Broker.Depth" in keys


def test_obs_dynamic_name_detected_with_pattern_keys(fixture_report):
    """f-strings keep their literal runs in the baseline key;
    fully-opaque names collapse to <dynamic>."""
    keys = _keys(fixture_report, "OBS802")
    assert "OBS802:fixpkg.obsmod:bad_dynamic:worker.by_*" in keys
    assert "OBS802:fixpkg.obsmod:bad_dynamic_ns:rogue.*" in keys
    assert "OBS802:fixpkg.obsmod:bad_var:<dynamic>" in keys


def test_obs_dynamic_unregistered_namespace_is_also_error(fixture_report):
    """A literal-prefix f-string under an unregistered namespace gets
    the namespace error on top of the cardinality warn."""
    assert "OBS801:fixpkg.obsmod:bad_dynamic_ns:rogue.*" in \
        _keys(fixture_report, "OBS801")


def test_obs_clean_sites_quiet(fixture_report):
    keys = _keys(fixture_report, "OBS801") | \
        _keys(fixture_report, "OBS802")
    assert not any(":good_" in k or ":unrelated_" in k for k in keys), \
        keys


def test_obs_tiers():
    from nomad_tpu.analysis import pass_of, severity_of
    assert severity_of("OBS801") == "error"
    assert severity_of("OBS802") == "warn"
    assert pass_of("OBS801") == "obs"


def test_repo_obs_zero_unsuppressed():
    """Every metric/series name in the real package is a registered
    lowercase dotted literal; the bounded dynamic sites carry baseline
    justifications naming the bound."""
    rep = _repo_report()
    bad = [f for f in rep.findings if f.rule.startswith("OBS")]
    assert not bad, "\n".join(f.render() for f in bad)


def test_baseline_requires_justification():
    with pytest.raises(BaselineError):
        parse_baseline_text(
            'version = 1\n[[suppress]]\nrule = "FSM101"\n'
            'key = "FSM101:m:f:time.time"\n')
    with pytest.raises(BaselineError):
        parse_baseline_text(
            '[[suppress]]\nrule = "FSM101"\n'
            'key = "FSM101:m:f:time.time"\njustification = "  "\n')


def test_baseline_suppresses_matching_finding(tmp_path):
    root = write_fixture(tmp_path, {"store.py": FIX_STORE,
                                    "fsm.py": FIX_FSM})
    bl = parse_baseline_text(
        '[[suppress]]\nrule = "FSM101"\n'
        'key = "FSM101:fixpkg.store:FakeStore.stamp_thing:*"\n'
        'justification = "fixture"\n')
    rep = analyze(package_dir=root, package_name="fixpkg",
                  baseline=bl, config=FIX_CFG)
    assert not _keys(rep, "FSM101")
    assert any(f.rule == "FSM101" for f in rep.suppressed)
    assert rep.stale_baseline_keys == []


# -------------------------------------------------- the real package
def test_repo_baseline_is_valid_and_fresh():
    bl = load_baseline(default_baseline_path())   # raises on missing
    assert all(e.get("justification", "").strip()  # justifications
               for e in bl.entries)


def test_repo_has_zero_unsuppressed_findings():
    """The tier-1 gate: any new unsuppressed finding fails the suite.
    Fix the code or add a JUSTIFIED baseline entry."""
    rep = _repo_report()
    assert rep.ok, "unsuppressed nomadlint findings:\n" + "\n".join(
        f.render() for f in rep.findings)
    # and the baseline itself must not rot
    assert rep.stale_baseline_keys == [], (
        "baseline entries matching nothing (remove them): "
        f"{rep.stale_baseline_keys}")


def test_repo_index_sanity():
    """The call graph actually resolved the load-bearing edges (guards
    against the passes going silently blind after a refactor)."""
    import nomad_tpu
    pkg_dir = os.path.dirname(os.path.dirname(
        os.path.abspath(nomad_tpu.__file__)))
    idx = PackageIndex.build(pkg_dir, "nomad_tpu")
    apply_key = "nomad_tpu.raft.fsm:StateFSM._ap_node_upsert"
    assert ("nomad_tpu.state.store:StateStore.upsert_node"
            in idx.callees(apply_key))
    reach = idx.reachable([apply_key])
    assert "nomad_tpu.state.store:StateStore._bump_locked" in reach


def test_repo_scorer_registry_resolves_all_backends():
    """SCORE6xx v3 on the real tree: the spec registry parses, the
    spec reference fingerprints every core term, every registered
    backend resolves, the hand backends (shortlist / pallas / native)
    match the SPEC fingerprints, and the spec-driven backends (host /
    kernel twins) fingerprint EMPTY — all their float ops live in
    score_spec (guards the registry against going silently blind)."""
    import nomad_tpu
    from nomad_tpu.analysis.score_pass import (
        native_fingerprint, python_fingerprint, spec_reference)
    pkg_dir = os.path.dirname(os.path.dirname(
        os.path.abspath(nomad_tpu.__file__)))
    idx = PackageIndex.build(pkg_dir, "nomad_tpu")
    terms_reg, spec_prints, names_map, const_set_groups, errors = \
        spec_reference(idx)
    assert terms_reg and not errors, errors
    core = ("free", "binpack", "anti", "pen", "n_scorers", "total")
    for group in core + ("spread", "learned"):
        assert group in spec_prints, group
    assert "spread" in const_set_groups
    by_backend = {s.backend: s for s in DEFAULT_SCORER_SITES}
    assert set(by_backend) == {"spec", "host", "kernel", "shortlist",
                               "pallas", "native"}
    all_groups = tuple(names_map)
    for backend in ("shortlist", "pallas", "native"):
        site = by_backend[backend]
        if site.kind == "python":
            fkeys = idx.match_funcs([site.site])
            assert fkeys, f"scorer site gone: {site.site}"
            fp = python_fingerprint(idx, idx.functions[fkeys[0]],
                                    all_groups, names_map)
        else:
            path = os.path.join(pkg_dir, site.site)
            assert os.path.exists(path), path
            fp = native_fingerprint(path, all_groups, names_map)
        for group in core:
            assert group in fp, (backend, group)
            assert (fp[group].consts, fp[group].ops) == \
                (spec_prints[group].consts,
                 spec_prints[group].ops), (backend, group)
        assert set(fp["spread"].const_set) == \
            set(spec_prints["spread"].const_set), backend
        # the learned term flows to the driven backends only
        assert "learned" not in fp, backend
    for backend in ("host", "kernel"):
        site = by_backend[backend]
        assert site.kind == "driven"
        fkeys = idx.match_funcs([site.site])
        assert fkeys, f"driven site gone: {site.site}"
        fp = python_fingerprint(idx, idx.functions[fkeys[0]],
                                all_groups, names_map)
        assert all(tp.empty() for tp in fp.values()), (backend, fp)


def test_repo_new_passes_have_no_unsuppressed_findings():
    """Zero-unsuppressed gate extension for SHARD4xx/ALIAS5xx/SCORE6xx
    specifically (the combined gate above covers everything; this one
    localizes a regression to the new passes)."""
    rep = _repo_report()
    new = [f for f in rep.findings
           if f.rule.startswith(("SHARD", "ALIAS", "SCORE"))]
    assert not new, "\n".join(f.render() for f in new)


# ------------------------------------------- baseline freshness tools
def test_stale_baseline_nearest_miss_suggested(tmp_path):
    """A renamed function strands its baseline entry; the freshness
    check must name the nearest current key so the rename is obvious."""
    root = write_fixture(tmp_path, {"store.py": FIX_STORE,
                                    "fsm.py": FIX_FSM})
    bl = parse_baseline_text(
        '[[suppress]]\nrule = "FSM101"\n'
        'key = "FSM101:fixpkg.store:FakeStore.stamp_thing_old:time.time"\n'
        'justification = "fixture"\n')
    rep = analyze(package_dir=root, package_name="fixpkg",
                  baseline=bl, config=FIX_CFG)
    key = "FSM101:fixpkg.store:FakeStore.stamp_thing_old:time.time"
    assert rep.stale_baseline_keys == [key]
    assert rep.stale_suggestions[key] == \
        "FSM101:fixpkg.store:FakeStore.stamp_thing:time.time"


def test_prune_stale_rewrites_baseline(tmp_path):
    """--prune-stale drops dead entries, keeps live ones (with their
    justifications), and the rewritten file round-trips the loader."""
    from nomad_tpu.analysis.baseline import Baseline
    bl = parse_baseline_text(
        '[[suppress]]\nrule = "FSM101"\n'
        'key = "FSM101:live:*"\njustification = "keep me"\n'
        '[[suppress]]\nrule = "FSM102"\n'
        'key = "FSM102:dead:*"\njustification = "stale"\n')
    pruned = bl.without(["FSM102:dead:*"])
    path = tmp_path / "baseline.toml"
    pruned.save(str(path))
    reloaded = load_baseline(str(path))
    assert reloaded.keys() == ["FSM101:live:*"]
    assert reloaded.entries[0]["justification"] == "keep me"


# ------------------------------------------------------ CLI contract
def test_cli_exit_contract_clean_tree():
    """Exit 0 on the real tree (everything baselined), both plain and
    --json."""
    from nomad_tpu.analysis.__main__ import main
    assert main([]) == 0


def test_cli_no_baseline_json_reports_but_does_not_fail(capsys):
    """The historical flag-interaction bug: `--no-baseline --json`
    must LIST baseline-suppressed findings (tagged) but exit by the
    baseline-aware verdict — a clean tree stays exit 0."""
    import json as _json
    from nomad_tpu.analysis.__main__ import main
    rc = main(["--no-baseline", "--json"])
    out = _json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["exit_code"] == 0
    assert out["suppressed"] > 0
    listed = out["unsuppressed"]
    assert listed and all(f["baselined"] for f in listed)
    assert all(f["severity"] in ("error", "warn") for f in listed)
    assert all("pass" in f for f in listed)


def test_cli_paths_incremental_mode(capsys):
    """--paths (pre-commit mode) scopes REPORTING to the named files
    while still indexing the whole package — kernel.py's collectives
    are only JIT205-clean because their mesh-root callers in OTHER
    files are visible, so a partial index would manufacture findings.
    SCORE603/SCORE604 (whole-package judgments) are muted, and
    --prune-stale is refused outright."""
    from nomad_tpu.analysis.__main__ import main
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    kern = os.path.join(repo, "nomad_tpu", "solver", "kernel.py")
    assert main(["--paths", kern]) == 0
    out = capsys.readouterr()
    assert "JIT205" not in out.out            # full-index reachability
    assert "stale baseline" not in out.err    # stale warnings muted
    assert main(["--paths", kern, "--prune-stale"]) == 2
    assert "whole-package view" in capsys.readouterr().err


def test_paths_mode_drops_whole_package_rules(tmp_path):
    """analyze(paths=...) scoping: a drifted shortlist twin keeps its
    per-file SCORE601, while whole-package judgments (SCORE603 for the
    registry rows the partial file set can't see, SCORE604) and
    findings in unlisted files are dropped."""
    root = write_fixture(tmp_path, {
        "score_sl.py": FIX_SCORE_SL.replace("/ 18.0", "/ 16.0"),
        "score_host.py": FIX_SCORE_HOST,
        "native_score.cc": FIX_SCORE_CC})
    rep = analyze(package_dir=root, package_name="fixpkg",
                  use_baseline=False, config=FIX_CFG,
                  paths=[os.path.join(root, "fixpkg", "score_sl.py")])
    assert rep.findings                    # the SL drift still reported
    assert all(f.rule not in ("SCORE603", "SCORE604")
               for f in rep.findings)
    assert all(os.path.normpath(f.path).endswith(
        os.path.join("fixpkg", "score_sl.py")) for f in rep.findings)


def test_nomadlint_console_script_declared():
    """The packaged entry point must keep pointing at the CLI main —
    `nomadlint` from a shell is the documented pre-commit invocation."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "pyproject.toml")) as f:
        toml = f.read()
    assert 'nomadlint = "nomad_tpu.analysis.__main__:main"' in toml


# ------------------------------------------------ race pass (pass 9)
FIX_RACE = """
    import threading
    import time


    class Unguarded:                        # RACE901: no common guard
        def __init__(self):
            self._lock = threading.Lock()
            self.table = {}

        def start(self):
            threading.Thread(target=self._run, daemon=True).start()

        def _run(self):
            with self._lock:
                self.table["tick"] = 1      # guarded here...

        def put(self, k, v):
            self.table[k] = v               # ...lockless here (RACE901)


    class GuardedTwin:
        def __init__(self):
            self._lock = threading.Lock()
            self.table = {}

        def start(self):
            threading.Thread(target=self._run, daemon=True).start()

        def _run(self):
            with self._lock:
                self.table["tick"] = 1

        def put(self, k, v):
            with self._lock:
                self.table[k] = v


    class SplitLocks:                       # RACE902: inconsistent guard
        def __init__(self):
            self._la = threading.Lock()
            self._lb = threading.Lock()
            self.mode = "idle"

        def start(self):
            threading.Thread(target=self._run, daemon=True).start()

        def _run(self):
            with self._la:
                self.mode = "running"

        def set_mode(self, m):
            with self._lb:                  # wrong lock (RACE902)
                self.mode = m


    class OneLockTwin:
        def __init__(self):
            self._la = threading.Lock()
            self.mode = "idle"

        def start(self):
            threading.Thread(target=self._run, daemon=True).start()

        def _run(self):
            with self._la:
                self.mode = "running"

        def set_mode(self, m):
            with self._la:
                self.mode = m


    class Reacquire:                        # RACE903: check-then-act
        def __init__(self):
            self._lock = threading.Lock()
            self.slots = {}

        def start(self):
            threading.Thread(target=self._run, daemon=True).start()

        def _run(self):
            with self._lock:
                self.slots["w"] = 0

        def claim(self, k):
            with self._lock:
                if k in self.slots:         # check under one hold...
                    return False
            with self._lock:
                self.slots[k] = True        # ...act under another
            return True


    class _ShardRepro:
        '''Seeded PR-17 shape: the nack timer validated the delivery
        token under the shard lock, dropped it, then requeued the eval
        under a second hold — the unacked-table entry can be acked or
        re-delivered in between.  RACE903 must catch this.'''

        def __init__(self):
            self._lock = threading.Lock()
            self._unack = {}

        def track(self, eval_id, token):
            with self._lock:
                self._unack[eval_id] = token
            t = threading.Timer(0.01, self._nack_timeout,
                                args=(eval_id, token))
            t.daemon = True
            t.start()

        def _nack_timeout(self, eval_id, token):
            with self._lock:
                tok = self._unack.get(eval_id)
                if tok != token:
                    return                  # check under one hold...
            with self._lock:
                self._unack.pop(eval_id, None)   # ...act under another


    class SingleHoldTwin:
        def __init__(self):
            self._lock = threading.Lock()
            self._unack = {}

        def track(self, eval_id, token):
            with self._lock:
                self._unack[eval_id] = token
            t = threading.Timer(0.01, self._nack_timeout,
                                args=(eval_id, token))
            t.daemon = True
            t.start()

        def _nack_timeout(self, eval_id, token):
            with self._lock:                # one hold: check AND act
                if self._unack.get(eval_id) == token:
                    self._unack.pop(eval_id, None)


    class SleepyHolder:                     # LOCK305: blocking under lock
        def __init__(self):
            self._lock = threading.Lock()
            self.beat = 0

        def start(self):
            threading.Thread(target=self._run, daemon=True).start()

        def _run(self):
            with self._lock:
                self.beat = self.beat + 1
                time.sleep(0.05)            # LOCK305 (direct)

        def flush(self):
            with self._lock:
                self._sync()                # LOCK305 (entry-propagated)

        def _sync(self):
            time.sleep(0.05)


    class PoliteSleeper:                    # clean twin: sleep outside
        def __init__(self):
            self._lock = threading.Lock()
            self.beat = 0

        def start(self):
            threading.Thread(target=self._run, daemon=True).start()

        def _run(self):
            with self._lock:
                self.beat = self.beat + 1
            time.sleep(0.05)


    def finish_round(pending):              # blocking BY CONTRACT via
        return pending                      # the config's blocking_roots


    class FetchUnderLock:                   # LOCK305: future-wait held
        def __init__(self):
            self._lock = threading.Lock()
            self.pending = None

        def start(self):
            threading.Thread(target=self.harvest, daemon=True).start()

        def harvest(self):
            with self._lock:
                out = finish_round(self.pending)  # LOCK305 (root)
                self.pending = None
            return out


    class FetchOutsideLock:                 # clean twin: snapshot under
        def __init__(self):                 # the lock, fetch after it
            self._lock = threading.Lock()
            self.pending = None

        def start(self):
            threading.Thread(target=self.harvest, daemon=True).start()

        def harvest(self):
            with self._lock:
                pending, self.pending = self.pending, None
            return finish_round(pending)
"""

# The race pass owns this fixture package outright: the lock pass is
# scoped away so RACE findings are not deduped against LOCK301 and the
# per-rule sets below stay exact.  scorer_sites=() leaves the score
# pass without a spec row — it emits one SCORE603 registry complaint,
# which the per-rule assertions ignore.
RACE_CFG = AnalysisConfig(
    race_module_prefixes=("racepkg",),
    lock_module_prefixes=(),
    fsm_roots=(),
    scorer_sites=(),
    # fixture-local stand-in for the package's fetch/future-wait entry
    # points (finish_stream / PendingSolve.wait / fleet_finish)
    blocking_roots=("racepkg.racemod:finish_round",),
)


@pytest.fixture(scope="module")
def race_report(tmp_path_factory):
    root = write_fixture(tmp_path_factory.mktemp("racefix"),
                         {"racemod.py": FIX_RACE}, pkg_name="racepkg")
    return analyze(package_dir=root, package_name="racepkg",
                   use_baseline=False, config=RACE_CFG)


def test_race_unguarded_write_detected_guarded_twin_clean(race_report):
    """RACE901: a thread-shared attr with an empty guard intersection
    and a lockless write; the twin guarding every write is quiet."""
    assert _keys(race_report, "RACE901") == {
        "RACE901:racepkg.racemod:Unguarded.put:table"}


def test_race_inconsistent_guard_detected_one_lock_twin_clean(race_report):
    """RACE902: every write guarded, but by different locks — the
    intersection is empty even though no single site looks wrong."""
    assert _keys(race_report, "RACE902") == {
        "RACE902:racepkg.racemod:SplitLocks._run:mode"}


def test_race_check_then_act_detected(race_report):
    """RACE903: check under one lock hold, act under a fresh hold of
    the same lock — including the seeded PR-17 nack-timer shape (token
    validated, lock dropped, requeue under a second hold).  The
    single-hold twin is quiet."""
    assert _keys(race_report, "RACE903") == {
        "RACE903:racepkg.racemod:Reacquire.claim:slots",
        "RACE903:racepkg.racemod:_ShardRepro._nack_timeout:_unack"}
    assert all(f.severity == "warn" for f in race_report.findings
               if f.rule == "RACE903")


def test_blocking_under_lock_detected_polite_twin_clean(race_report):
    """LOCK305: time.sleep while a hot lock is held — both directly in
    the locked region and inside a helper whose entry lockset the
    interprocedural fixpoint propagates — plus a config-declared
    blocking root (the fetch/future-wait contract) called under the
    lock.  The twins (sleep after release; snapshot under the lock,
    fetch after it) are quiet."""
    assert _keys(race_report, "LOCK305") == {
        "LOCK305:racepkg.racemod:SleepyHolder._run:time.sleep",
        "LOCK305:racepkg.racemod:SleepyHolder._sync:time.sleep",
        "LOCK305:racepkg.racemod:FetchUnderLock.harvest:finish_round"}


def test_race_guard_inference_exports_guarded_by_map(tmp_path):
    """infer_guards (the lockdep runtime witness's static side) maps
    the clean twin's table to its lock."""
    from nomad_tpu.analysis.race_pass import infer_guards
    root = write_fixture(tmp_path, {"racemod.py": FIX_RACE},
                         pkg_name="racepkg")
    idx = PackageIndex.build(root, "racepkg")
    guards = infer_guards(idx, RACE_CFG)
    assert guards[("racepkg.racemod:GuardedTwin", "table")] == \
        frozenset({"GuardedTwin._lock"})
    # the racy classes must NOT be certified as guarded
    assert ("racepkg.racemod:Unguarded", "table") not in guards
    assert ("racepkg.racemod:SplitLocks", "mode") not in guards


def test_cli_diff_mode_contract(monkeypatch, capsys):
    """--diff is a computed --paths: it is mutually exclusive with an
    explicit --paths, resolves changed files from git, and refuses
    cleanly (exit 2, not a traceback) when git is unavailable."""
    from nomad_tpu.analysis import __main__ as cli
    assert cli.main(["--diff", "--paths", "x.py"]) == 2
    assert "mutually exclusive" in capsys.readouterr().err
    # the resolver returns absolute, existing .py paths
    paths = cli._diff_paths()
    assert all(os.path.isabs(p) and p.endswith(".py")
               and os.path.exists(p) for p in paths)
    assert paths == sorted(paths)

    def no_git(*a, **k):
        raise OSError("git: not found")
    monkeypatch.setattr(cli.subprocess, "run", no_git)
    assert cli.main(["--diff"]) == 2
    assert "needs a git checkout" in capsys.readouterr().err


def test_index_cache_roundtrip_and_corruption_fallback(tmp_path):
    """--cache-dir machinery: the first build populates per-file
    content-hash AST pickles, a second build reuses them and indexes
    identically, and a corrupted entry silently falls back to a fresh
    parse (a poisoned cache can never mask a finding)."""
    root = write_fixture(tmp_path, {"racemod.py": FIX_RACE},
                         pkg_name="racepkg")
    cache = str(tmp_path / "astcache")
    idx1 = PackageIndex.build(root, "racepkg", cache_dir=cache)
    entries = [f for f in os.listdir(cache) if f.endswith(".ast.pkl")]
    assert len(entries) == 2              # __init__.py + racemod.py
    idx2 = PackageIndex.build(root, "racepkg", cache_dir=cache)
    assert sorted(idx2.functions) == sorted(idx1.functions)
    for e in entries:                     # poison every entry
        with open(os.path.join(cache, e), "wb") as f:
            f.write(b"not a pickle")
    idx3 = PackageIndex.build(root, "racepkg", cache_dir=cache)
    assert sorted(idx3.functions) == sorted(idx1.functions)
    # findings are identical through the cache
    rep = analyze(package_dir=root, package_name="racepkg",
                  use_baseline=False, config=RACE_CFG,
                  cache_dir=cache)
    assert "RACE901:racepkg.racemod:Unguarded.put:table" in {
        f.key for f in rep.findings}
