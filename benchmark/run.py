#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which owns the chip.  It builds `Server()` at the defaults a
user gets, registers the configuration's nodes, seeds its resident allocs
through raft entries, warms up every shape the traffic can form, drives
the window, waits out what is still open, reads the device's memory peak,
stops the server, decides `correct` against the plain reference, and
prints the contract's result as the last line of standard output.

A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result.  `--rehearse` (sandbox only, never a
cell) swaps in a few hundred nodes on the CPU backend and prints
`correct` from the same code; it prints no device metric.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)

#: run-time outputs (the trace of a --trace 1 run): inside the checkout,
#: at a fixed path, git-ignored
OUT_DIR = os.path.join(ROOT, ".bench_out")
#: after its bursts the warm-up sends one more full-width burst, and
#: again (this often at most) if that one still asked the backend for an
#: executable: the window should find every shape compiled or loaded
MAX_SETTLE_BURSTS = 2
EXIT_NO_CHIP = 3


def log(msg: str) -> None:
    print(f"[bench {time.monotonic() - T_PROCESS_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="sandbox only: tiny cluster on the CPU backend")
    return ap.parse_args(argv)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json (have "
                     f"{[w['name'] for w in bench['workloads']]})")


def metrics_of(bench: dict, section: str, cell: str) -> list:
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def require_chips(want: int, rehearse: bool):
    """The device this process measures on, as JAX reports it."""
    import jax
    devs = jax.devices()
    if rehearse:
        return devs
    if devs[0].platform != "tpu" or len(devs) < want:
        print(f"benchmark needs {want} TPU chip(s); JAX reports "
              f"{len(devs)} x {devs[0].platform}: no result",
              file=sys.stderr)
        sys.exit(EXIT_NO_CHIP)
    return devs


def terminal_evals(store) -> int:
    from nomad_tpu.structs import (EVAL_STATUS_CANCELLED,
                                   EVAL_STATUS_COMPLETE, EVAL_STATUS_FAILED)
    done = (EVAL_STATUS_COMPLETE, EVAL_STATUS_FAILED, EVAL_STATUS_CANCELLED)
    return sum(1 for e in store.evals() if e.status in done)


class Probe:
    """Reads, at one instant, everything a per-layer reader may diff."""

    def __init__(self, server, gen, watch):
        self.server, self.gen, self.watch = server, gen, watch

    def read(self) -> dict:
        from nomad_tpu.utils.metrics import global_metrics
        with self.gen._sent_lock:
            regs = list(self.gen.sent)
        return {"t": time.monotonic(),
                "dump": global_metrics.dump(),
                "evals": terminal_evals(self.server.store),
                "placements": sum(r.asked if r.t_visible is not None
                                  else r.seen for r in regs),
                "compile": self.watch.snapshot()}


def warm_up(gen, traffic, watch, patience: float) -> int:
    """The traffic mix's warm-up, then one more full-width burst, and
    again (MAX_SETTLE_BURSTS at most) while a burst still asks the
    backend for an executable."""
    n_warm = gen.warm_up(timeout_s=patience)
    width = int(max(traffic["warmup_bursts"]))
    for _ in range(MAX_SETTLE_BURSTS):
        c0 = watch.snapshot()
        give_up = time.monotonic() + patience
        regs = gen.closed(width, give_up, give_up, jobs_per_client=1)
        n_warm += len(regs)
        if any(r.t_visible is None for r in regs):
            raise RuntimeError("a settle burst did not finish")
        if watch.diff(c0, watch.snapshot())["requests"] == 0:
            break
    return n_warm


def measure(a, gen, probe) -> dict:
    """Drive the window; a --trace 1 run traces exactly the window and
    reads the program's counters at both of its ends."""
    import jax
    result = {}
    win = threading.Thread(
        target=lambda: result.update(gen.window(a.seconds)), name="window")
    trace_dir = start_trace(a) if a.trace else None
    result["before"] = probe.read()
    win.start()
    if a.trace:
        time.sleep(a.seconds)
        p1 = probe.read()
        jax.profiler.stop_trace()
        result["traced"] = {"dir": trace_dir, "p0": result["before"],
                            "p1": p1,
                            "window_s": p1["t"] - result["before"]["t"]}
    win.join()            # returns once what was open has been waited out
    result["after"] = probe.read()
    return result


def off_device_solves(counters: dict, platform: str, brownout: bool,
                      rehearse: bool) -> tuple:
    solves = {k[len("solver.solve."):]: int(v) for k, v in counters.items()
              if k.startswith("solver.solve.")}
    # at rehearsal size the program's own rule (`prefer_host`) answers
    # from numpy; at a cell's size every solve has to come from the chip
    home = (platform, "numpy") if rehearse else (platform,)
    off = sum(v for k, v in solves.items() if k not in home) \
        + int(counters.get("watchdog.host_failover", 0)) \
        + int(counters.get("watchdog.host_quarantine", 0)) \
        + int(counters.get("solver.degraded", 0)) + int(brownout) \
        + (0 if sum(solves.values()) else 1)
    return solves, off


def run(a) -> int:
    import check
    import cluster
    import load
    bench = load_benchmark()
    cell = find_cell(bench, a.workload)
    # a configuration that cannot be held to what it lists fails here,
    # before JAX, the chip or a Server is touched
    cfg = cluster.load_config(cell["config"], rehearse=a.rehearse)
    check.validate(cfg)
    traffic = load.load_traffic(cell["traffic"])
    if a.rehearse:
        traffic.update(cluster.REHEARSE_TRAFFIC)
        os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        from nomad_tpu.server.server import Server
        from nomad_tpu.utils.compile_cache import (CompileWatch,
                                                   enable_compile_cache)
        from nomad_tpu.utils.metrics import global_metrics
    except ImportError as exc:
        print(f"the system under test is not in this checkout: {exc}",
              file=sys.stderr)
        return 2
    devs = require_chips(int(cell["chips"]), a.rehearse)
    import jax
    # the program's one cache (`<checkout>/.jax_cache`, or where
    # JAX_COMPILATION_CACHE_DIR says); keep every program in it, however
    # quickly it compiled, so that a second run compiles nothing
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    watch = CompileWatch().install()

    plain = cluster.make_plain_nodes(cfg, a.seed)
    log(f"cell {cell['name']}: {len(plain)} nodes, "
        f"{cfg['resident']['allocs']} resident allocs, traffic "
        f"{traffic['name']}, device {devs[0].device_kind} x{len(devs)}, "
        f"cache {cache_dir}")

    server = Server()
    server.start()
    pump = load.HeartbeatPump(server, len(plain))
    pump.start()
    gen = None
    try:
        setup = cluster.seed_cluster(server, cfg, plain, a.seed,
                                     on_node=pump.node_ids.append)
        n_store = len(server.store.allocs())
        if n_store != int(cfg["resident"]["allocs"]):
            raise RuntimeError(f"seeding left {n_store} allocs in the "
                               f"store, wanted {cfg['resident']['allocs']}")
        log(f"seeded: nodes {setup['register_nodes_s']:.1f}s, resident "
            f"{setup['seed_resident_s']:.1f}s in "
            f"{setup['resident_entries']} raft entries")

        gen = load.LoadGen(server, cfg, a.seed, traffic)
        t0 = time.monotonic()
        # the first solve builds the resident world and may compile: the
        # warm-up gets the allowance of a cell's first run
        setup["warm_up_jobs"] = warm_up(gen, traffic, watch,
                                        120.0 if a.rehearse else 900.0)
        setup["warm_up_s"] = time.monotonic() - t0
        setup["compile"] = watch.snapshot()
        ceiling = cluster.ceiling_jobs(cfg)
        log(f"warm-up: {setup['warm_up_jobs']} jobs in "
            f"{setup['warm_up_s']:.1f}s, compiles so far "
            f"{setup['compile']}; ceiling {ceiling} jobs")

        setup_s = time.monotonic() - T_PROCESS_START
        result = measure(a, gen, Probe(server, gen, watch))
        regs = result["regs"]
        # cut-down warm-up jobs count by the allocs they ask for
        n_jobs = sum(r.asked for r in gen.sent) / gen.asked
        log(f"window: {len(regs)} registrations sent, "
            f"{result['allocs_at_close']} allocs visible at close, "
            f"{n_jobs:.1f} jobs' worth of allocs asked in the run "
            f"(ceiling {ceiling})")
        if n_jobs > ceiling:
            raise RuntimeError(
                f"the run asked for {n_jobs:.1f} jobs' worth of allocs, "
                f"over the configuration's ceiling of {ceiling}: the "
                "cluster is too full to stand for it (PERF.md, known "
                "ceiling)")

        # after the window: the memory peak first, then what `correct`
        # needs from the program, then the program goes
        peak = max(int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in devs)
        down = sum(1 for n in server.store.nodes() if not n.ready())
        solves, off_device = off_device_solves(
            global_metrics.dump()["counters"], devs[0].platform,
            bool(server.serving.admission.brownout_active()), a.rehearse)
        snapshot = server.store.snapshot()
        raft = check.raft_view(server)
    finally:
        if gen is not None:
            gen.close()
        pump.stop()
        server.stop()

    t0 = time.monotonic()
    rows = check.rows_from_snapshot(cfg, snapshot, plain)
    del snapshot
    sent = [(r.job_id, r.shape) for r in gen.sent if r.error is None]
    numbers = check.compare(cfg, plain, rows, sent, raft, off_device)
    v = check.verdict(cfg, numbers)
    check_s = time.monotonic() - t0
    failed = sum(1 for r in regs if r.t_visible is None)
    if down or pump.unknown:
        log(f"{down} nodes not ready at the end, {pump.unknown} "
            "heartbeats to unknown nodes")
        v["correct"] = False

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    line = {"correct": v["correct"] and failed == 0,
            "attempted": len(regs), "failed": failed, "device": device}
    if a.trace:
        traced = result["traced"]
        traced["regs"] = regs
        line["metrics"] = per_layer(bench, cell, cfg, devs, traced, a)
        if traced.get("busy_s"):
            device["busy_s"] = traced["busy_s"]
            device["window_s"] = traced["window_s"]
            line["breakdown"] = traced["breakdown"]
    else:
        line["metrics"] = end_to_end(bench, cell, result, setup_s,
                                     a.seconds)
    line["info"] = {
        "setup": {k: (round(x, 3) if isinstance(x, float) else x)
                  for k, x in setup.items()},
        "jobs_in_run": n_jobs, "ceiling_jobs": ceiling,
        "solves": solves, "heartbeat_sweeps": pump.sweeps,
        "watcher_looks": gen.watcher.looks,
        "close_late_s": result["close_late_s"],
        "send_late_ms_max": 1000.0 * max(
            (r.t_sent - r.t_due for r in regs), default=0.0),
        "raft_compacted_to": raft["compacted_to"],
        "compile_window": watch.diff(result["before"]["compile"],
                                     result["after"]["compile"]),
        "latency_ms": latency_info(regs, result["t_end"]),
        "run_totals": run_totals(result["after"]["dump"]),
        "not_compared": {k: x for k, x in numbers.items()
                         if k not in v["compared"]},
        "check_s": check_s}
    # last in the line, and the last lines of standard error: every number
    # compared beside its limit
    line["compared"] = dict(v["compared"],
                            failed={"value": failed, "limit": 0})
    for k, c in line["compared"].items():
        print(f"compared {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


def latency_info(regs, t_end) -> dict:
    lat = sorted(1000.0 * (r.t_visible - r.t_due) for r in regs
                 if r.t_visible is not None)
    if not lat:
        return {}
    return {"min": lat[0], "p25": lat[len(lat) // 4],
            "p75": lat[(3 * len(lat)) // 4], "max": lat[-1],
            "visible_in_window": sum(
                1 for r in regs
                if r.t_visible is not None and r.t_visible <= t_end)}


def run_totals(dump: dict) -> dict:
    """The program's stage histograms and solve counters over the whole
    run, warm-up included (for the builder's eye; no metric reads it)."""
    out = {k: [round(v["sum"], 3), v["count"]]
           for k, v in dump["histograms"].items()
           if k.startswith("coordinator.stage.")}
    for k in ("plan.apply", "plan.evaluate", "coordinator.fused_evals",
              "worker.invoke_scheduler_service", "worker.submit_plan"):
        v = dump["samples"].get(k)
        if v:
            out[k] = [v["sum"], v["count"]]
    for k, v in dump["counters"].items():
        if k.startswith(("solver.", "coordinator.", "plan.", "broker.")):
            out[k] = v
    return out


def end_to_end(bench, cell, result, setup_s, seconds) -> dict:
    import stats
    regs = result["regs"]
    lat = [r.t_visible - r.t_due for r in regs if r.t_visible is not None]
    values = {"setup_s": setup_s,
              "placements_per_s": stats.rate_per_s(
                  result["allocs_at_close"], seconds)}
    if lat:
        s = stats.latency_summary_ms(lat)
        values["reg_to_visible_p50_ms"] = s["p50"]
        values["reg_to_visible_p95_ms"] = s["p95"]
    out = {}
    for m in metrics_of(bench, "end_to_end", cell["name"]):
        if m["name"] in values:
            out[m["name"]] = {"value": values[m["name"]],
                              "unit": m["unit"]}
    return out


def start_trace(a) -> str:
    """A --trace 1 run traces its whole window: a fused round of 32
    evals lasts some twenty seconds, so a shorter stretch would see no
    round end.  The per-layer metrics are taken over that same stretch,
    the program's counters read at both of its ends."""
    import jax
    trace_dir = os.path.join(OUT_DIR, "trace", a.workload)
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    return trace_dir


def per_layer(bench, cell, cfg, devs, traced, a) -> dict:
    import host_spans
    import layers
    import stats
    import xplane
    p0, p1 = traced["p0"], traced["p1"]
    d = layers.diff_dumps(p0["dump"], p1["dump"])
    lat = [r.t_visible - r.t_due for r in traced["regs"]
           if r.t_visible is not None]
    summary = stats.latency_summary_ms(lat) if lat else {}
    obs = layers.Observed(
        counters=d["counters"], samples=d["samples"], hists=d["hists"],
        harness={"window_s": traced["window_s"],
                 "reg_to_visible_p50_ms": summary.get("p50"),
                 "reg_to_visible_p95_ms": summary.get("p95"),
                 "evals_completed": p1["evals"] - p0["evals"],
                 "placements_visible": p1["placements"] - p0["placements"],
                 "compile_requests": p1["compile"]["requests"]
                 - p0["compile"]["requests"]},
        config=cfg)
    path = xplane.find_xplane(traced["dir"])
    if devs[0].platform == "tpu":
        if path is None:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        obs.peaks = layers.load_peaks(devs[0].device_kind)
        obs.profile = xplane.load(path)
        traced["busy_s"] = xplane.busy_seconds(obs.profile)
        traced["breakdown"] = {
            "device_ops": xplane.top_ops(obs.profile),
            "idle_gaps": host_spans.idle_gaps(obs.profile)}
    out = {}
    for m in metrics_of(bench, "per_layer", cell["name"]):
        value = layers.read_metric(m["name"], obs)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    shutil.rmtree(traced["dir"], ignore_errors=True)
    return out


if __name__ == "__main__":
    sys.exit(run(parse_args()))
