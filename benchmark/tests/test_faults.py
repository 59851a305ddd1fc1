"""Drive the rest of a run (the harness's look for a chip skipped by
--rehearse) with the timed path broken underneath, and see `correct`
come out false.  Faults this system's cells can have: half of a batch
left out; an answer altered where it is produced.  (No cell trains and
none spans chips, so "a step that returns its state unchanged" and "the
exchange between chips left out" have nothing to break here.)"""
import json

import pytest

import cluster
import load
import run

CELL = "c3-affinity-spread-10k.closed1"


def drive(capsys, monkeypatch, seed):
    monkeypatch.setitem(cluster.REHEARSE_TRAFFIC, "wait_timeout_s", 3)
    a = run.parse_args(["--workload", CELL, "--seed", str(seed),
                        "--seconds", "1", "--trace", "0", "--rehearse"])
    assert run.run(a) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def arm_at_window(monkeypatch, flag):
    real = load.LoadGen.window

    def window(self, seconds):
        flag["on"] = True
        return real(self, seconds)
    monkeypatch.setattr(load.LoadGen, "window", window)


def test_a_sound_run_is_correct(capsys, monkeypatch):
    line = drive(capsys, monkeypatch, 2**31 + 21)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "compared"
    # the tail is per layer in this cell (`gc_edge_reg_to_visible_p95_ms`)
    assert set(line["metrics"]) == {"placements_per_s", "setup_s",
                                    "reg_to_visible_p50_ms"}


def test_half_of_a_batch_left_out(capsys, monkeypatch):
    from nomad_tpu.state.store import StateStore
    flag = {"on": False}
    arm_at_window(monkeypatch, flag)
    real = StateStore.upsert_plan_results

    def dropping(self, index, result, job=None):
        if flag["on"]:
            for nid, allocs in list(result.node_allocation.items()):
                result.node_allocation[nid] = allocs[::2]
        return real(self, index, result, job)
    monkeypatch.setattr(StateStore, "upsert_plan_results", dropping)
    line = drive(capsys, monkeypatch, 2**31 + 22)
    assert line["correct"] is False
    assert line["compared"]["jobs_off_count"]["value"] > 0
    assert line["failed"] > 0


def test_an_answer_altered_where_it_is_produced(capsys, monkeypatch):
    from nomad_tpu.scheduler.generic import GenericScheduler
    flag = {"on": False, "left": 3}
    arm_at_window(monkeypatch, flag)
    real = GenericScheduler._emit_alloc

    def altered(self, m, node, resources, score, metrics):
        if flag["on"] and flag["left"] > 0:
            # a node the job's constraint `rack != r63` rules out
            wrong = next(n for n in self.state.nodes()
                         if n.attributes.get("rack") == "r63")
            flag["left"] -= 1
            node = wrong
        return real(self, m, node, resources, score, metrics)
    monkeypatch.setattr(GenericScheduler, "_emit_alloc", altered)
    line = drive(capsys, monkeypatch, 2**31 + 23)
    assert line["correct"] is False
    assert line["compared"]["constraint_violations"]["value"] == 3


def test_scores_in_the_lower_precision(capsys, monkeypatch):
    """The solve's scores as bfloat16 would leave them: the arithmetic
    number catches what no guarantee does."""
    import ml_dtypes
    import numpy as np
    from nomad_tpu.scheduler.generic import GenericScheduler
    flag = {"on": False}
    arm_at_window(monkeypatch, flag)
    real = GenericScheduler._emit_alloc

    def rounded(self, m, node, resources, score, metrics):
        if flag["on"]:
            score = float(np.float32(score).astype(ml_dtypes.bfloat16))
        return real(self, m, node, resources, score, metrics)
    monkeypatch.setattr(GenericScheduler, "_emit_alloc", rounded)
    line = drive(capsys, monkeypatch, 2**31 + 24)
    assert line["correct"] is False
    c = line["compared"]["score_mismatch_p99"]
    assert c["value"] > 3 * c["limit"]
    assert line["compared"]["overcommitted_nodes"]["value"] == 0
