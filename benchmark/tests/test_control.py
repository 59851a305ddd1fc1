"""The controls of `correct`, at a size a test run can hold: the plain
reference put in the program's place is correct, and each control the
configuration lists is not, by the number that is there to catch it."""
import pytest

import cluster
import controls


def small(name, nodes=1024):
    cfg = cluster.load_config(name)
    per_node = cfg["resident"]["allocs"] // cfg["cluster"]["nodes"]
    cfg["cluster"]["nodes"] = nodes
    cfg["resident"]["allocs"] = nodes * per_node
    return cfg


def over(res, control, number, times=3.0):
    c = res[control]["compared"][number]
    return not res[control]["correct"] and c["value"] > times * c["limit"]


@pytest.mark.parametrize("seed", [3, 2**31 + 3, 77])
def test_c3_controls_come_out_not_correct(seed):
    res = controls.run_controls(small("c3-affinity-spread-10k"), seed, 48)
    assert set(res) == {"sound", "bfloat16", "isolated_round",
                        "spread_ignored"}
    assert res["sound"]["correct"], res["sound"]
    assert res["sound"]["compared"]["spread_miss_share"]["value"] < 0.05
    assert res["sound"]["compared"]["score_mismatch_p99"]["value"] == 0
    assert over(res, "isolated_round", "overcommitted_nodes", 0)
    assert over(res, "bfloat16", "overcommitted_nodes", 0)
    assert over(res, "bfloat16", "score_mismatch_p99")
    assert over(res, "spread_ignored", "spread_miss_share")


@pytest.mark.parametrize("seed", [3, 2**31 + 3, 77])
def test_c2_controls_come_out_not_correct(seed):
    res = controls.run_controls(small("c2-binpack-10k"), seed, 48)
    assert set(res) == {"sound", "bfloat16", "isolated_round",
                        "sampled_14_nodes"}
    assert res["sound"]["correct"], res["sound"]
    assert res["sound"]["compared"]["score_mismatch_p99"]["value"] == 0
    assert res["sound"]["compared"]["choice_gap_p90"]["value"] == 0
    assert over(res, "isolated_round", "overcommitted_nodes", 0)
    # the lower precision: float32 stated, bfloat16 sums
    assert over(res, "bfloat16", "score_mismatch_p99")
    assert over(res, "sampled_14_nodes", "choice_gap_p90")
