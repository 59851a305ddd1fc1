"""chip_smoke.py, rehearsed on the CPU at toy size.

The real check runs on a TPU through the chip tool; tier-1 only proves
that the script still runs end to end, that its summary line has the
contracted shape, and that without a TPU (and without --allow-cpu) it
refuses before building anything.
"""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, SMOKE, *args],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)


def test_refuses_without_a_tpu(tmp_path):
    out = _run("--out", str(tmp_path))
    assert out.returncode not in (0, None)
    assert "no TPU" in out.stderr
    lines = out.stdout.strip().splitlines()
    # the device line and nothing else: no phase ran, no result printed
    assert len(lines) == 1 and "platform=cpu" in lines[0]
    assert not os.listdir(tmp_path)


def test_toy_rehearsal_summary_line(tmp_path):
    out = _run("--allow-cpu", "--nodes", "96", "--allocs", "400",
               "--fill-count", "40", "--fresh-jobs", "2",
               "--evals-per-call", "2", "--stream-batches", "2",
               "--timeout", "120", "--out", str(tmp_path))
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    # the last line is the driver's verdict: these keys and no others
    verdict = json.loads(lines[-1])
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    summary = json.loads(lines[-2])
    assert summary["ok"] is True and summary["device"] == verdict["device"]
    # a rehearsal says so: it can never be quoted as a chip pass
    assert summary["device"]["platform"] == "cpu"
    assert set(summary["device"]) == {"platform", "kind", "count"}
    assert summary["phases"] == {"served": "pass", "kernel": "pass",
                                 "resident": "pass", "health": "pass"}
    assert summary["claim"] is None
    assert summary["compiles"]["requests"] > 0
    served = summary["detail"]["served"]
    assert served["live_allocs"] == served["fill_allocs"] + 2 * 64 \
        + max(8, 96 // 5) + max(4, 96 // 50) + 8 + 16
    assert served["watchdog.host_failover"] == 0
    with open(tmp_path / "chip_smoke.json") as f:
        assert json.loads(f.read()) == summary

