"""bench.py's launcher: one process per chip, no holes in the results.

A chip belongs to one process at a time, so the parent must never
initialise a JAX backend (every measurement runs in a child), and a
child that fails — non-zero exit or no record — must fail the bench
instead of leaving a `"skipped": true` hole (ISSUE 21).
"""
import os
import subprocess
import sys
import textwrap

import pytest

import bench

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_failing_child_fails_the_bench(capfd):
    # config 99 does not exist: the child dies before printing a record
    with pytest.raises(SystemExit) as exc:
        bench._run_child(["--one", "99"])
    assert exc.value.code not in (0, None)
    assert "exited" in capfd.readouterr().err


def test_child_without_a_record_fails_the_bench(monkeypatch):
    done = subprocess.CompletedProcess([], 0, stdout="no record\n",
                                       stderr="")
    monkeypatch.setattr(subprocess, "run", lambda *a, **k: done)
    with pytest.raises(SystemExit):
        bench._run_child(["--one", "3"])


def test_parent_never_initialises_a_jax_backend(tmp_path):
    """Drive main() end to end with stub children and ask JAX whether
    any backend came up in the parent."""
    script = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {REPO!r})
        import bench
        bench.REPO = {str(tmp_path)!r}
        calls = []

        def child(args, env=None):
            calls.append(args[0])
            if args[0] == "--one":
                return {{"config": int(args[1]), "ratio_placements": 1.0,
                        "ours": {{"placements_per_sec": 1.0}}}}
            return {{"phase": args[0]}}

        bench._run_child = child
        bench.lint_summary = lambda: {{}}     # pure AST, 12 s, not at issue
        sys.argv = ["bench.py"]
        bench.main()
        from jax._src import xla_bridge
        assert not xla_bridge.backends_are_initialized(), \\
            "bench.py's parent initialised a JAX backend"
        assert calls.count("--one") == len(bench.CONFIGS)
        assert "--analysis" in calls and "--overcommit" in calls
        detail = json.load(open({str(tmp_path / "BENCH_DETAIL.json")!r}))
        assert "skipped" not in json.dumps(detail)
        print("PARENT-OFF-JAX")
    """)
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=600,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "PARENT-OFF-JAX" in out.stdout
