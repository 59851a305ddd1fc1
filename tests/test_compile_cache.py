"""One compile cache, placeable from outside (utils/compile_cache)."""
import os
import re

import jax
import pytest

from nomad_tpu.utils import compile_cache as cc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# spelled in two halves so this file is not itself a hit
KNOB = "jax_compilation" + "_cache_dir"


@pytest.fixture
def fresh(monkeypatch):
    """The module as a new process sees it, with jax.config.update
    recorded instead of applied (the suite's real cache stays put)."""
    calls = []
    monkeypatch.setattr(cc, "_enabled_dir", None)
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    return calls


def test_env_var_wins_and_code_sets_nothing(fresh, monkeypatch, tmp_path):
    monkeypatch.setenv(cc.ENV_VAR, "/x")
    assert cc.enable_compile_cache(str(tmp_path)) == "/x"
    assert fresh == []              # JAX reads the variable itself
    assert not os.path.exists("/x")


def test_agent_config_dir_comes_second(fresh, tmp_path):
    d = str(tmp_path / "cfg")
    assert cc.enable_compile_cache(d) == d
    assert fresh == [(KNOB, d)] and os.path.isdir(d)
    # a later default-resolution call (Server.__init__) keeps it
    assert cc.enable_compile_cache() == d
    assert fresh == [(KNOB, d)]


def test_default_is_the_checkout(fresh):
    want = os.path.join(REPO, ".jax_cache")
    assert cc.DEFAULT_DIR == want
    assert cc.enable_compile_cache() == want
    assert fresh == [(KNOB, want)]


def test_one_site_touches_the_knob():
    hits = []
    for root, dirs, files in os.walk(REPO):
        # git-ignored scratch (chip tool output, unpacked copies of
        # the tree for a chip run) is not the program
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out",
                                 "chip_archive", "chip_parent")]
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path) as f:
                    if re.search(KNOB, f.read()):
                        hits.append(os.path.relpath(path, REPO))
    assert hits == [os.path.join("nomad_tpu", "utils",
                                 "compile_cache.py")]


def test_compile_watch_counts_backend_compiles():
    watch = cc.CompileWatch().install()
    before = watch.snapshot()
    jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7))
    d = watch.diff(before, watch.snapshot())
    assert d["requests"] >= 1
    assert d["compiles"] == d["requests"] - d["cache_hits"]
