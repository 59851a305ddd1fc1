"""Every f32 dot in the solver goes through kernel.exact_dot.

A TPU dot at default precision rounds f32 inputs to bf16; the solver's
dots multiply 0/1 masks by resource asks and attribute ranks, where a
rounded operand over-commits a node (ISSUE 21).  CPU computes them
exactly either way, so only a source-level check can hold the line
here; chip_smoke.py's float64 capacity check holds it on the chip.
"""
import ast
import os

import jax
import jax.numpy as jnp
import numpy as np

from nomad_tpu.solver.kernel import exact_dot

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOT_CALLS = {"dot", "matmul", "einsum", "tensordot", "dot_general",
             "vdot", "inner"}


def _raw_dots(path):
    with open(path) as f:
        tree = ast.parse(f.read())
    found = []

    def visit(node, inside_helper):
        if isinstance(node, ast.FunctionDef):
            inside_helper = inside_helper or node.name == "exact_dot"
        if not inside_helper:
            if isinstance(node, ast.BinOp) and isinstance(node.op,
                                                          ast.MatMult):
                found.append((node.lineno, "@"))
            if isinstance(node, ast.Call):
                fn = node.func
                name = (fn.attr if isinstance(fn, ast.Attribute)
                        else getattr(fn, "id", ""))
                if name in DOT_CALLS:
                    found.append((node.lineno, name))
        for child in ast.iter_child_nodes(node):
            visit(child, inside_helper)

    visit(tree, False)
    return found


def test_no_dot_bypasses_the_exact_helper():
    for rel in ("kernel.py", "resident.py"):
        path = os.path.join(REPO, "nomad_tpu", "solver", rel)
        assert _raw_dots(path) == [], (
            f"{rel}: matmul outside exact_dot — on a TPU it would run "
            "at bf16 input precision")


def test_exact_dot_asks_for_full_precision():
    a = jnp.ones((4, 4), jnp.float32)
    jaxpr = str(jax.make_jaxpr(exact_dot)(a, a))
    assert "HIGHEST" in jaxpr
    # and is exact for the operands the solver feeds it: a 0/1 mask
    # times asks that are not bf16-representable
    mask = jnp.asarray(np.tril(np.ones((8, 8), np.float32), -1))
    asks = jnp.asarray(np.full((8, 2), [1251.0, 4321.0], np.float32))
    want = np.tril(np.ones((8, 8)), -1) @ np.full((8, 2), [1251., 4321.])
    assert np.array_equal(np.asarray(exact_dot(mask, asks)), want)
