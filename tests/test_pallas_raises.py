"""On a `tpu` backend a pallas lowering failure RAISES.

The fused wave kernel used to compile-probe a toy shape inside
`except Exception: return False`, so a Mosaic refusal silently
downgraded every solve to the unfused path (ISSUE 21: that is exactly
what the real chip did to it).  Nothing may catch it now: a backend
that calls itself `tpu` but cannot compile the kernel — here, the CPU
backend asked to compile a non-interpreted pallas call — must fail the
solve with the compiler's message.
"""
import jax
import pytest

from nomad_tpu import mock
from nomad_tpu.solver import pallas_kernel as PK
from nomad_tpu.solver.kernel import solve_kernel
from nomad_tpu.solver.solve import _kernel_args


def test_lowering_failure_on_tpu_backend_raises(monkeypatch):
    assert not hasattr(PK, "available")     # the swallowing probe is gone
    monkeypatch.delenv("NOMAD_TPU_PALLAS", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    PK.enabled.cache_clear()
    try:
        assert PK.enabled() and not PK._interpret()
        # a shape no other test compiles: the jit cache cannot answer
        pb = mock.rich_solve_batch(83, 5)
        with pytest.raises(Exception, match="(?i)interpret|pallas|mosaic"):
            res = solve_kernel(*_kernel_args(pb), pallas_mode="auto")
            jax.block_until_ready(res.choice)
    finally:
        monkeypatch.undo()
        PK.enabled.cache_clear()
