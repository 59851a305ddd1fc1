"""Standalone feasibility-mask kernel + bitpacked boolean planes.

Computes only the static [G, N] feasibility mask (constraints + dc +
host-evaluated ops) without the placement scan — used by the system
scheduler, which forces placements onto specific nodes and only needs
the mask (reference analog: feasible.go checks without rank/limit).

Bitpacking, two layouts:

  * `pack_bool_u32` folds 32 NODE columns into one uint32 word — 8x
    fewer bytes when a [G, N] mask crosses the host/device boundary
    (the stacked ask planes going in, `static_feasibility` coming
    out).  Bit j of word w is node column ``w * 32 + j``; the node
    axis must be a multiple of 32, which every tensorize padding (pow2
    >= 32, or 1024-multiples) guarantees.
  * `pack_groups_i32` folds 32 GROUP rows into one int32 word,
    [ceil(G/32), N] — the layout the pallas wave kernel re-reads every
    full wave (feasibility, penalty, distinct-blocking).  The node
    axis stays the lane axis, so a tile's words are a lane-aligned
    block and unpack with a sublane broadcast; the node-axis layout
    cannot be blocked or unpacked by Mosaic (pallas_kernel.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import _op_eval

#: node columns folded per packed word
PACK_LANES = 32


def pack_bool_u32(mask: jnp.ndarray) -> jnp.ndarray:
    """[..., N] bool/int mask -> [..., ceil(N/32)] uint32 words (jnp;
    traceable inside jit).  Node axes below a 32-multiple (tiny test
    pads) zero-fill the trailing bits."""
    n = mask.shape[-1]
    if n % PACK_LANES:
        pad = PACK_LANES - n % PACK_LANES
        mask = jnp.concatenate(
            [mask, jnp.zeros(mask.shape[:-1] + (pad,), mask.dtype)],
            axis=-1)
        n += pad
    bits = mask.astype(jnp.uint32).reshape(
        mask.shape[:-1] + (n // PACK_LANES, PACK_LANES))
    shifts = jnp.arange(PACK_LANES, dtype=jnp.uint32)
    return (bits << shifts).sum(axis=-1, dtype=jnp.uint32)


def unpack_bool_u32(words: jnp.ndarray, n: int) -> jnp.ndarray:
    """[..., N // 32] uint32 -> [..., n] bool (jnp; traceable inside
    jit and inside a pallas kernel body)."""
    shifts = jnp.arange(PACK_LANES, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(words.shape[:-1]
                        + (words.shape[-1] * PACK_LANES,))[..., :n] != 0


def pack_groups_i32(mask: jnp.ndarray) -> jnp.ndarray:
    """[G, N] bool mask -> [ceil(G/32), N] int32 words, bit ``g % 32``
    of word row ``g // 32`` (jnp; traceable inside jit).  Trailing
    bits of a short last word are zero."""
    g, n = mask.shape
    gw = -(-g // PACK_LANES)
    if g % PACK_LANES:
        mask = jnp.concatenate(
            [mask, jnp.zeros((gw * PACK_LANES - g, n), mask.dtype)])
    bits = mask.astype(jnp.uint32).reshape(gw, PACK_LANES, n)
    shifts = jnp.arange(PACK_LANES, dtype=jnp.uint32)[None, :, None]
    words = (bits << shifts).sum(axis=1, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32)


def np_pack_bool_u32(mask: np.ndarray) -> np.ndarray:
    """Host-side (numpy) twin of pack_bool_u32."""
    n = mask.shape[-1]
    if n % PACK_LANES:
        pad = PACK_LANES - n % PACK_LANES
        mask = np.concatenate(
            [mask, np.zeros(mask.shape[:-1] + (pad,), mask.dtype)],
            axis=-1)
        n += pad
    bits = np.asarray(mask, bool).reshape(
        mask.shape[:-1] + (n // PACK_LANES, PACK_LANES))
    weights = (np.uint32(1) << np.arange(PACK_LANES, dtype=np.uint32))
    return (bits * weights).sum(axis=-1, dtype=np.uint64).astype(np.uint32)


def np_unpack_bool_u32(words: np.ndarray, n: int) -> np.ndarray:
    """Host-side (numpy) twin of unpack_bool_u32."""
    shifts = np.arange(PACK_LANES, dtype=np.uint32)
    bits = (words[..., None] >> shifts) & np.uint32(1)
    return bits.reshape(words.shape[:-1]
                        + (words.shape[-1] * PACK_LANES,))[..., :n] != 0


@jax.jit
def _feas_kernel(valid, node_dc, attr_rank, dc_ok, host_ok, c_op, c_col,
                 c_rank):
    import jax.numpy as jnp
    from jax import lax

    def per_ask(g):
        vals = attr_rank[:, c_col[g]]
        ok = _op_eval(vals, c_op[g], c_rank[g])
        base = valid & dc_ok[g][node_dc] & host_ok[g]
        return base & ok.all(axis=1)

    Gp = c_op.shape[0]
    feas = lax.map(per_ask, jnp.arange(Gp))
    # fetch bitpacked words, not bools: the [G, N] plane crosses the
    # transport 8x smaller (the system scheduler fetches this whole)
    return pack_bool_u32(feas)


def static_feasibility(pb) -> np.ndarray:
    """[G, N] bool mask for a PackedBatch (fetched as packed uint32
    words, unpacked host-side)."""
    words = _feas_kernel(pb.valid, pb.node_dc, pb.attr_rank, pb.dc_ok,
                         pb.host_ok, pb.c_op, pb.c_col, pb.c_rank)
    return np_unpack_bool_u32(np.asarray(words), pb.valid.shape[0])
