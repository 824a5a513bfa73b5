"""Pallas kernel: bucket rank + histogram for the shuffle (Alltoallv analogue).

The exchange operator must place row i into slot ``rank(i)`` of bucket
``dest(i)`` where rank is the stable within-bucket position.  The reference
path derives ranks from a stable argsort (O(n log n) bitonic on TPU); this
kernel computes them in ONE streaming pass over lane-dense blocks of the
destination array: for each bucket p, the row-major inclusive scan of
``dest == p`` (the rotation ladder of ``kernels/tiling.py``) gives the
within-block rank, and an SMEM histogram carries the running per-bucket
count across the sequential grid.  Work is O(n·P / lanes) with unit-stride
VPU ops, for the small P of a device mesh.

Rows with dest == P (invalid/padding) match no bucket: rank 0, counted
nowhere.  Valid rows form a prefix, so their ranks are unaffected.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tiling import LANES, block_rows, scan_block, to_tiles


def _kernel(dest_ref, rank_ref, hist_ref, *, P: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        def zero(p, c):
            hist_ref[p] = jnp.int32(0)
            return c
        lax.fori_loop(0, P, zero, 0)

    d = dest_ref[...]
    no_carry = jnp.zeros((1, LANES), jnp.int32)

    def bucket(p, rank):
        m = (d == p).astype(jnp.int32)
        incl, _ = scan_block(m, no_carry)
        base = hist_ref[p]                              # carried bucket count
        hist_ref[p] = base + jnp.sum(m)
        return rank + jnp.where(m != 0, incl - 1 + base, 0)

    rank_ref[...] = lax.fori_loop(0, P, bucket, jnp.zeros_like(d))


def bucket_ranks_pallas(dest: jax.Array, P: int, interpret: bool = True):
    """(ranks, send_counts) for bucket ids in [0, P]; P marks invalid rows."""
    n = dest.shape[0]
    rows = block_rows(n)
    dt, nb = to_tiles(dest.astype(jnp.int32), rows, fill=P)
    spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    ranks, counts = pl.pallas_call(
        functools.partial(_kernel, P=P),
        grid=(nb,),
        in_specs=[spec],
        out_specs=[spec, pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[jax.ShapeDtypeStruct(dt.shape, jnp.int32),
                   jax.ShapeDtypeStruct((P,), jnp.int32)],
        interpret=interpret,
    )(dt)
    return ranks.reshape(-1)[:n], counts
