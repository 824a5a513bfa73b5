"""Pallas kernel: fused in-segment ranking (row_number / rank / dense_rank).

Inputs are two boundary masks: ``seg_b`` marks segment heads, ``ord_b`` marks
order-key run heads (every segment head is also a run head, by construction
in ``physical.segment_rank``).  All three rank kinds reduce to segmented
scans of those masks:

  row_number[i] = segmented sum of 1        (position in segment, 1-based)
  dense_rank[i] = segmented sum of ord_b    (run index in segment, 1-based)
  rank[i]       = segmented running max of (ord_b ? row_number : 0)
                  (row_number at the latest run head — ties share it)

The kernel runs the segmented rotation ladder of ``kernels/tiling.py`` (sum
monoid for the count, max monoid with identity 0 for rank), with two
``(1, 128)`` VMEM carries: the count scan at the previous block's last row
and the running max.  The max carry is valid across blocks because
row_number only grows within a segment and the latest run head at or before
row i is always inside row i's segment.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..tiling import scan_block, scan_call


def _kernel(seg_ref, ord_ref, o_ref, c_count, c_max, *, kind: str):
    f = seg_ref[...]
    ob = ord_ref[...]
    inc = ob if kind == "dense_rank" else jnp.ones_like(ob)
    rn, nxt = scan_block(inc, c_count[...], flags=f)
    c_count[...] = nxt
    if kind == "rank":
        out, nxt = scan_block(jnp.where(ob != 0, rn, 0), c_max[...],
                              flags=f, op=jnp.maximum)
        c_max[...] = nxt
        o_ref[...] = out
    else:
        o_ref[...] = rn


def segment_rank_pallas(seg_b: jax.Array, ord_b: jax.Array, kind: str,
                        interpret: bool = True) -> jax.Array:
    """1-based in-segment ranks; kind in {row_number, rank, dense_rank}."""
    if kind not in ("row_number", "rank", "dense_rank"):
        raise ValueError(f"unknown rank kind: {kind!r}")
    return scan_call(functools.partial(_kernel, kind=kind),
                     [(seg_b != 0).astype(jnp.int32),
                      (ord_b != 0).astype(jnp.int32)],
                     [1, 1], jnp.int32, [jnp.int32, jnp.int32], interpret)
