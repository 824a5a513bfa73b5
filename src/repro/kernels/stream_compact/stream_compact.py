"""Pallas kernel: carried blocked prefix-sum (stream compaction backbone).

Filter is the paper's no-communication operator: each shard moves its kept
rows into a dense prefix.  The hot loop is the inclusive prefix-sum of the
keep-predicate that assigns destination slots.  TPU grid steps execute
sequentially, so a ``(1, 128)`` VMEM tile carries the running total across
lane-dense blocks — one pass, no re-scan (the classic decoupled-lookback is
unnecessary on TPU's sequential grid).  The in-block scan is the rotation
ladder of ``kernels/tiling.py``.

The same kernel (float path) is the local phase of distributed cumsum
(paper Fig. 8b) — MPI_Exscan's local partial sums.
"""
from __future__ import annotations

import jax

from ..tiling import scan_block, scan_call


def _kernel(x_ref, o_ref, carry):
    out, nxt = scan_block(x_ref[...], carry[...])
    o_ref[...] = out
    carry[...] = nxt


def prefix_sum_pallas(x: jax.Array, interpret: bool = True) -> jax.Array:
    """Inclusive prefix sum over a 1-D array (int32/float32)."""
    return scan_call(_kernel, [x], [0], x.dtype, [x.dtype], interpret)
