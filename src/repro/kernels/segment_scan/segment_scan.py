"""Pallas kernel: fused segmented inclusive scan (boundary mask + scan, one pass).

The partitioned-window backbone: ``out[i]`` is the running sum of ``x`` within
the segment containing row i, where ``boundary[i] != 0`` marks segment heads.
The lax composition (``ref.py``) needs three sweeps — a global cumsum, a
cummax to locate segment heads, and a gather to subtract the pre-segment
base.  This kernel fuses them into ONE pass using the segmented-scan monoid

    (v1, f1) + (v2, f2) = (v2 if f2 else v1 + v2,  f1 | f2)

applied as the rotation ladder of ``kernels/tiling.py`` (pure VPU, no
gathers), with a ``(1, 128)`` VMEM tile carrying the scan value at the
previous block's last row.  Rows before the first in-block boundary continue
the prior segment, so adding the carry where the accumulated flag is still
unset is exactly the cross-block fixup.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..tiling import scan_block, scan_call


def _kernel(x_ref, b_ref, o_ref, carry):
    out, nxt = scan_block(x_ref[...], carry[...], flags=b_ref[...])
    o_ref[...] = out
    carry[...] = nxt


def segment_scan_pallas(x: jax.Array, boundary: jax.Array,
                        interpret: bool = True) -> jax.Array:
    """Segmented inclusive sum-scan; boundary != 0 starts a new segment."""
    b = (boundary != 0).astype(jnp.int32)
    return scan_call(_kernel, [x, b], [0, 0], x.dtype, [x.dtype], interpret)
