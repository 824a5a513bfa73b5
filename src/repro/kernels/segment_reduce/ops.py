"""jit'd wrapper: per-segment sums read off a segmented scan at run ends."""
import functools

import jax
import jax.numpy as jnp

from .segment_reduce import run_scan_pallas


@functools.partial(jax.jit, static_argnames=("num_segments", "interpret"))
def segment_sums(values, seg_id, valid, num_segments: int, interpret: bool = True):
    """Sums of sorted, consecutive segments 0..num_segments-1.

    values: (n,), seg_id: (n,) int32 sorted ascending over the valid prefix.
    Returns (num_segments,) f32 sums (empty segments -> 0).
    """
    v = jnp.where(valid, values.astype(jnp.float32), 0.0)
    prv = jnp.concatenate([jnp.full((1,), -1, seg_id.dtype), seg_id[:-1]])
    s = run_scan_pallas(v, seg_id != prv, interpret=interpret)  # kernel phase
    nxt = jnp.concatenate([seg_id[1:], jnp.full((1,), -1, seg_id.dtype)])
    nxt_valid = jnp.concatenate([valid[1:], jnp.zeros((1,), bool)])
    is_end = valid & ((seg_id != nxt) | ~nxt_valid)
    sid = jnp.where(is_end, seg_id, num_segments)
    # the scan value at the end of segment k is segment k's total
    return jnp.zeros((num_segments + 1,), jnp.float32).at[sid].set(
        s, mode="drop")[:num_segments]
