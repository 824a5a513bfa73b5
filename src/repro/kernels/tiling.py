"""Lane-dense tiling shared by the 1-D Pallas kernels.

The TPU compiler (Mosaic) wants blocks whose last two dimensions are
multiples of (8, 128), keeps carried state in vector tiles rather than
scalar VMEM cells, and has no ``cumsum``.  So every 1-D kernel here views
its flat input as a ``(rows, 128)`` row-major matrix, walks it in
``(block_rows, 128)`` blocks over a sequential grid, and scans a block with
static rotations: a Hillis-Steele ladder along the 128 lanes, then one
along the rows over the per-row totals.  Element ``k`` of the flat array is
element ``(k // 128, k % 128)`` of the view, so a row-major scan of the
view is the 1-D scan.

Scans take an optional int32 ``flags`` tile (nonzero starts a segment) and
a combine op (``jnp.add`` or ``jnp.maximum``; both have identity 0 on the
values callers feed them), under the segmented-scan monoid

    (v1, f1) + (v2, f2) = (v2 if f2 else v1 op v2,  f1 | f2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
MAX_BLOCK_ROWS = 256          # 32K elements, 128 KiB of 32-bit data per block


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def block_rows(n: int) -> int:
    """Rows per block for an ``n``-element array: a multiple of 8, at most
    ``MAX_BLOCK_ROWS``, no larger than the array needs."""
    rows = cdiv(max(n, 1), LANES)
    return min(MAX_BLOCK_ROWS, cdiv(rows, SUBLANES) * SUBLANES)


def to_tiles(x: jax.Array, rows: int, fill=0, extra_rows: int = 0):
    """Pad a flat array and view it as ``(nb * rows + extra_rows, 128)``;
    returns the view and the block count ``nb``."""
    n = x.shape[0]
    nb = max(1, cdiv(n, rows * LANES))
    total = (nb * rows + extra_rows) * LANES
    xp = jnp.pad(x, (0, total - n), constant_values=fill)
    return xp.reshape(total // LANES, LANES), nb


def shift(v: jax.Array, s: int, axis: int, fill) -> jax.Array:
    """``v`` moved ``s`` places toward higher indices along ``axis``; the
    first ``s`` places take ``fill``."""
    idx = lax.broadcasted_iota(jnp.int32, v.shape, axis)
    return jnp.where(idx >= s, pltpu.roll(v, s, axis), fill)


def rotate_back(v: jax.Array, s: int, axis: int) -> jax.Array:
    """``v`` rotated ``s`` places toward lower indices: ``out[i] = v[i+s]``
    (cyclically)."""
    size = v.shape[axis]
    s %= size
    return v if s == 0 else pltpu.roll(v, size - s, axis)


def _ladder(v, f, axis: int, op):
    zero = jnp.zeros((), v.dtype)
    s = 1
    while s < v.shape[axis]:
        vs = shift(v, s, axis, zero)
        if f is None:
            v = op(v, vs)
        else:
            v = op(v, jnp.where(f != 0, zero, vs))
            f = jnp.maximum(f, shift(f, s, axis, 0))
        s *= 2
    return v, f


def _lane_last(v):
    """Each row's last-lane value, broadcast along the row."""
    lane = lax.broadcasted_iota(jnp.int32, v.shape, 1)
    last = jnp.sum(jnp.where(lane == v.shape[1] - 1, v, jnp.zeros((), v.dtype)),
                   axis=1, keepdims=True)
    return jnp.broadcast_to(last, v.shape)


def scan_block(v: jax.Array, carry: jax.Array, flags: jax.Array | None = None,
               op=jnp.add):
    """Row-major inclusive (segmented, when ``flags`` is given) scan of one
    ``(R, 128)`` block, continuing from ``carry`` — a ``(1, 128)`` tile
    holding the scan value just before the block in every lane.

    Returns ``(out, next_carry)``, ``next_carry`` being the block's last
    scan value as a ``(1, 128)`` tile."""
    zero = jnp.zeros((), v.dtype)
    v, f = _ladder(v, flags, 1, op)
    # Per-row totals, scanned down the rows, then moved one row down: the
    # value (and "a segment started") before each row inside the block.
    tot, ftot = _ladder(_lane_last(v), None if f is None else _lane_last(f),
                        0, op)
    before = shift(tot, 1, 0, zero)
    c = jnp.broadcast_to(carry, v.shape)
    if f is None:
        out = op(op(c, before), v)
    else:
        fbefore = shift(ftot, 1, 0, 0)
        inc = jnp.where(fbefore != 0, before, op(c, before))
        out = jnp.where(f != 0, v, op(inc, v))
    return out, last_value(out)


def last_value(v: jax.Array) -> jax.Array:
    """The block's last element (row-major), as a ``(1, 128)`` tile."""
    row = v[v.shape[0] - 1:, :]
    return _lane_last(row)


def scan_call(kernel, arrays, fills, out_dtype, carry_dtypes,
              interpret: bool) -> jax.Array:
    """Run ``kernel(*in_refs, out_ref, *carry_refs)`` over equal-length flat
    ``arrays`` in lane-dense blocks on a sequential grid; the ``(1, 128)``
    VMEM carries start at zero.  Returns the flat output, trimmed."""
    n = arrays[0].shape[0]
    rows = block_rows(n)
    tiles = [to_tiles(a, rows, f)[0] for a, f in zip(arrays, fills)]
    nb = tiles[0].shape[0] // rows
    n_in = len(tiles)

    def body(*refs):
        @pl.when(pl.program_id(0) == 0)
        def _init():
            for c in refs[n_in + 1:]:
                c[...] = jnp.zeros(c.shape, c.dtype)

        kernel(*refs)

    spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        body,
        grid=(nb,),
        in_specs=[spec] * n_in,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(tiles[0].shape, out_dtype),
        scratch_shapes=[pltpu.VMEM((1, LANES), dt) for dt in carry_dtypes],
        interpret=interpret,
    )(*tiles)
    return out.reshape(-1)[:n]
