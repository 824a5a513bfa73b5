"""Pallas kernel: 1-D weighted window (SMA/WMA) — the paper's stencil op.

Tiling: the extended array ``ext`` (local shard + exchanged halos, length
n + K - 1) is viewed lane-dense as ``(rows, 128)`` (``kernels/tiling.py``).
Each grid step loads its ``(R, 128)`` block plus the next 8 rows of the same
array (the halo tail, so K - 1 <= 1024) into VMEM; tap j of output element
``(r, c)`` is flat element ``128 r + c + j``, fetched with one sublane and
one lane rotation.  The weighted window sum is K static shifted
multiply-adds — MXU-free, pure VPU.  Weights are compile-time constants
folded into the kernel body.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from ..tiling import LANES, SUBLANES, block_rows, rotate_back, to_tiles

MAX_TAPS = SUBLANES * LANES
# The unrolled window keeps about one (rows, 128) temporary per tap alive in
# VMEM (200 taps over 256-row blocks asked for 33.5 MB, twice the 16 MiB
# scoped limit): blocks shrink with the window so that its taps fit.
TAP_ROWS_BUDGET = 4096


def _taps(x_ref, tail_ref):
    """``tap(j)``: the block's view of the flat array advanced by j."""
    x = jnp.concatenate([x_ref[...], tail_ref[...]], axis=0)
    R = x_ref.shape[0]
    lane = lax.broadcasted_iota(jnp.int32, x_ref.shape, 1)

    def tap(j: int):
        q, s = divmod(j, LANES)
        a = rotate_back(x, q, 0)[:R]
        if s == 0:
            return a
        b = rotate_back(x, q + 1, 0)[:R]
        return jnp.where(lane < LANES - s, rotate_back(a, s, 1),
                         rotate_back(b, s, 1))
    return tap


def _renorm(acc, mass, weights):
    total = np.float32(sum(weights))
    safe = jnp.where(mass != 0.0, mass, np.float32(1.0))
    return jnp.where(mass != 0.0, acc * total / safe, np.float32(0.0))


def _stencil_call(kernel, exts, fills, dtypes, n: int, K: int,
                  interpret: bool):
    """Run ``kernel(x, x_tail, [y, y_tail,] out)`` over lane-dense blocks of
    the extended arrays for a ``K``-tap window; returns the first ``n``
    outputs."""
    rows = min(block_rows(n), max(
        SUBLANES, TAP_ROWS_BUDGET // K // SUBLANES * SUBLANES))
    tiles = [to_tiles(e.astype(dt), rows, f, extra_rows=SUBLANES)[0]
             for e, f, dt in zip(exts, fills, dtypes)]
    nb = (tiles[0].shape[0] - SUBLANES) // rows
    block = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    tail = pl.BlockSpec((SUBLANES, LANES),
                        lambda i: ((i + 1) * (rows // SUBLANES), 0))
    out = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[block, tail] * len(tiles),
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((nb * rows, LANES), jnp.float32),
        interpret=interpret,
    )(*[t for t in tiles for _ in range(2)])
    return out.reshape(-1)[:n]


def _check_taps(K: int):
    if not 1 <= K <= MAX_TAPS:
        raise ValueError(f"stencil window must have 1..{MAX_TAPS} taps, "
                         f"got {K}")


def _kernel(x_ref, xt_ref, o_ref, *, weights: tuple[float, ...]):
    tap = _taps(x_ref, xt_ref)
    acc = np.float32(weights[0]) * tap(0)
    for j in range(1, len(weights)):
        acc = acc + np.float32(weights[j]) * tap(j)
    o_ref[...] = acc


def stencil1d_pallas(ext: jax.Array, weights: tuple[float, ...],
                     interpret: bool = True) -> jax.Array:
    """out[i] = sum_j w[j] * ext[i+j], for i in [0, len(ext) - K + 1)."""
    K = len(weights)
    _check_taps(K)
    return _stencil_call(functools.partial(_kernel, weights=tuple(weights)),
                         [ext], [0], [jnp.float32], ext.shape[0] - (K - 1),
                         K, interpret)


def _kernel_exact(x_ref, xt_ref, m_ref, mt_ref, o_ref, *,
                  weights: tuple[float, ...]):
    xtap, mtap = _taps(x_ref, xt_ref), _taps(m_ref, mt_ref)
    acc = np.float32(weights[0]) * xtap(0)
    mass = np.float32(weights[0]) * mtap(0)
    for j in range(1, len(weights)):
        acc = acc + np.float32(weights[j]) * xtap(j)
        mass = mass + np.float32(weights[j]) * mtap(j)
    o_ref[...] = _renorm(acc, mass, weights)


def stencil1d_exact_pallas(ext: jax.Array, ext_m: jax.Array,
                           weights: tuple[float, ...],
                           interpret: bool = True) -> jax.Array:
    """Fused stencil + edge renormalize: the weighted sum over in-bounds taps
    (``ext_m`` carries the validity mask through the same halo machinery) is
    rescaled by total_weight / covered_mass in the SAME kernel pass — the
    second full stencil sweep that ``exact=True`` rolling windows used to pay
    disappears."""
    K = len(weights)
    _check_taps(K)
    return _stencil_call(
        functools.partial(_kernel_exact, weights=tuple(weights)),
        [ext, ext_m], [0, 0], [jnp.float32, jnp.float32],
        ext.shape[0] - (K - 1), K, interpret)


def _kernel_segment(x_ref, xt_ref, s_ref, st_ref, o_ref, *,
                    weights: tuple[float, ...], center: int, exact: bool):
    xtap, stap = _taps(x_ref, xt_ref), _taps(s_ref, st_ref)
    sid = stap(center)
    acc = jnp.zeros(x_ref.shape, jnp.float32)
    mass = jnp.zeros(x_ref.shape, jnp.float32)
    for j, wj in enumerate(weights):
        same = stap(j) == sid
        acc = acc + np.float32(wj) * jnp.where(same, xtap(j), np.float32(0.0))
        if exact:
            mass = mass + np.float32(wj) * same.astype(jnp.float32)
    o_ref[...] = _renorm(acc, mass, weights) if exact else acc


def segment_stencil_pallas(ext: jax.Array, ext_s: jax.Array,
                           weights: tuple[float, ...], center: int,
                           exact: bool = False,
                           interpret: bool = True) -> jax.Array:
    """Partition-masked stencil: tap j contributes only where the extended
    segment-id array matches the centre row's id (``ext_s`` uses sentinel ids
    for halo/invalid rows, so cross-partition taps never match).  With
    ``exact`` the in-segment mass renormalize is fused in, same as
    ``stencil1d_exact``."""
    K = len(weights)
    _check_taps(K)
    return _stencil_call(
        functools.partial(_kernel_segment, weights=tuple(weights),
                          center=center, exact=exact),
        [ext, ext_s], [0, -2], [jnp.float32, jnp.int32],
        ext.shape[0] - (K - 1), K, interpret)
