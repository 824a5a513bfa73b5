"""Pallas kernel phase of the segmented reduction over sorted runs.

The TPU replacement for the paper's hash-table aggregation: after the shuffle
and local sort, rows with equal keys are contiguous runs.  The kernel phase is
a float32 scan that restarts at every run start (the ``segment_scan``
kernel); the wrapper reads each run's total off the scan at the run's last
row — one sequential pass over HBM-streamed blocks, no scatter in the inner
loop (scatters are the VPU's weakness; the end-of-run scatter is tiny).
Restarting per run keeps every sum as accurate as the run is short: a
difference of one whole-shard prefix sum would lose the run's low-order bits
to the magnitude of everything before it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..segment_scan.segment_scan import segment_scan_pallas


def run_scan_pallas(values: jax.Array, starts: jax.Array,
                    interpret: bool = True) -> jax.Array:
    """Inclusive f32 scan of values, restarting where ``starts`` is set."""
    return segment_scan_pallas(values.astype(jnp.float32), starts,
                               interpret=interpret)
