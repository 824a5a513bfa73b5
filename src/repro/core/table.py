"""DTable — the runtime carrier of a distributed data frame.

The paper's 1D_VAR distribution ("variable-length chunks per rank") is carried
on TPU as **static per-shard capacity + dynamic valid-prefix counts**: every
column is a dense array of global shape ``(P * capacity,)`` sharded by rows
over the data axes, plus a ``(P,)`` count vector.  Rows ``[count, capacity)``
of each shard are padding.  1D_BLOCK is the special case where every count
equals the block size (last shard possibly partial).

Columns are ordinary ``jax.Array``s — the paper's dual representation: any
column can flow into arbitrary array computation, and any array can become a
column.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import numpy as np

from . import distribution as D
from .spans import MONO, span

# Logical dtypes live in core/dtypes.py; re-exported here because ingest
# coercion (dictionary encode, null promotion) is part of the table contract.
from .dtypes import (  # noqa: F401
    CODE_DTYPE, NULL_CODE, DType, as_nullable, categories_of, coerce_column,
    dict_decode, dict_encode, is_category, is_nullable, physical_dtype,
    recode_map, union_categories,
)

@dataclass(eq=False)
class DTable:
    """A materialized distributed table."""

    columns: dict[str, jax.Array]   # each of global shape (P * capacity,)
    counts: jax.Array               # (P,) int32 valid rows per shard
    capacity: int                   # per-shard row capacity
    nshards: int
    dist: str = D.ONE_D             # lattice element this table satisfies
    overflow: Any = None            # bool; True => some capacity site overflowed
    # per-op failure attribution (docs/robustness.md): physical-plan op id ->
    # {"kind", "op", "cap", "bucket", "cap_req", "bucket_req", "strategy"}
    # for every capacity site whose flag fired.  Empty dict on a clean run.
    overflow_ops: dict = None       # type: ignore[assignment]
    # ExecConfig.validate check results: tuple of errors.InvariantFailure.
    invariant_failures: tuple = ()
    # retry/degradation events (runtime/retry.RetryEvent) from the policy
    # that produced this table — the collect report.
    events: tuple = ()
    # the serving session's runtime/session.QueryRecord of the query that
    # produced this table (None outside a session); to_numpy adds to it.
    query_record: Any = None

    def __post_init__(self):
        if self.overflow_ops is None:
            self.overflow_ops = {}

    @property
    def schema(self) -> dict[str, np.dtype]:
        return {k: np.dtype(v.dtype) for k, v in self.columns.items()}

    def num_rows(self) -> int:
        counts = np.asarray(self.counts)
        if self.dist == D.REP:           # every shard holds the full table
            return int(counts[0])
        return int(np.sum(counts))

    def to_numpy(self) -> dict[str, np.ndarray]:
        """Gather valid rows to host (drops padding).  Every padded column
        moves whole; with a ``query_record`` the seconds (host span
        ``hf.readback``) and the column bytes moved are added to its
        ``readback_s`` and ``readback_bytes``."""
        t0 = MONO()
        moved = 0
        with span("readback"):
            counts = np.asarray(self.counts)
            shards = 1 if self.dist == D.REP else self.nshards
            out: dict[str, np.ndarray] = {}
            for name, col in self.columns.items():
                a = np.asarray(col)
                moved += a.nbytes
                a = a.reshape(self.nshards, self.capacity)
                out[name] = np.concatenate(
                    [a[r, : counts[r]] for r in range(shards)]
                ) if shards else a[:0]
        rec = self.query_record
        if rec is not None:
            rec.readback_s += MONO() - t0
            rec.readback_bytes += moved
        return out

    def column(self, name: str) -> jax.Array:
        """The raw padded column array (1D_BLOCK tables: padding only on the
        last shard) — for tight integration with array code."""
        return self.columns[name]

    def __repr__(self):
        cols = ", ".join(f"{k}:{v.dtype}" for k, v in self.columns.items())
        return (f"DTable[{self.dist}] P={self.nshards} cap={self.capacity} "
                f"rows={self.num_rows()} ({cols})")


def pad_to(arr: np.ndarray, n: int) -> np.ndarray:
    """Pad a host array with zeros to length n."""
    if arr.shape[0] == n:
        return arr
    out = np.zeros((n,) + arr.shape[1:], dtype=arr.dtype)
    out[: arr.shape[0]] = arr
    return out


def block_counts(total_rows: int, nshards: int, capacity: int) -> np.ndarray:
    """Valid counts for a 1D_BLOCK layout of ``total_rows``."""
    c = np.clip(total_rows - np.arange(nshards) * capacity, 0, capacity)
    return c.astype(np.int32)
