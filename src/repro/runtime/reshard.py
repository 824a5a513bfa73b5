"""On-device resharding of persisted frames: P -> P' without a host gather.

A persisted frame's columns are ``(P * cap,)`` device arrays with per-shard
valid prefixes (the 1D_VAR carrier).  Re-entering the same data under a
different shard count P' — a serving session restarted on a larger or
smaller mesh, or a registered table shared with a query running at another
parallelism — previously meant ``ScanLayout.gather_host()``: copy every
valid prefix to host numpy, re-pad, re-upload.  This module replaces that
round-trip with a pure device-side gather:

  * the **index map** is computed from the layout's ``counts`` vector alone
    (host metadata, O(P) ints in, one int per row out) — row data never
    leaves the device;
  * the new geometry is the order-preserving balanced re-block: the global
    valid-row stream (shard-0 prefix, then shard-1, ...) is cut into P'
    near-equal contiguous prefixes.  For divisible ratios this degenerates
    to the natural split (each old shard becomes k new ones) / merge (k old
    shards concatenate into one new one);
  * because global row order is preserved, ordering claims survive:
    ``globally_sorted`` + ``sorted_by`` carry over verbatim.  Hash/range
    partitioning claims are shard-count-bound (routing is ``hash % P`` /
    splitter-based) and are dropped — :func:`reshard` can re-establish them
    with ONE on-device exchange (``repartition(keys).persist()`` over the
    already-resharded scan, which is device-valid at P', so the planner
    starts from device shards, not a host table).

Failure behaviour (PR 9 taxonomy): a frame without device buffers
(``counts is None``) raises ``ValueError`` — there is nothing to reshard;
capacity overflow cannot occur (the new capacity is sized from the true
row count).
"""
from __future__ import annotations

import dataclasses as _dc

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..core import ir
from ..core.dtypes import carry_logical


def _index_map(counts: np.ndarray, cap_old: int, P_new: int
               ) -> tuple[np.ndarray, np.ndarray, int]:
    """(flat gather indices, new per-shard counts, new capacity) for the
    order-preserving balanced re-block.  Pure counts metadata — no row data.
    """
    cnts = np.asarray(counts, dtype=np.int64)
    P_old = cnts.shape[0]
    total = int(cnts.sum())
    base, rem = divmod(total, P_new)
    counts_new = base + (np.arange(P_new) < rem).astype(np.int64)
    cap_new = max(int(counts_new.max(initial=0)), 1)
    cum = np.concatenate([[0], np.cumsum(cnts)])
    cumn = np.concatenate([[0], np.cumsum(counts_new)])
    pos = np.arange(P_new * cap_new, dtype=np.int64)
    r_new, j = pos // cap_new, pos % cap_new
    # global rank of each output slot's row (invalid slots clamp to a valid
    # rank — their gathered value is masked off by the count vector anyway)
    q = cumn[r_new] + np.minimum(j, np.maximum(counts_new[r_new] - 1, 0))
    q = np.clip(q, 0, max(total - 1, 0))
    src_shard = np.clip(np.searchsorted(cum, q, side="right") - 1, 0,
                        max(P_old - 1, 0))
    idx = src_shard * cap_old + (q - cum[src_shard])
    return idx.astype(np.int32), counts_new.astype(np.int32), cap_new


def reshard(df, P_new: int, cfg=None, *, reestablish: bool = True,
            name: str | None = None):
    """Re-enter a persisted frame's device shards at shard count ``P_new``.

    ``df`` must be a persisted DataFrame (its node an ``ir.Scan`` carrying
    device buffers).  Returns a new persisted frame whose scan is
    ``device_valid(P_new)``.  Ordering claims survive; hash/range claims are
    re-established via one on-device exchange when ``reestablish=True`` and
    ``cfg`` (an ExecConfig for the P_new mesh) is given, else dropped.
    """
    from ..core.api import DataFrame

    node = df.node if isinstance(df, DataFrame) else df
    if not isinstance(node, ir.Scan) or node.layout is None:
        raise ValueError("reshard: input must be a persisted frame "
                         "(df.persist()) whose scan carries a layout")
    lay = node.layout
    if lay.counts is None:
        raise ValueError(
            "reshard: frame has no device shards (host/REP table) — "
            "re-enter it directly; only device layouts need resharding")
    P_new = int(P_new)
    if P_new < 1:
        raise ValueError(f"reshard: invalid shard count {P_new}")
    if lay.nshards == P_new:
        return df if isinstance(df, DataFrame) else DataFrame(node)

    idx, counts_new, cap_new = _index_map(lay.counts, int(lay.capacity),
                                          P_new)
    jidx = jnp.asarray(idx)
    # the gather runs wherever the source shards live; the result is then
    # committed onto the TARGET mesh (device-to-device placement — the rows
    # never surface as host numpy).
    if cfg is not None:
        mesh, axes = cfg.get_mesh(), cfg.axes
        got = int(np.prod([mesh.shape[a] for a in axes]))
        if got != P_new:
            raise ValueError(
                f"reshard: cfg mesh has {got} shard(s), target is {P_new}")
    else:
        mesh, axes = Mesh(np.array(jax.devices()[:P_new]), ("data",)), ("data",)
    sh = NamedSharding(mesh, P(axes))
    cols = {c: jax.device_put(jnp.take(jnp.asarray(v), jidx, axis=0), sh)
            for c, v in node.columns.items()}

    keep_part = lay.kind in ("hash", "range") and bool(lay.partitioned_by)
    new_lay = _dc.replace(
        lay,
        kind="block" if keep_part else lay.kind,
        partitioned_by=() if keep_part else lay.partitioned_by,
        counts=counts_new, capacity=int(cap_new), nshards=P_new,
        dist="1D_VAR")
    scan = ir.Scan(name or f"{node.name}@P{P_new}", cols,
                   carry_logical(cols, node.schema), layout=new_lay)
    out = DataFrame(scan)
    if keep_part and reestablish and cfg is not None and lay.kind == "hash":
        # one on-device hash exchange re-establishes the partitioning claim
        # at P_new (the intermediate scan is device-valid, so the planner
        # feeds device shards straight through — no host round-trip).
        q = out.repartition(list(lay.partitioned_by))
        if lay.sorted_by:
            q = q.sort_within_partitions(list(lay.sorted_by))
        out = q.persist(cfg, name=scan.name)
    return out
