"""Physical operators — the CGen analogue (paper §4.5), re-thought for TPU.

Every function in this module is *per-shard* code: it runs inside a single
``jax.shard_map`` region spanning the whole query plan, operating on one
shard's ``(capacity,)`` column slices plus a scalar valid-row ``count``.
Collectives (`lax.all_to_all`, `lax.all_gather`, `lax.ppermute`, `lax.psum`)
replace the paper's MPI calls:

  MPI_Alltoallv  -> fixed-capacity bucketed all_to_all + count vector
  MPI_Alltoall   -> (the count exchange folds into the same all_to_all)
  MPI_Exscan     -> ppermute ladder / all_gather-of-scalars exclusive scan
  Isend/Irecv    -> ppermute halo exchange (XLA emits async start/done pairs)

All shapes are static; validity is tracked with counts and masks (DESIGN.md
§2).  Key sentinel for sorts is the dtype max, so padding sorts to the end.
"""
from __future__ import annotations

import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..kernels import registry as _registry

Axes = tuple[str, ...]


def _K(kernels) -> "_registry.KernelSet":
    """Resolve the kernel set for a per-shard operator.

    ``Lowered`` threads the :class:`~repro.kernels.registry.KernelSet` picked
    by ``ExecConfig.use_pallas`` into every call below; ``None`` (direct
    callers, tests) falls back to the ref backends — the pure lax
    compositions that are bit-for-bit the pre-registry numerics.
    """
    return kernels if kernels is not None else _registry.REF


# ---------------------------------------------------------------------------
# small helpers
# ---------------------------------------------------------------------------

def nshards(axes: Axes) -> int:
    return int(np.prod([lax.axis_size(a) for a in axes]))


def my_rank(axes: Axes):
    return lax.axis_index(axes)


def valid_mask(count, cap: int):
    return jnp.arange(cap, dtype=jnp.int32) < count


def _sentinel(dtype) -> jnp.ndarray:
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.finfo(dtype).max, dtype)
    return jnp.array(jnp.iinfo(dtype).max, dtype)


def hash_u32(x: jax.Array) -> jax.Array:
    """Lowbias32-style integer mix; floats are bitcast first."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        x = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    else:
        x = x.astype(jnp.uint32)
    x = (x ^ (x >> 16)) * np.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * np.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def hash_combine(h: jax.Array, h2: jax.Array) -> jax.Array:
    """Boost-style hash combine on uint32 (wraps mod 2^32)."""
    return h ^ (h2 + np.uint32(0x9E3779B9) + (h << 6) + (h >> 2))


def hash_keys(cols: dict[str, jax.Array], key_names: Sequence[str]) -> jax.Array:
    """Composite row hash: per-column hash_u32 folded with hash_combine.

    Rows whose key TUPLES are equal get equal hashes, so shuffle_by_key
    co-locates composite-key groups exactly as it does single-key ones.
    """
    h = hash_u32(cols[key_names[0]])
    for kn in key_names[1:]:
        h = hash_combine(h, hash_u32(cols[kn]))
    return h


# ---------------------------------------------------------------------------
# compaction (filter backend) — paper: "filter requires no communication"
# ---------------------------------------------------------------------------

def compact(cols: dict[str, jax.Array], keep: jax.Array, cap_out: int,
            kernels=None):
    """Move rows where ``keep`` into the prefix of fresh (cap_out, ...) buffers.

    Returns (cols_out, count_out, overflow).  Rows beyond cap_out are dropped
    and flagged — the driver's retry hook (fault tolerance for capacity
    planning, DESIGN.md §2).  The slot-assignment scan resolves through the
    registry's ``prefix_sum`` primitive (stream_compact Pallas kernel when
    ``use_pallas`` is on); ``keep`` may be boolean or an integer 0/1 vector —
    both take the same path.  Columns may carry trailing dims (the
    packed-word matrix of :func:`pack_columns` compacts row-wise like any
    scalar column).  A zero-length shard (empty ``keep``) short-circuits
    before any scan runs — the prefix kernel never sees a zero-size input.
    """
    if keep.shape[0] == 0:
        out = {name: jnp.zeros((cap_out,) + v.shape[1:], v.dtype)
               for name, v in cols.items()}
        return out, jnp.int32(0), jnp.array(False)
    keep = keep.astype(jnp.int32)
    incl = _K(kernels).prefix_sum(keep)
    dest = incl - 1
    total = incl[-1]
    dest = jnp.where(keep > 0, dest, cap_out)          # parked -> dropped
    overflow = total > cap_out
    out = {}
    for name, v in cols.items():
        buf = jnp.zeros((cap_out,) + v.shape[1:], v.dtype)
        out[name] = buf.at[dest].set(v, mode="drop")
    return out, jnp.minimum(total, cap_out).astype(jnp.int32), overflow


# ---------------------------------------------------------------------------
# skew salting (adaptive_stats; docs/adaptive_planning.md)
# ---------------------------------------------------------------------------

# The salt column a salted join's two SaltOps inject and the join strips.
SALT_COL = "__salt__"


def hot_mask(cols: dict[str, jax.Array], key_names: Sequence[str],
             hot: Sequence[tuple]) -> jax.Array:
    """Boolean row mask: key tuple ∈ ``hot`` (a STATIC plan constant — the
    same literal set on both join sides, so membership agrees exactly)."""
    cap = cols[key_names[0]].shape[0]
    m = jnp.zeros((cap,), dtype=bool)
    for vals in hot:
        eq = jnp.ones((cap,), dtype=bool)
        for kn, v in zip(key_names, vals):
            c = cols[kn]
            eq = eq & (c == jnp.asarray(v, c.dtype))
        m = m | eq
    return m


def salt_probe(cols: dict[str, jax.Array], count, key_names: Sequence[str],
               hot: Sequence[tuple], R: int):
    """Probe-side salting: hot rows get salt ``position % R`` (spreading a
    hot key's rows over R sub-partitions of the keys+salt exchange), every
    other row salt 0.  Row set and order unchanged; returns (cols, count)."""
    cap = cols[key_names[0]].shape[0]
    is_hot = hot_mask(cols, key_names, hot)
    salt = jnp.where(is_hot, jnp.arange(cap, dtype=jnp.int32) % R,
                     jnp.int32(0))
    out = dict(cols)
    out[SALT_COL] = salt
    return out, count


def salt_build(cols: dict[str, jax.Array], count, key_names: Sequence[str],
               hot: Sequence[tuple], R: int, cap_out: int, kernels=None):
    """Build-side salting: hot rows are replicated to every salt 0..R-1 so
    each probe sub-partition finds its match; non-hot rows keep one salt-0
    copy.  Every (probe row, build row) pair with equal keys then agrees on
    exactly ONE salt value — the salted join's row set is exactly the
    unsalted one.  Returns (cols, count, overflow) via :func:`compact`."""
    cap = cols[key_names[0]].shape[0]
    is_hot = hot_mask(cols, key_names, hot)
    valid = valid_mask(count, cap)
    rep = {name: jnp.concatenate([v] * R)       # replica r at rows [r*cap, ...)
           for name, v in cols.items()}
    rep[SALT_COL] = jnp.repeat(jnp.arange(R, dtype=jnp.int32), cap)
    keep = jnp.tile(valid, R) & ((rep[SALT_COL] == 0) | jnp.tile(is_hot, R))
    return compact(rep, keep, cap_out, kernels=kernels)


# ---------------------------------------------------------------------------
# column packing — the byte-transport layer of the packed exchange
# ---------------------------------------------------------------------------

# Word width of the packed transport buffer: every column is bitcast into
# uint32 words, so a whole table shuffles as ONE (P, bucket_cap, W) payload.
PACK_WORD_BYTES = 4


def col_words(dtype) -> int:
    """uint32 words one value of ``dtype`` occupies in the packed layout.

    4-byte types bitcast 1:1; 8-byte types split into two words; sub-word
    types (bool, int8/16, fp16/bf16) zero-extend into one word — the packed
    layout trades a little padding on narrow columns for a single collective.
    """
    dtype = np.dtype(dtype)
    if dtype == np.bool_:
        return 1
    return max(1, dtype.itemsize // PACK_WORD_BYTES)


def pack_columns(cols: dict[str, jax.Array]):
    """Bitcast-pack every column into one (rows, W) uint32 word matrix.

    Returns ``(words, layout)`` where ``layout`` is the per-column
    ``(name, dtype, word_offset, n_words)`` recipe :func:`unpack_columns`
    inverts.  Pure bit movement (``lax.bitcast_convert_type``): floats keep
    their payload bits exactly — NaNs, signed zeros and all.
    """
    words, layout, off = [], [], 0
    for name, v in cols.items():
        dt = jnp.dtype(v.dtype)
        if dt == jnp.bool_:
            w = v.astype(jnp.uint32)[:, None]
        elif dt.itemsize == 4:
            w = lax.bitcast_convert_type(v, jnp.uint32)[:, None]
        elif dt.itemsize == 8:
            w = lax.bitcast_convert_type(v, jnp.uint32)       # (rows, 2)
        elif dt.itemsize == 2:
            w = lax.bitcast_convert_type(v, jnp.uint16).astype(jnp.uint32)[:, None]
        else:                                                 # 1-byte ints
            w = lax.bitcast_convert_type(v, jnp.uint8).astype(jnp.uint32)[:, None]
        layout.append((name, dt, off, w.shape[1]))
        off += w.shape[1]
        words.append(w)
    return jnp.concatenate(words, axis=1), layout


def unpack_columns(words: jax.Array, layout) -> dict[str, jax.Array]:
    """Invert :func:`pack_columns`: slice each column's words and bitcast
    back to its original dtype."""
    out = {}
    for name, dt, off, nw in layout:
        w = words[:, off:off + nw]
        if dt == jnp.bool_:
            out[name] = w[:, 0] != 0
        elif dt.itemsize == 4:
            out[name] = lax.bitcast_convert_type(w[:, 0], dt)
        elif dt.itemsize == 8:
            out[name] = lax.bitcast_convert_type(w, dt)       # (rows, 2) -> (rows,)
        elif dt.itemsize == 2:
            out[name] = lax.bitcast_convert_type(w[:, 0].astype(jnp.uint16), dt)
        else:
            out[name] = lax.bitcast_convert_type(w[:, 0].astype(jnp.uint8), dt)
    return out


# ---------------------------------------------------------------------------
# exchange (MPI_Alltoallv analogue) — backbone of shuffle/join/aggregate,
# and of MoE expert-parallel dispatch (models/moe.py reuses this).
# ---------------------------------------------------------------------------

def exchange(cols: dict[str, jax.Array], count, dest: jax.Array, *,
             axes: Axes, bucket_cap: int, cap_out: int,
             kernels=None, packed: bool = True):
    """Route row i of this shard to shard ``dest[i]``.

    Static-shape plan: rows are stably grouped by destination into a
    per-shard bucket buffer, exchanged with ``lax.all_to_all``, then
    compacted into a (cap_out,) valid-prefix buffer.  Counts ride along as a
    (P,) vector through their own all_to_all.  Stability: row order within a
    (src, dst) pair is preserved and receives are concatenated in src order,
    so global row order is preserved for order-sensitive users (rebalance).

    Slot assignment resolves through the registry's ``bucket_scatter``
    primitive — ``(slot, send_counts)`` with each row's stable within-bucket
    slot at its ORIGINAL position, so rows scatter straight into the bucket
    buffer with no reorder pass.  The ref backend derives slots from a
    stable argsort; the Pallas backend (hash_partition) computes them in one
    streaming count+scatter pass with a carried per-bucket histogram.

    ``packed=True`` (default) ships ALL columns as one word-packed
    (P, bucket_cap, W) uint32 payload (:func:`pack_columns`), so an exchange
    of any table costs exactly TWO collectives — counts + payload — with a
    single fused scatter for slot assignment and one unpack after the wire.
    ``packed=False`` restores the one-collective-per-column baseline (the
    ``ExecConfig.packed_exchange`` A/B lever).
    """
    P = nshards(axes) if axes else 1
    valid = valid_mask(count, dest.shape[0])
    dest = jnp.where(valid, dest.astype(jnp.int32), P)

    if P == 1:
        # single shard: no collective; just clamp into the output capacity.
        return compact(cols, valid, cap_out, kernels=kernels)

    slot, send_counts = _K(kernels).bucket_scatter(dest, P)
    in_range = dest < P
    overflow_send = jnp.any(in_range & (slot >= bucket_cap))
    scatter_slot = jnp.where(in_range & (slot < bucket_cap), slot, bucket_cap)

    sent = jnp.minimum(send_counts, bucket_cap)
    recv_counts = lax.all_to_all(sent.reshape(P, 1), axes, 0, 0).reshape(P)

    slot_idx = jnp.arange(bucket_cap, dtype=jnp.int32)[None, :]
    keep = (slot_idx < recv_counts[:, None]).reshape(-1)

    if packed:
        # ONE payload collective for the whole table: pack -> one fused
        # scatter into (P, bucket_cap+1, W) -> one all_to_all -> compact the
        # word matrix row-wise -> unpack.
        words, layout = pack_columns(cols)
        buf = jnp.zeros((P, bucket_cap + 1, words.shape[1]), jnp.uint32)
        buf = buf.at[dest, scatter_slot].set(words, mode="drop")
        recv = lax.all_to_all(buf[:, :bucket_cap, :], axes, 0, 0)
        flat = {"__packed__": recv.reshape(P * bucket_cap, -1)}
        out, count_out, overflow_recv = compact(flat, keep, cap_out,
                                                kernels=kernels)
        out = unpack_columns(out["__packed__"], layout)
        return out, count_out, overflow_send | overflow_recv

    recv = {}
    for name, v in cols.items():
        buf = jnp.zeros((P, bucket_cap + 1), v.dtype)
        buf = buf.at[dest, scatter_slot].set(v, mode="drop")
        buf = buf[:, :bucket_cap]
        recv[name] = lax.all_to_all(buf, axes, 0, 0)

    flat = {k: v.reshape(-1) for k, v in recv.items()}
    out, count_out, overflow_recv = compact(flat, keep, cap_out, kernels=kernels)
    return out, count_out, overflow_send | overflow_recv


def shuffle_by_key(cols: dict[str, jax.Array], count, key_names, *,
                   axes: Axes, bucket_cap: int, cap_out: int,
                   kernels=None, packed: bool = True):
    """Hash-partition rows so equal (possibly composite) keys co-locate.

    ``key_names`` is a column name or a sequence of names; multiple names
    route on the combined hash (see :func:`hash_keys`).
    """
    if isinstance(key_names, str):
        key_names = (key_names,)
    P = nshards(axes) if axes else 1
    dest = (hash_keys(cols, key_names) % np.uint32(P)).astype(jnp.int32)
    return exchange(cols, count, dest, axes=axes, bucket_cap=bucket_cap,
                    cap_out=cap_out, kernels=kernels, packed=packed)


# ---------------------------------------------------------------------------
# local sort (bitonic via lax.sort — the TPU-native Timsort replacement)
# ---------------------------------------------------------------------------

def local_sort(cols: dict[str, jax.Array], count, key_names):
    """Stable lexicographic sort of valid rows by one or more key columns
    (padding sorts to the end via per-dtype max sentinels).

    ``key_names`` is a column name or a sequence of names (most-significant
    first); ``lax.sort`` with ``num_keys=len(keys)+1`` does the multi-key
    comparison natively on TPU.  Returns ``(sorted_cols, skeys)`` where
    ``skeys`` is the tuple of SENTINEL-MASKED sorted key arrays (one per name
    in ``key_names``) used for splitter sampling downstream.
    """
    if isinstance(key_names, str):
        key_names = (key_names,)
    key_names = tuple(key_names)
    cap = cols[key_names[0]].shape[0]
    valid = valid_mask(count, cap)
    keys = [jnp.where(valid, cols[kn], _sentinel(cols[kn].dtype))
            for kn in key_names]
    # stable tiebreaker: original index
    keys.append(jnp.arange(cap, dtype=jnp.int32))
    names = list(cols)
    operands = keys + [cols[n] for n in names]
    res = lax.sort(tuple(operands), num_keys=len(keys))
    sorted_keys = dict(zip(key_names, res[: len(keys) - 1]))
    sorted_cols = dict(zip(names, res[len(keys):]))
    # masked key columns come back with sentinels; restore real values where valid
    for kn, kv in sorted_keys.items():
        sorted_cols[kn] = jnp.where(valid, kv, jnp.zeros((), kv.dtype))
    return sorted_cols, tuple(sorted_keys[kn] for kn in key_names)


# ---------------------------------------------------------------------------
# merge join (rank join: one fused union sort; inputs need NOT be pre-sorted)
# ---------------------------------------------------------------------------


def lex_ranks(keycols: Sequence[jax.Array], valid: jax.Array):
    """Dense lexicographic ranks of row tuples via ONE multi-key sort.

    Sorts the tuples (``lax.sort`` with a stable index tiebreaker), detects
    run boundaries, and scatters the dense rank back to each row's original
    position.  Equal tuples share a rank and rank order equals lexicographic
    tuple order.  Invalid rows carry per-dtype max sentinels (they sort to
    the end) and get the int32 max sentinel rank.

    Returns ``(ranks, sidx, rank_sorted)``: per-original-row ranks, the
    original indices in sorted order, and the rank sequence in sorted order —
    the latter two let callers recover a key-sorted permutation without a
    second sort (merge join, sample-sort splitter routing).
    """
    n = keycols[0].shape[0]
    masked = [jnp.where(valid, k, _sentinel(k.dtype)) for k in keycols]
    idx = jnp.arange(n, dtype=jnp.int32)
    res = lax.sort(tuple(masked) + (idx,), num_keys=len(masked) + 1)
    sk, sidx = res[:-1], res[-1]
    neq = functools.reduce(jnp.logical_or, [k[1:] != k[:-1] for k in sk])
    boundary = jnp.concatenate([jnp.full((1,), True), neq])
    rank_sorted = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    ranks = jnp.zeros((n,), jnp.int32).at[sidx].set(rank_sorted)
    ranks = jnp.where(valid, ranks, _sentinel(jnp.int32))
    return ranks, sidx, rank_sorted


def merge_join(lcols, lcount, rcols, rcount, lkeys, rkeys, *,
               cap_out: int, r_suffix_map: dict[str, str], how: str = "inner",
               null_fill: dict[str, Any] | None = None):
    """Equi-join of two co-partitioned shards (inner or left-outer) on one
    or more key columns.  Inputs do NOT need to be pre-sorted.

    Both sides' key columns are concatenated and sorted ONCE as tuples
    (:func:`lex_ranks`); the same sort yields (a) a dense rank per row and
    (b) the right side's key-sorted permutation.  Expansion trick: per-left-
    row match counts -> prefix sums -> each output slot s maps back to
    (left row, offset within its match range) with two searchsorteds into
    the rank arrays; matched right rows are gathered through the
    permutation.  Output rows follow LEFT row order, so a sorted left input
    yields key-sorted output.  Left-outer: unmatched rows get count 1 and
    zero-filled right columns plus a ``_matched`` indicator (the
    static-shape NULL).  Fully static shapes; overflow flagged.
    """
    if isinstance(lkeys, str):
        lkeys = (lkeys,)
    if isinstance(rkeys, str):
        rkeys = (rkeys,)
    lkeys, rkeys = tuple(lkeys), tuple(rkeys)
    lcap = lcols[lkeys[0]].shape[0]
    rcap = rcols[rkeys[0]].shape[0]
    lvalid = valid_mask(lcount, lcap)
    rvalid = valid_mask(rcount, rcap)

    valid = jnp.concatenate([lvalid, rvalid])
    keycols = []
    for lk, rk in zip(lkeys, rkeys):
        la, ra = lcols[lk], rcols[rk]
        dt = jnp.promote_types(la.dtype, ra.dtype)
        keycols.append(jnp.concatenate([la.astype(dt), ra.astype(dt)]))
    ranks, sidx, rank_sorted = lex_ranks(keycols, valid)
    lrank = ranks[:lcap]

    # right rows in key-sorted order, extracted from the SAME sort: a stable
    # compaction of the sorted union down to right-side entries.
    is_r = (sidx >= lcap).astype(jnp.int32)
    pos_r = jnp.cumsum(is_r) - 1
    scat = jnp.where(is_r > 0, pos_r, lcap + rcap)
    rsorted_rank = jnp.full((rcap,), _sentinel(jnp.int32)) \
        .at[scat].set(rank_sorted, mode="drop")
    rperm = jnp.zeros((rcap,), jnp.int32) \
        .at[scat].set((sidx - lcap).astype(jnp.int32), mode="drop")

    lo = jnp.searchsorted(rsorted_rank, lrank, side="left")
    hi = jnp.searchsorted(rsorted_rank, lrank, side="right")
    hi = jnp.minimum(hi, rcount)
    lo = jnp.minimum(lo, rcount)
    matches = (hi - lo).astype(jnp.int32)
    cnt = jnp.where(lvalid, matches, 0)
    if how == "left":
        cnt = jnp.where(lvalid & (matches == 0), 1, cnt)

    incl = jnp.cumsum(cnt)
    excl = incl - cnt
    total = incl[-1] if lcap else jnp.int32(0)
    overflow = total > cap_out

    s = jnp.arange(cap_out, dtype=jnp.int32)
    li = jnp.searchsorted(incl, s, side="right")
    li_c = jnp.clip(li, 0, lcap - 1)
    matched = matches[li_c] > 0
    rpos = lo[li_c] + (s - excl[li_c])          # position in key-sorted right
    ri_c = rperm[jnp.clip(rpos, 0, rcap - 1)]   # original right row
    out_valid = s < jnp.minimum(total, cap_out)
    r_valid = out_valid & (matched if how == "left" else True)

    out = {}
    for name, v in lcols.items():
        out[name] = jnp.where(out_valid, v[li_c], jnp.zeros((), v.dtype))
    for name, v in rcols.items():
        if name in rkeys:
            continue
        # unmatched left rows NULL-fill right columns: NaN for floats, the
        # null dictionary code for categories (null_fill, from the schema);
        # other dtypes keep zero-fill + the _matched indicator.
        fill = jnp.asarray((null_fill or {}).get(name, 0), v.dtype)
        out[r_suffix_map.get(name, name)] = jnp.where(r_valid, v[ri_c], fill)
    if how == "left":
        out["_matched"] = (out_valid & matched).astype(jnp.int32)
    return out, jnp.minimum(total, cap_out).astype(jnp.int32), overflow


# ---------------------------------------------------------------------------
# segmented aggregation (group-by backend; sorted-key TPU idiom)
# ---------------------------------------------------------------------------

def null_mask(x: jax.Array, nulltag: str | None):
    """Row nullity under the in-band null encoding (docs/dtypes.md):
    ``"nan"`` — floats, null iff NaN; ``"code"`` — dictionary codes, null
    iff negative; ``None`` — the column cannot hold nulls."""
    if nulltag == "nan":
        if not jnp.issubdtype(x.dtype, jnp.floating):
            return jnp.zeros(x.shape, bool)
        return jnp.isnan(x)
    if nulltag == "code":
        return x < 0
    return None


def null_value(dtype, nulltag: str | None):
    """The in-band null of a value dtype (NaN / the null code)."""
    dtype = jnp.asarray(jnp.zeros((), dtype)).dtype
    if nulltag == "code" or not jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(-1, dtype)
    return jnp.asarray(jnp.nan, dtype)


def _value_spec(spec):
    """Normalize a values entry: (fn, x) or (fn, x, skipna, nulltag)."""
    if len(spec) == 2:
        fn, x = spec
        return fn, x, True, None
    fn, x, skipna, nulltag = spec
    return fn, x, skipna, nulltag


def segment_aggregate(keys_sorted, count, values: dict[str, tuple],
                      *, cap_out: int, kernels=None,
                      presorted: Sequence[str] = ()):
    """Aggregate ``values`` over runs of equal (grouped) composite keys.

    ``keys_sorted`` is one key array or a tuple of them; the valid prefix
    must have equal key tuples CONTIGUOUS (sorted by a key prefix, either
    direction — though ``nunique`` additionally requires ascending, see
    below).  A new run starts where ANY key column differs from the previous
    row.  values: name -> (fn, value_array) or (fn, value_array, skipna,
    nulltag) with fn in {sum, mean, count, min, max, prod, any, all, var,
    std, first, nunique} (``any``/``all`` reduce the truth of ``x != 0`` and
    return bool).

    ``nulltag`` ("nan" | "code" | None, see :func:`null_mask`) marks value
    columns that can hold in-band nulls; with ``skipna=True`` (pandas
    default) null rows are excluded from the reduction and all-null groups
    yield the null value; with ``skipna=False`` nulls poison their group's
    result.  ``count`` over a nullable column counts non-null rows (pandas
    ``count``); ``nunique`` always ignores nulls (pandas ``dropna=True``).
    Columns without a nulltag take the exact pre-null code paths.

    Any number of nunique columns is
    supported: each one re-sorts (keys..., x) independently with one
    ``lax.sort`` and counts within-run value boundaries; the aux sort is
    ascending, so its group order matches the main segment order only for
    ascending inputs (the physical planner inserts a LocalSort otherwise).
    ``presorted`` names nunique entries whose value column already arrives
    sorted WITHIN each key run (it rode the planner's LocalSort as a trailing
    sort key) — those skip the aux ``lax.sort`` and count boundaries off the
    main segment machinery directly.
    Returns ``({__key0__..., **aggs}, n_groups, overflow)`` with one output
    column per key, in key order, named ``__key<i>__``.
    """
    if not isinstance(keys_sorted, (tuple, list)):
        keys_sorted = (keys_sorted,)
    keys_sorted = tuple(keys_sorted)
    cap = keys_sorted[0].shape[0]
    valid = valid_mask(count, cap)
    neq = functools.reduce(jnp.logical_or,
                           [k[1:] != k[:-1] for k in keys_sorted])
    prev = jnp.concatenate([jnp.full((1,), True), neq])
    seg_start = valid & prev
    seg_id = jnp.cumsum(seg_start.astype(jnp.int32)) - 1
    seg_id = jnp.where(valid, seg_id, cap_out)          # padding -> dropped
    n_seg = jnp.sum(seg_start.astype(jnp.int32))
    overflow = n_seg > cap_out

    def ssum(x, v=None):
        v = valid if v is None else v
        if x.dtype == jnp.bool_:
            x = x.astype(jnp.int32)      # sum(:x < 1.0) counts True rows
        if jnp.issubdtype(x.dtype, jnp.floating):
            # registry segment_sums: ref is the dtype-preserving
            # jax.ops.segment_sum composition; the Pallas backend reads
            # each run's total off a per-run f32 scan (segment_reduce).
            return _K(kernels).segment_sums(x, seg_id, v, cap_out)
        # integer sums stay on segment_sum directly for exactness (the
        # Pallas kernel accumulates in f32).
        return jax.ops.segment_sum(jnp.where(v, x, jnp.zeros((), x.dtype)),
                                   seg_id, num_segments=cap_out + 1)[:cap_out]

    def smin(x, v=None):
        v = valid if v is None else v
        if x.dtype == jnp.bool_:
            x = x.astype(jnp.int32)      # bool has no iinfo sentinel
        big = _sentinel(x.dtype)
        return jax.ops.segment_min(jnp.where(v, x, big), seg_id,
                                   num_segments=cap_out + 1)[:cap_out]

    def smax(x, v=None):
        v = valid if v is None else v
        if x.dtype == jnp.bool_:
            x = x.astype(jnp.int32)
        if jnp.issubdtype(x.dtype, jnp.floating):
            small = jnp.array(jnp.finfo(x.dtype).min, x.dtype)
        else:
            small = jnp.array(jnp.iinfo(x.dtype).min, x.dtype)
        return jax.ops.segment_max(jnp.where(v, x, small), seg_id,
                                   num_segments=cap_out + 1)[:cap_out]

    def sprod(x, v=None):
        v = valid if v is None else v
        if x.dtype == jnp.bool_:
            x = x.astype(jnp.int32)
        one = jnp.ones((), x.dtype)
        return jax.ops.segment_prod(jnp.where(v, x, one), seg_id,
                                    num_segments=cap_out + 1)[:cap_out]

    ones = valid.astype(jnp.int32)
    group_n = jax.ops.segment_sum(ones, seg_id, num_segments=cap_out + 1)[:cap_out]

    out: dict[str, jax.Array] = {}
    for i, ks in enumerate(keys_sorted):
        neg = (jnp.array(jnp.iinfo(ks.dtype).min, ks.dtype)
               if jnp.issubdtype(ks.dtype, jnp.integer)
               else jnp.array(jnp.finfo(ks.dtype).min, ks.dtype))
        out[f"__key{i}__"] = jax.ops.segment_max(
            jnp.where(valid, ks, neg),
            seg_id, num_segments=cap_out + 1)[:cap_out]

    for name, spec in values.items():
        fn, x, skipna, nulltag = _value_spec(spec)
        nullm = null_mask(x, nulltag) if x is not None else None
        # vvalid: rows contributing under skipna; vn: their per-group count;
        # has_null: whether the group saw a null (skipna=False poisoning).
        vvalid = valid if nullm is None else valid & ~nullm
        vn = has_null = None
        if nullm is not None:
            vn = jax.ops.segment_sum(vvalid.astype(jnp.int32), seg_id,
                                     num_segments=cap_out + 1)[:cap_out]
            has_null = vn < group_n

        def _null_out(res, dt):
            """null-fill groups with no contributing rows (skipna) or with
            any null row (skipna=False)."""
            if nullm is None:
                return res
            bad = (vn == 0) if skipna else has_null
            return jnp.where(bad, null_value(dt, nulltag).astype(dt), res)

        if fn == "count":
            out[name] = group_n if nullm is None else vn
        elif fn == "sum":
            # skipna sum of an all-null group is 0 (pandas); skipna=False
            # lets NaN propagate — codes are never summed.
            out[name] = ssum(x, vvalid if skipna else valid)
        elif fn == "mean":
            xf = x.astype(jnp.float32)
            v = vvalid if skipna else valid
            n = vn if (skipna and nullm is not None) else group_n
            res = ssum(xf, v) / jnp.maximum(n, 1)
            out[name] = _null_out(res, res.dtype)
        elif fn == "min":
            res = smin(x, vvalid if skipna else valid)
            out[name] = _null_out(res, res.dtype)
        elif fn == "max":
            res = smax(x, vvalid if skipna else valid)
            out[name] = _null_out(res, res.dtype)
        elif fn == "prod":
            # skipna prod of an all-null group is 1 (pandas)
            out[name] = sprod(x, vvalid if skipna else valid)
        elif fn == "any":
            # skipna: nulls never assert truth; skipna=False: NaN is truthy
            # (x != 0 holds for NaN), matching pandas
            flag = (x != 0).astype(jnp.int32)
            out[name] = smax(flag, vvalid if skipna else valid) > 0
        elif fn == "all":
            flag = (x != 0).astype(jnp.int32)
            out[name] = smin(flag, vvalid if skipna else valid) > 0
        elif fn in ("var", "std"):
            xf = x.astype(jnp.float32)
            v = vvalid if skipna else valid
            n = vn if (skipna and nullm is not None) else group_n
            m = ssum(xf, v) / jnp.maximum(n, 1)
            m2 = ssum(xf * xf, v) / jnp.maximum(n, 1)
            var = jnp.maximum(m2 - m * m, 0.0)
            res = jnp.sqrt(var) if fn == "std" else var
            out[name] = _null_out(res, res.dtype)
        elif fn == "first":
            # pandas groupby.first(skipna=True) takes the first NON-NULL
            v = vvalid if skipna else valid
            first_idx = jax.ops.segment_min(
                jnp.where(v, jnp.arange(cap, dtype=jnp.int32), cap),
                seg_id, num_segments=cap_out + 1)[:cap_out]
            res = x[jnp.clip(first_idx, 0, cap - 1)]
            if nullm is not None and skipna:
                res = jnp.where(first_idx >= cap,
                                null_value(res.dtype, nulltag).astype(res.dtype),
                                res)
            out[name] = res
        elif fn == "nunique" and name in presorted:
            # aux-sort elision: x is already sorted within each key run (it
            # was a trailing key of the planner's LocalSort), so distinct
            # values are contiguous and boundaries fall out of the MAIN
            # segment machinery — no extra lax.sort.
            vprev = jnp.concatenate([jnp.full((1,), True), x[1:] != x[:-1]])
            boundary = (seg_start | vprev) & vvalid   # nulls never distinct
            out[name] = jax.ops.segment_sum(boundary.astype(jnp.int32), seg_id,
                                            num_segments=cap_out + 1)[:cap_out]
        elif fn == "nunique":
            # independent aux sort by (keys..., x): groups x within each key
            # run.  Group ORDER matches the main segment order because both
            # enumerate distinct key tuples ascending (see docstring).
            masked = [jnp.where(valid, k, _sentinel(k.dtype))
                      for k in keys_sorted]
            res = lax.sort(tuple(masked) + (x,), num_keys=len(masked) + 1)
            sx = res[-1]
            neq2 = functools.reduce(jnp.logical_or,
                                    [k[1:] != k[:-1] for k in res[:-1]])
            prev2 = jnp.concatenate([jnp.full((1,), True), neq2])
            seg_start2 = valid & prev2          # valid rows stay a prefix
            seg_id2 = jnp.cumsum(seg_start2.astype(jnp.int32)) - 1
            seg_id2 = jnp.where(valid, seg_id2, cap_out)
            vprev = jnp.concatenate([jnp.full((1,), True), sx[1:] != sx[:-1]])
            boundary = (seg_start2 | vprev) & valid
            snullm = null_mask(sx, nulltag)
            if snullm is not None:
                boundary = boundary & ~snullm   # null runs don't count
            out[name] = jax.ops.segment_sum(boundary.astype(jnp.int32), seg_id2,
                                            num_segments=cap_out + 1)[:cap_out]
        else:
            raise ValueError(fn)
    gvalid = jnp.arange(cap_out, dtype=jnp.int32) < jnp.minimum(n_seg, cap_out)
    for name in out:
        out[name] = jnp.where(gvalid, out[name], jnp.zeros((), out[name].dtype))
    return out, jnp.minimum(n_seg, cap_out).astype(jnp.int32), overflow


# ---------------------------------------------------------------------------
# map-side partial aggregation (combiner algebra for the shuffle engine)
#
# Every decomposable agg fn splits into partial statistics a shard can
# pre-reduce over its LOCAL key groups before the hash exchange, so the wire
# carries at most the shard's DISTINCT key tuples instead of all raw rows.
# The WHOLE algebra lives in one table (AGG_DECOMP): per fn, the partial
# columns it decomposes into — suffix, map-side segment fn, reduce-side
# combine fn, wire dtype rule, input transform — plus the finalizer that
# folds the combined partials into the result.  partial_decompose /
# final_aggregate / the planner's schema annotation all read this table, so
# adding a decomposable fn is ONE entry (prod, any and all below are exactly
# that).
#
# first (arrival-order-sensitive) and nunique (set-valued partial state)
# are NOT decomposable — the planner keeps those on the raw-row path.
# ---------------------------------------------------------------------------


class PartialSpec:
    """One partial column of a decomposable aggregation.

    ``suffix``     the wire column is named ``__p_<out>__<suffix>``
    ``partial_fn`` segment fn reducing raw rows map-side
    ``combine_fn`` segment fn merging per-shard partials reduce-side
                   (count partials COMBINE by sum, hence the split)
    ``dtype``      wire dtype as a function of the value column's dtype
    ``prep``       input transform applied before the partial stage
    """

    __slots__ = ("suffix", "partial_fn", "combine_fn", "dtype", "prep")

    def __init__(self, suffix, partial_fn, combine_fn=None, dtype=None,
                 prep=None):
        self.suffix = suffix
        self.partial_fn = partial_fn
        self.combine_fn = combine_fn or partial_fn
        self.dtype = dtype or (lambda vd: np.dtype(np.int32)
                               if np.dtype(vd) == np.bool_ else np.dtype(vd))
        self.prep = prep or (lambda x: x)


def _dt_i32(_vd):
    return np.dtype(np.int32)


def _dt_f32(_vd):
    return np.dtype(np.float32)


def _as_f32(x):
    return x.astype(jnp.float32)


def _as_flag(x):
    return (x != 0).astype(jnp.int32)


def _as_int_if_bool(x):
    # min/max of a bool column compare as 0/1 int32 (bool has no sentinel;
    # the raw-path smin/smax apply the same cast, so both paths agree).
    return x.astype(jnp.int32) if x.dtype == jnp.bool_ else x


def _mean_final(p):
    return p["s"] / jnp.maximum(p["n"], 1)


def _var_final(p):
    n = jnp.maximum(p["n"], 1)
    m = p["s"] / n
    m2 = p["q"] / n
    return jnp.maximum(m2 - m * m, 0.0)


# fn -> (partial column specs, finalize(dict suffix -> combined array))
AGG_DECOMP: dict[str, tuple[tuple[PartialSpec, ...], Any]] = {
    "sum":   ((PartialSpec("s", "sum"),), lambda p: p["s"]),
    "count": ((PartialSpec("n", "count", combine_fn="sum", dtype=_dt_i32),),
              lambda p: p["n"]),
    "min":   ((PartialSpec("m", "min", prep=_as_int_if_bool),),
              lambda p: p["m"]),
    "max":   ((PartialSpec("m", "max", prep=_as_int_if_bool),),
              lambda p: p["m"]),
    "prod":  ((PartialSpec("p", "prod"),), lambda p: p["p"]),
    "any":   ((PartialSpec("b", "max", dtype=_dt_i32, prep=_as_flag),),
              lambda p: p["b"] != 0),
    "all":   ((PartialSpec("b", "min", dtype=_dt_i32, prep=_as_flag),),
              lambda p: p["b"] != 0),
    "mean":  ((PartialSpec("s", "sum", dtype=_dt_f32, prep=_as_f32),
               PartialSpec("n", "count", combine_fn="sum", dtype=_dt_i32)),
              _mean_final),
    "var":   ((PartialSpec("s", "sum", dtype=_dt_f32, prep=_as_f32),
               PartialSpec("q", "sum", dtype=_dt_f32,
                           prep=lambda x: _as_f32(x) * _as_f32(x)),
               PartialSpec("n", "count", combine_fn="sum", dtype=_dt_i32)),
              _var_final),
    "std":   ((PartialSpec("s", "sum", dtype=_dt_f32, prep=_as_f32),
               PartialSpec("q", "sum", dtype=_dt_f32,
                           prep=lambda x: _as_f32(x) * _as_f32(x)),
               PartialSpec("n", "count", combine_fn="sum", dtype=_dt_i32)),
              lambda p: jnp.sqrt(_var_final(p))),
}

DECOMPOSABLE_AGGS = frozenset(AGG_DECOMP)


def _agg_null_spec(fn: str, skipna: bool, nulltag: str | None):
    """Normalize a final_aggregate ``agg_fns`` entry (str, or a tuple of
    (fn, skipna, nulltag)) — nulltag None means the pre-null exact path."""
    return fn, skipna, nulltag


def decomposable(fn: str, skipna: bool = True, nulltag: str | None = None) -> bool:
    """Whether this agg can take the partial/final two-stage path.

    ``skipna=False`` on a nullable column needs the group's full row set to
    poison correctly, so the planner keeps it on the raw single-stage path.
    """
    if fn not in AGG_DECOMP:
        return False
    return skipna or nulltag is None


def _partial_marker(partial_fn: str, dtype):
    """The in-band "no contributing rows" marker a null-masked partial
    min/max reduces to: the same sentinel the validity masking uses, so an
    all-null group's partial is the sentinel on every shard and survives the
    combine.  The finalizer maps it to the null value."""
    if partial_fn == "min":
        return _sentinel(dtype)
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.finfo(dtype).min, dtype)
    return jnp.array(jnp.iinfo(dtype).min, dtype)


def partial_decompose(name: str, fn: str, x: jax.Array, skipna: bool = True,
                      nulltag: str | None = None):
    """Partial-column specs for one decomposable agg output: a list of
    ``(partial_name, partial_fn, array)`` triples feeding segment_aggregate.

    With a ``nulltag`` the preps implement skipna map-side: null rows
    contribute the reduction identity (0 for sums, 1 for prod, the sentinel
    for min/max) and count partials count NON-null rows — so the wire schema
    (column count and dtypes) is identical to the null-free decomposition
    and the finalizer undoes the identities (docs/dtypes.md).
    """
    if not decomposable(fn, skipna, nulltag):
        raise ValueError(f"{fn} is not decomposable")
    specs, _final = AGG_DECOMP[fn]
    nullm = null_mask(x, nulltag) if x is not None else None
    out = []
    for s in specs:
        pcol = f"__p_{name}__{s.suffix}"
        if nullm is None:
            out.append((pcol, s.partial_fn, s.prep(x)))
            continue
        if s.partial_fn == "count":
            # count partials become sums of the non-null flag (same wire
            # column name/dtype; the combine is already "sum")
            out.append((pcol, "sum", (~nullm).astype(jnp.int32)))
            continue
        arr = s.prep(x)
        if s.partial_fn in ("min", "max"):
            ident = _partial_marker(s.partial_fn, arr.dtype)
        elif s.partial_fn == "prod":
            ident = jnp.ones((), arr.dtype)
        else:                                   # sum
            ident = jnp.zeros((), arr.dtype)
        out.append((pcol, s.partial_fn, jnp.where(nullm, ident, arr)))
    return out


def partial_aggregate(keys_sorted, count, values: dict[str, tuple],
                      *, cap_out: int, kernels=None):
    """Map-side stage: reduce each LOCAL key run to its partial statistics.

    Same grouped-input contract, values-entry forms ((fn, x) or
    (fn, x, skipna, nulltag)) and ``(__key<i>__, ...)`` output convention
    as :func:`segment_aggregate`; the output rows (one per local distinct key
    tuple) are what the hash exchange ships.
    """
    pvals: dict[str, tuple[str, jax.Array]] = {}
    for name, spec in values.items():
        fn, x, skipna, nulltag = _value_spec(spec)
        for pcol, pfn, arr in partial_decompose(name, fn, x, skipna, nulltag):
            pvals[pcol] = (pfn, arr)
    return segment_aggregate(keys_sorted, count, pvals, cap_out=cap_out,
                             kernels=kernels)


def final_aggregate(keys_sorted, count, agg_fns: dict[str, Any],
                    cols: dict[str, jax.Array], *, cap_out: int,
                    kernels=None):
    """Reduce-side stage: combine :func:`partial_aggregate` rows from every
    shard (grouped by key after the exchange + local sort) into final
    results.  ``agg_fns`` maps output name -> original agg fn (a bare str,
    or ``(fn, skipna, nulltag)`` for nullable value columns); ``cols``
    holds the partial ``__p_<name>__*`` columns.
    """
    norm = {name: (_agg_null_spec(*spec) if isinstance(spec, tuple)
                   else (spec, True, None))
            for name, spec in agg_fns.items()}
    cvals: dict[str, tuple[str, jax.Array]] = {}
    for name, (fn, skipna, tag) in norm.items():
        if not decomposable(fn, skipna, tag):
            raise ValueError(f"{fn} is not decomposable")
        for s in AGG_DECOMP[fn][0]:
            pcol = f"__p_{name}__{s.suffix}"
            cvals[pcol] = (s.combine_fn, cols[pcol])
    agg, n_seg, ovf = segment_aggregate(keys_sorted, count, cvals,
                                        cap_out=cap_out, kernels=kernels)
    gvalid = jnp.arange(cap_out, dtype=jnp.int32) < n_seg
    out = {k: v for k, v in agg.items() if k.startswith("__key")}
    for name, (fn, skipna, nulltag) in norm.items():
        specs, final = AGG_DECOMP[fn]
        p = {s.suffix: agg[f"__p_{name}__{s.suffix}"] for s in specs}
        res = final(p)
        if nulltag is not None and skipna:
            # undo the skipna identities: all-null groups reduced to the
            # pure marker/identity — map them back to the null value
            if fn in ("min", "max"):
                pf = specs[0].partial_fn
                marker = _partial_marker(pf, res.dtype)
                res = jnp.where(gvalid & (res == marker),
                                null_value(res.dtype, nulltag).astype(res.dtype),
                                res)
            elif fn in ("mean", "var", "std"):
                res = jnp.where(gvalid & (p["n"] == 0),
                                null_value(res.dtype, nulltag).astype(res.dtype),
                                res)
        out[name] = res
    return out, n_seg, ovf


# ---------------------------------------------------------------------------
# partitioned (segmented) windows — OVER (PARTITION BY ... ORDER BY ...)
#
# The physical planner guarantees the input is hash-partitioned on the
# partition keys (every group lives whole on ONE shard) and locally sorted by
# (partition keys, order keys), so all three kernels below are collective-free
# segment computations: the group-by layout that makes relational planning
# and array analytics compose (paper's core thesis).
# ---------------------------------------------------------------------------

def run_starts(keys: Sequence[jax.Array], valid: jax.Array) -> jax.Array:
    """Boolean mask: True at the first row of each run of equal key tuples
    (grouped input).  Invalid rows are never starts."""
    neq = functools.reduce(jnp.logical_or, [k[1:] != k[:-1] for k in keys])
    return valid & jnp.concatenate([jnp.full((1,), True), neq])


def _segment_first_index(seg_start: jax.Array) -> jax.Array:
    """For every row, the index of its segment's first row (running max of
    start positions; rows before the first start map to 0)."""
    idx = jnp.arange(seg_start.shape[0], dtype=jnp.int32)
    return lax.cummax(jnp.where(seg_start, idx, 0))


def segment_cumsum(x: jax.Array, part_keys: Sequence[jax.Array], count,
                   kernels=None, nulltag: str | None = None):
    """Grouped cumulative sum via the registry's ``segment_scan`` primitive.
    The ref backend is a plain inclusive scan minus the running total at each
    row's segment start (segment-reset exscan); the Pallas backend fuses the
    boundary mask and the scan into one pass.  No collectives — groups are
    shard-local under hash(partition_by).

    With a ``nulltag`` the semantics match pandas cumsum on nullable data:
    null rows stay null in the output and the running total skips them.
    """
    cap = x.shape[0]
    valid = valid_mask(count, cap)
    nullm = null_mask(x, nulltag)
    skip = valid if nullm is None else valid & ~nullm
    xz = jnp.where(skip, x, jnp.zeros((), x.dtype))
    if xz.dtype == jnp.bool_:
        xz = xz.astype(jnp.int32)        # cumsum of bool promotes anyway
    seg_start = run_starts(part_keys, valid)
    out = _K(kernels).segment_scan(xz, seg_start.astype(jnp.int32))
    if nullm is not None:
        out = jnp.where(nullm, null_value(out.dtype, nulltag).astype(out.dtype),
                        out)
    return jnp.where(valid, out, jnp.zeros((), out.dtype))


def segment_stencil1d(x: jax.Array, part_keys: Sequence[jax.Array], count,
                      weights: Sequence[float], center: int,
                      exact: bool = False, kernels=None):
    """Boundary-masked 1-D stencil: taps that would cross a group edge are
    zeroed (the zero-border convention applied per group).  No halo exchange
    — groups are shard-local, so neighbors outside the group are simply
    masked by segment-id mismatch.  The tap loop (and the ``exact`` mass
    renormalize, fused) resolves through the registry's ``segment_stencil``
    primitive.

    ``exact=True`` renormalizes each output by the realized weight mass:
    rows near a group edge divide by the weights of the taps that actually
    contributed instead of the full window (for uniform weights this is
    pandas' ``min_periods=1`` exact rolling mean; interior rows are
    untouched since their mass is the full weight sum).
    """
    w = [float(v) for v in weights]
    k_left, k_right = center, len(w) - 1 - center
    cap = x.shape[0]
    valid = valid_mask(count, cap)
    xz = jnp.where(valid, x.astype(jnp.float32), 0.0)
    seg_start = run_starts(part_keys, valid)
    sid = jnp.cumsum(seg_start.astype(jnp.int32)) - 1
    sid = jnp.where(valid, sid, -1)                 # padding never matches
    ext_x = jnp.concatenate([jnp.zeros((k_left,), jnp.float32), xz,
                             jnp.zeros((k_right,), jnp.float32)])
    ext_s = jnp.concatenate([jnp.full((k_left,), -2, jnp.int32), sid,
                             jnp.full((k_right,), -2, jnp.int32)])
    out = _K(kernels).segment_stencil(ext_x, ext_s, w, center, exact)
    return jnp.where(valid, out, 0.0)


def segment_rank(part_keys: Sequence[jax.Array],
                 order_keys: Sequence[jax.Array], count, kind: str,
                 kernels=None):
    """SQL ranking within groups of rows sorted by (part_keys, order_keys).

    row_number: 1-based position in the group (ties broken by the stable
    sort).  rank: 1 + position of the first row with the same order-key
    tuple (ties share, gaps after).  dense_rank: 1 + number of distinct
    order-key tuples before this row's (ties share, no gaps).  The two
    boundary masks (group starts, (group, order) run starts — every group
    start is also a run start) feed the registry's ``segment_rank``
    primitive; the ref backend composes cummax-located head indices, the
    Pallas backend runs fused segmented scans of the masks.
    """
    if kind not in ("row_number", "rank", "dense_rank"):
        raise ValueError(kind)
    cap = part_keys[0].shape[0]
    valid = valid_mask(count, cap)
    seg_start = run_starts(part_keys, valid)
    if kind == "row_number":
        order_start = seg_start
    else:
        order_start = run_starts(tuple(part_keys) + tuple(order_keys), valid)
    r = _K(kernels).segment_rank(seg_start.astype(jnp.int32),
                                 order_start.astype(jnp.int32), kind)
    return jnp.where(valid, r, 0).astype(jnp.int32)


def global_rank(order_keys: Sequence[jax.Array], count, cap: int, kind: str,
                axes: Axes, method: str = "allgather", kernels=None):
    """GLOBAL SQL ranking (no PARTITION BY) over the shard-concatenated
    stream, via a per-shard-count exscan — never a second global sort.

    row_number: 1-based global position in arrival order (an exclusive scan
    of the per-shard valid counts plus the local index).  rank/dense_rank:
    REQUIRE equal order-key tuples adjacent across the global stream (the
    planner guarantees it; api.rank sorts first).  Cross-shard tie runs are
    reconciled from tiny all-gathered per-shard scalars — each shard's
    count, first/last key tuple, trailing-run start and run count — so the
    only collectives are O(P) scalar gathers, no row movement.
    """
    if kind not in ("row_number", "rank", "dense_rank"):
        raise ValueError(kind)
    valid = valid_mask(count, cap)
    cnt = jnp.asarray(count, jnp.int32).reshape(())
    idx = jnp.arange(cap, dtype=jnp.int32)
    P = nshards(axes) if axes else 1

    if kind == "row_number":
        base = (exscan_scalar(cnt, axes, method=method) if axes
                else jnp.int32(0))
        return jnp.where(valid, base + idx + 1, 0).astype(jnp.int32)

    keys = tuple(order_keys)
    order_start = run_starts(keys, valid)
    start_idx = _segment_first_index(order_start)       # local run-start index
    run_ord = jnp.cumsum(order_start.astype(jnp.int32))  # 1-based local run #

    if P == 1:
        r = start_idx + 1 if kind == "rank" else run_ord
        return jnp.where(valid, r, 0).astype(jnp.int32)

    # -- tiny boundary gathers (one scalar all_gather per quantity) ----------
    last_i = jnp.clip(cnt - 1, 0, cap - 1)
    t_loc = start_idx[last_i]                   # trailing run's local start
    nruns = jnp.sum(order_start.astype(jnp.int32))
    gather = functools.partial(lax.all_gather, axis_name=axes, tiled=False)
    cnts = gather(cnt)                                          # (P,)
    ts = gather(t_loc)
    runs = gather(nruns)
    firsts = [gather(k[0]) for k in keys]
    lasts = [gather(k[last_i]) for k in keys]
    bases = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                             jnp.cumsum(cnts)[:-1]])            # exclusive
    r_me = my_rank(axes)
    base = bases[r_me]

    def key_eq(cols_a, j, cols_b):
        return functools.reduce(
            jnp.logical_and, [a[j] == b for a, b in zip(cols_a, cols_b)])

    if kind == "rank":
        # Walk backward from my shard: while the previous shard's trailing
        # run carries my first key, my leading run started there (or
        # earlier, when that whole shard is the key).  P is static and
        # small, so the walk unrolls to scalar selects.
        fk = [k[0] for k in keys]
        g = base                                 # leading run's global start
        alive = cnt > 0
        for step in range(1, P):
            j = jnp.maximum(r_me - step, 0)
            inb = (r_me - step >= 0) & alive
            nonempty = cnts[j] > 0
            take = inb & nonempty & key_eq(lasts, j, fk)
            g = jnp.where(take, bases[j] + ts[j], g)
            alive = inb & (~nonempty | (take & (ts[j] == 0)))
        out = jnp.where(start_idx == 0, g, base + start_idx) + 1
        return jnp.where(valid, out, 0).astype(jnp.int32)

    # dense_rank: distinct runs in shards before mine, minus the boundary
    # merges (a run continuing across consecutive non-empty shards counts
    # once).  M[j] = shard j's first key equals the last key of the nearest
    # previous non-empty shard.
    prev_any = jnp.bool_(False)
    prev_last = [jnp.zeros((), k.dtype) for k in keys]
    merges = []
    for j in range(P):                           # static unroll
        nonempty = cnts[j] > 0
        merges.append(nonempty & prev_any & key_eq(firsts, j, prev_last))
        prev_last = [jnp.where(nonempty, c[j], p)
                     for c, p in zip(lasts, prev_last)]
        prev_any = prev_any | nonempty
    m = jnp.stack(merges).astype(jnp.int32)
    sh = jnp.arange(P)
    runs_before = (jnp.sum(jnp.where(sh < r_me, runs, 0))
                   - jnp.sum(jnp.where(sh <= r_me, m, 0)))
    return jnp.where(valid, runs_before + run_ord, 0).astype(jnp.int32)


# ---------------------------------------------------------------------------
# distributed scans (MPI_Exscan analogue)
# ---------------------------------------------------------------------------

def exscan_scalar(v, axes: Axes, method: str = "allgather"):
    """Exclusive prefix-sum of a per-shard scalar across shards."""
    P = nshards(axes)
    if P == 1:
        return jnp.zeros_like(v)
    if method == "ladder" and len(axes) == 1:
        # Hillis–Steele ladder over ppermute: log2(P) hops on the ICI ring.
        x = v
        shift = 1
        while shift < P:
            y = lax.ppermute(x, axes[0],
                             perm=[(i, i + shift) for i in range(P - shift)])
            x = x + y
            shift *= 2
        return x - v
    idx = my_rank(axes)
    allv = lax.all_gather(v, axes, tiled=False)          # (P, ...)
    ranks = jnp.arange(P)
    mask = (ranks < idx).astype(allv.dtype)
    return jnp.tensordot(mask, allv, axes=1)


def dist_cumsum(x: jax.Array, count, axes: Axes, method: str = "allgather",
                kernels=None):
    """Distributed cumulative sum over the valid prefix of each shard."""
    valid = valid_mask(count, x.shape[0])
    xz = jnp.where(valid, x, jnp.zeros((), x.dtype))
    local = _K(kernels).prefix_sum(xz) if x.shape[0] else xz
    total = local[-1] if x.shape[0] else jnp.zeros((), x.dtype)
    base = exscan_scalar(total, axes, method=method)
    return local + base


# ---------------------------------------------------------------------------
# 1-D stencil with halo exchange (SMA / WMA)
# ---------------------------------------------------------------------------

def halo_exchange(x: jax.Array, count, k_left: int, k_right: int, axes: Axes):
    """Count-aware halo exchange over the valid prefixes.

    Each shard's valid rows are the prefix ``x[:count]``; the global array is
    the concatenation of the prefixes.  The left halo is the left neighbor's
    *valid tail* ``x[count-k : count]``; the right halo is the right
    neighbor's (masked) head ``x[:k]``.  Zeros at the global borders.  The
    window radius must not exceed the smallest non-empty shard count (true
    for 1D_BLOCK layouts with radius << block — asserted at plan time).
    """
    P = nshards(axes) if axes else 1
    cap = x.shape[0]
    xz = jnp.where(valid_mask(count, cap), x, jnp.zeros((), x.dtype))
    left = jnp.zeros((k_left,), x.dtype)
    right = jnp.zeros((k_right,), x.dtype)
    if P == 1:
        return left, right
    my_tail = lax.dynamic_slice(
        xz, (jnp.maximum(count - k_left, 0),), (max(k_left, 1),))[:k_left] \
        if k_left else jnp.zeros((0,), x.dtype)
    my_head = xz[:k_right] if k_right else jnp.zeros((0,), x.dtype)
    if len(axes) == 1:
        ax = axes[0]
        if k_left:
            left = lax.ppermute(my_tail, ax,
                                perm=[(i, i + 1) for i in range(P - 1)])
        if k_right:
            right = lax.ppermute(my_head, ax,
                                 perm=[(i + 1, i) for i in range(P - 1)])
    else:
        # multi-axis fallback: gather edges, select flat neighbors
        idx = my_rank(axes)
        if k_left:
            edges = lax.all_gather(my_tail, axes)         # (P, k)
            left = jnp.where(idx > 0, edges[jnp.maximum(idx - 1, 0)], left)
        if k_right:
            edges = lax.all_gather(my_head, axes)
            right = jnp.where(idx < P - 1,
                              edges[jnp.minimum(idx + 1, P - 1)], right)
    return left, right


def stencil1d(x: jax.Array, count, weights: Sequence[float], center: int,
              axes: Axes, kernels=None, exact: bool = False):
    """out[i] = sum_j w[j] * x[i + j - center] over the distributed valid
    prefix, halos from neighbors (paper's SMA/WMA; MPI_Isend/Irecv analogue).

    The windowed weighted sum resolves through the registry's ``stencil1d``
    primitive (kernels/stencil1d Pallas kernel vs the jnp sliding-window
    ref).

    ``exact=True`` renormalizes rows near the GLOBAL borders by the realized
    weight mass (see :func:`segment_stencil1d`): the mass is the same
    stencil applied to a ones-vector through the same halo machinery, so a
    tap into a populated neighbor shard counts while a tap past the global
    ends does not.  Both stencils and the renormalize fuse into ONE
    ``stencil1d_exact`` kernel pass (the halo exchange for the mass vector
    still happens — masses near shard edges depend on neighbor validity).
    """
    w = [float(v) for v in weights]
    k_left, k_right = center, len(w) - 1 - center
    cap = x.shape[0]
    valid = valid_mask(count, cap)

    def build_ext(vals):
        vz = jnp.where(valid, vals.astype(jnp.float32), 0.0)
        left, right = halo_exchange(vz, count, k_left, k_right, axes)
        # ext[k_left + i] = v[i] (valid rows), right halo lands AT the
        # dynamic position k_left + count so windows never straddle padding.
        ext = jnp.zeros((cap + k_left + k_right,), jnp.float32)
        ext = lax.dynamic_update_slice(ext, vz, (k_left,))
        if k_right:
            ext = lax.dynamic_update_slice(ext, right, (k_left + count,))
        if k_left:
            ext = lax.dynamic_update_slice(ext, left, (0,))
        return ext

    kset = _K(kernels)
    if exact:
        out = kset.stencil1d_exact(build_ext(x),
                                   build_ext(jnp.ones((cap,), jnp.float32)), w)
    else:
        out = kset.stencil1d(build_ext(x), w)
    return jnp.where(valid, out, 0.0)


# ---------------------------------------------------------------------------
# limit (first n rows in global shard-concatenation order; df.head backend)
# ---------------------------------------------------------------------------

def limit(cols: dict[str, jax.Array], count, n: int, axes: Axes,
          cap_out: int):
    """Keep the first ``n`` valid rows of the global concatenation.

    No rows move: each shard clamps its valid count to its slice of
    ``[0, n)`` via an exclusive scan of counts (REP inputs skip even that —
    every shard independently keeps its first ``n``).  Buffers shrink to
    ``cap_out`` (valid rows always fit: the clamped count is <= n <=
    cap_out).
    """
    if axes:
        base = exscan_scalar(count.astype(jnp.int32), axes)
    else:
        base = jnp.int32(0)
    cnt = jnp.clip(jnp.int32(n) - base, 0, count).astype(jnp.int32)
    out = {k: v[:cap_out] for k, v in cols.items()}
    return out, cnt


# ---------------------------------------------------------------------------
# rebalance (1D_VAR -> 1D_BLOCK) and sample sort
# ---------------------------------------------------------------------------

def rebalance(cols: dict[str, jax.Array], count, *, axes: Axes,
              bucket_cap: int, cap_out: int, kernels=None,
              packed: bool = True):
    """Even out row counts across shards, preserving global row order."""
    P = nshards(axes) if axes else 1
    cap = next(iter(cols.values())).shape[0]
    if P == 1:
        return compact(cols, valid_mask(count, cap), cap_out, kernels=kernels)
    counts = lax.all_gather(count, axes)                 # (P,)
    total = jnp.sum(counts)
    base = exscan_scalar(count, axes)
    block = (total + P - 1) // P                          # ceil
    g = base + jnp.arange(cap, dtype=jnp.int32)
    dest = jnp.where(valid_mask(count, cap),
                     g // jnp.maximum(block, 1), P).astype(jnp.int32)
    out, cnt, ovf = exchange(cols, count, dest, axes=axes,
                             bucket_cap=bucket_cap, cap_out=cap_out,
                             kernels=kernels, packed=packed)
    return out, cnt, ovf


def sample_sort(cols: dict[str, jax.Array], count, key_names, *,
                axes: Axes, bucket_cap: int, cap_out: int, n_samples: int = 64,
                ascending: bool = True, pre_sorted: bool = False,
                kernels=None, packed: bool = True):
    """Global sort: local sort -> splitter selection -> route -> local sort.

    ``key_names`` may name several columns (lexicographic order, all
    ascending or all descending).  ``pre_sorted=True`` skips the first local
    sort — the physical planner sets it when the input already provides the
    required ordering.

    Splitters are full key TUPLES sampled from every shard and sorted
    lexicographically; rows route via dense lexicographic ranks over the
    union of local rows and splitters (:func:`lex_ranks` — the same
    machinery merge join uses), with a side="right" comparison so rows tying
    with a splitter tuple co-locate.  Routing therefore balances on the
    WHOLE key, not just the most-significant column: heavy skew on key0 with
    varied minor keys spreads across shards instead of piling ties onto one
    (the pre-composite-splitter failure mode).  Cross-shard order follows
    the splitter tuples and within-shard order comes from the final
    multi-key local sort, so the concatenation of shard prefixes is globally
    lexicographically sorted.
    """
    if isinstance(key_names, str):
        key_names = (key_names,)
    key_names = tuple(key_names)
    P = nshards(axes) if axes else 1
    if pre_sorted:
        scols = cols
    else:
        scols, _ = local_sort(cols, count, key_names)
    cap = scols[key_names[0]].shape[0]
    valid = valid_mask(count, cap)
    if P > 1:
        # sample key tuples evenly from the valid prefix of every shard
        pos = (jnp.arange(n_samples, dtype=jnp.int32) *
               jnp.maximum(count, 1)) // n_samples
        pos = jnp.clip(pos, 0, cap - 1)
        allsamp = []
        for kn in key_names:
            kv = scols[kn]
            samp = jnp.where(count > 0, kv[pos], _sentinel(kv.dtype))
            allsamp.append(lax.all_gather(samp, axes).reshape(-1))   # (P*n,)
        ssamp = lax.sort(tuple(allsamp), num_keys=len(allsamp)) \
            if len(allsamp) > 1 else (jnp.sort(allsamp[0]),)
        # P-1 splitter tuples at even quantiles
        qpos = (jnp.arange(1, P, dtype=jnp.int32) * ssamp[0].shape[0]) // P
        splitters = tuple(s[qpos] for s in ssamp)
        if len(key_names) == 1:
            key_vals = jnp.where(valid, scols[key_names[0]],
                                 _sentinel(scols[key_names[0]].dtype))
            dest = jnp.searchsorted(splitters[0], key_vals,
                                    side="right").astype(jnp.int32)
        else:
            # dense ranks over rows ∪ splitters; splitter ranks ascend (the
            # splitters are sorted), so a searchsorted on ranks IS the
            # lexicographic tuple comparison.
            joint = [jnp.concatenate([jnp.where(valid, scols[kn],
                                                _sentinel(scols[kn].dtype)), sp])
                     for kn, sp in zip(key_names, splitters)]
            jvalid = jnp.concatenate([valid, jnp.full((P - 1,), True)])
            ranks, _, _ = lex_ranks(joint, jvalid)
            dest = jnp.searchsorted(ranks[cap:], ranks[:cap],
                                    side="right").astype(jnp.int32)
        if not ascending:
            dest = (P - 1) - dest
    else:
        dest = jnp.zeros((cap,), jnp.int32)
    out, cnt, ovf = exchange(scols, count, dest, axes=axes,
                             bucket_cap=bucket_cap, cap_out=cap_out,
                             kernels=kernels, packed=packed)
    out, _ = local_sort(out, cnt, key_names)
    if not ascending:
        # reverse valid prefix
        capo = out[key_names[0]].shape[0]
        idx = jnp.where(valid_mask(cnt, capo),
                        jnp.maximum(cnt - 1, 0) - jnp.arange(capo, dtype=jnp.int32),
                        jnp.arange(capo, dtype=jnp.int32))
        idx = jnp.clip(idx, 0, capo - 1)
        out = {k: v[idx] for k, v in out.items()}
    return out, cnt, ovf


# ---------------------------------------------------------------------------
# concat
# ---------------------------------------------------------------------------

def concat(parts: Sequence[tuple[dict[str, jax.Array], jax.Array]], cap_out: int,
           kernels=None):
    """Vertical concat of per-shard tables (counts add; padding squeezed)."""
    names = list(parts[0][0])
    stacked = {n: jnp.concatenate([p[0][n] for p in parts]) for n in names}
    keep = jnp.concatenate([valid_mask(c, p[next(iter(p))].shape[0])
                            for p, c in parts])
    return compact(stacked, keep, cap_out, kernels=kernels)
