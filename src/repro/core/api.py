"""HiFrames user API — fluent, pandas-flavored data frames that compile
with the surrounding array code.

The surface is METHOD-CHAINED (API v2); every relational verb returns a new
lazy DataFrame wrapping a logical plan node:

    import repro.hiframes as hf
    df = hf.table({"id": ids, "x": xs, "y": ys})   # DataSource analogue

    out = (df[df.x > 0.0]                          # filter (df.x == df["x"])
             .merge(dim, on=("id", "cid"))         # equi-join
             .assign(z=df.x * 2.0)                 # derived columns
             .groupby("id")                        # GroupBy proxy
             .agg(total=("z", "sum"),              # pandas named-agg specs
                  n=("z", "count"),
                  ym=hf.mean(df.y))                # ...or AggExpr spellings
             .sort_values("total", ascending=False)
             .head(10)
             .collect())                           # optimize+distribute+jit+run

    df["r"] = df.x / df.y                          # column assignment
    df2 = df.drop(["y"])                           # column removal

Composite (multi-column) keys are supported end-to-end — merge, groupby and
sort accept key tuples, which shuffle on a combined hash, sort
lexicographically and compare position-wise (TPCx-BB-style query shapes):

    l.merge(r, on=[("a", "ca"), ("b", "cb")])      # 2-column equi-join
    df.groupby(("k1", "k2")).agg(s=("x", "sum"))
    df.sort(by=("k1", "k2"))

``on=("id", "cid")`` — a 2-tuple of strings — keeps its historical meaning of
a SINGLE key pair with different names; use a list for composite keys.

**Materialization with a layout contract** — the repeated-query hook:

    hot = df.groupby(("k1", "k2")).agg(s=("x", "sum")).persist()

``persist()`` (alias ``cache()``) executes the plan ONCE and returns a new
DataFrame backed by a Scan that carries the materialized layout — hash/range
partitioning keys, per-shard sort order, global sortedness, per-shard valid
counts.  The device shards re-enter later executions without a host
round-trip, and downstream ``groupby``/``merge``/``over``/``sort`` on the
persisted keys plan ZERO exchanges and ZERO sorts (docs/api.md).  A persisted
dimension table turns every query against it into the elided plan.

Window functions may be PARTITIONED (SQL ``OVER (PARTITION BY ... ORDER BY
...)``) — per-group cumsum/SMA/WMA/lag/lead plus rank/row_number and rolling
sums/means, planned as hash co-location + grouped local sort (both elided
when the input already provides them):

    w = df.over("g", order_by="t")                 # the OVER clause
    d1 = w.cumsum(df.x)                            # per-group running total
    d2 = w.rolling_mean(df.x, 5, exact=True)       # pandas min_periods=1 mode
    d3 = w.rank()                                  # SQL RANK()

Every collected column is a plain jax.Array; any jax array can be attached
with ``with_column``/``assign`` or referenced directly inside expressions
(the paper's "any array in the program" rule).

The pre-v2 free functions (``hf.join(df, ...)``, ``hf.aggregate(df, by,
...)``, ``hf.cumsum(df, ...)``) remain as thin shims delegating to the
fluent surface — existing code keeps working unchanged (migration table in
docs/api.md).
"""
from __future__ import annotations

import dataclasses as _dc
import functools as _ft
from typing import Any, Sequence

import numpy as np

from . import distribution as D
from . import ir
from .dtypes import (CODE_DTYPE, DType, NULL_CODE, as_nullable, categories_of,
                     coerce_column, dict_decode, is_category, is_nullable,
                     physical_dtype, recode_map, union_categories)
from .expr import (AGG_FNS, AggExpr, BinOp, Cast, ColRef, Const, Expr, IsIn,
                   UDF, UnOp, all_, any_, as_expr, count, first, fn_expr,
                   max_, mean, min_, nunique, prod, std, sum_, var)
from .lower import ExecConfig, Lowered, lower
from .table import DTable

__all__ = [
    "DataFrame", "GroupBy", "Over", "table", "from_pandas", "join",
    "aggregate", "concat",
    "cumsum", "stencil", "sma", "wma", "lag", "lead", "rank", "dense_rank",
    "row_number", "rolling_sum", "rolling_mean", "sum_", "mean", "count",
    "min_", "max_", "prod", "any_", "all_", "var", "std", "first", "nunique",
    "udf", "ExecConfig", "explain", "DType",
]


# ---------------------------------------------------------------------------
# string/null expression rewriting (docs/dtypes.md)
#
# Strings never reach the device: comparisons and membership tests against a
# category column are rewritten INTO CODE SPACE when the expression is
# attached to a plan (filter/assign/agg construction).  Dictionaries are
# sorted, so code order IS lexicographic order — equality maps to a code
# constant, ranges map to searchsorted thresholds — and isna() resolves to
# the dtype's in-band null test (code < 0, isnan) or a constant False.
# ---------------------------------------------------------------------------

_CMP_SWAP = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le",
             "eq": "eq", "ne": "ne"}


def _cat_dtype_of(e: Expr, schemas: dict[int, dict]):
    if isinstance(e, ColRef):
        dt = schemas.get(e.table_id, {}).get(e.name)
        if is_category(dt):
            return dt
    return None


def _code_const(code: int) -> Const:
    return Const(np.int32(code))


def _rewrite_cat_cmp(col: ColRef, dt, op: str, v: str) -> Expr:
    """One string comparison against a sorted dictionary, in code space.
    Nulls (code -1) compare False except under ``ne`` (pandas semantics)."""
    cats = categories_of(dt)
    if op in ("eq", "ne"):
        if v in cats:
            return BinOp(op, col, _code_const(cats.index(v)))
        return Const(op == "ne")            # absent value: eq False, ne True
    arr = np.asarray(cats)
    if op in ("lt", "le"):
        t = int(np.searchsorted(arr, v, side="left" if op == "lt" else "right"))
        if t == 0:
            return Const(False)
        return BinOp("and", BinOp("ge", col, _code_const(0)),
                     BinOp("lt", col, _code_const(t)))
    # gt / ge: codes >= threshold — null (-1) can never satisfy it
    t = int(np.searchsorted(arr, v, side="right" if op == "gt" else "left"))
    return BinOp("ge", col, _code_const(max(t, 0)))


def _rewrite_strings(e: Expr, schemas: dict[int, dict]) -> Expr:
    if e.children:
        kids = tuple(_rewrite_strings(c, schemas) for c in e.children)
        if any(k is not o for k, o in zip(kids, e.children)):
            e = e.with_children(kids)
    if isinstance(e, UnOp) and e.op == "isna":
        c = e.children[0]
        if _cat_dtype_of(c, schemas) is not None:
            return BinOp("lt", c, _code_const(0))
        if isinstance(c, ColRef):
            dt = schemas.get(c.table_id, {}).get(c.name)
            if dt is not None and not is_nullable(dt) and \
                    not np.issubdtype(physical_dtype(dt), np.floating):
                return Const(False)         # int/bool columns hold no nulls
        return e
    if isinstance(e, IsIn):
        dt = _cat_dtype_of(e.children[0], schemas)
        if dt is None or not any(isinstance(v, str) for v in e.values):
            return e
        cats = categories_of(dt)
        lut = {v: i for i, v in enumerate(cats)}
        bad = [v for v in e.values if not isinstance(v, str)]
        if bad:
            raise TypeError(
                f"isin on a category column mixes strings and {bad!r}; "
                "pass homogeneous string values")
        codes = tuple(np.int32(lut[v]) for v in e.values if v in lut)
        return IsIn(e.children[0], codes) if codes else Const(False)
    if isinstance(e, BinOp) and e.op in _CMP_SWAP:
        a, b = e.children
        da, db = _cat_dtype_of(a, schemas), _cat_dtype_of(b, schemas)
        if da is not None and db is not None:
            if categories_of(da) != categories_of(db):
                raise TypeError(
                    "cannot compare category columns with different "
                    "dictionaries; merge/concat unify them, or ingest the "
                    "columns together")
            return e
        if da is None and db is None:
            for x in (a, b):
                if isinstance(x, Const) and isinstance(x.value, str):
                    raise TypeError(
                        f"string constant {x.value!r} compared against a "
                        "non-category column — strings only compare against "
                        "dictionary-encoded (category) columns")
            return e
        col, const, op = (a, b, e.op) if da is not None \
            else (b, a, _CMP_SWAP[e.op])
        dt = da if da is not None else db
        if isinstance(const, Const) and isinstance(
                const.value, (int, np.integer)):
            return e                        # already in code space
        if not isinstance(const, Const) or not isinstance(const.value, str):
            raise TypeError(
                f"category column {col.name!r} compares against string "
                f"constants, got {const!r}")
        return _rewrite_cat_cmp(col, dt, op, const.value)
    return e


# Device-side null/dictionary helpers, lifted into expressions via fn_expr.
# Each is a closure factory so the host constants (LUT, fill code/value) bake
# into the trace as literals.


def _recode_fn(lut: np.ndarray, fill: int | None = None):
    """codes -> codes through a host LUT (dictionary unification); null
    codes stay null unless ``fill`` maps them to a new code (fillna)."""
    fillc = np.int32(NULL_CODE if fill is None else fill)

    def f(c):
        import jax.numpy as jnp
        return jnp.where(c >= 0, jnp.asarray(lut)[jnp.clip(c, 0)], fillc)
    return f


def _fill_code_fn(code: int):
    fillc = np.int32(code)

    def f(c):
        import jax.numpy as jnp
        return jnp.where(c < 0, fillc, c)
    return f


def _fill_nan_fn(v: float):
    def f(c):
        import jax.numpy as jnp
        return jnp.where(jnp.isnan(c), jnp.asarray(v, c.dtype), c)
    return f


def _over_keys(x) -> tuple[str, ...]:
    """Normalize an optional partition/order key spec to a tuple (an absent
    spec — None or an empty sequence — becomes ())."""
    return () if not x else ir.as_keys(x)


class DataFrame:
    """Lazy distributed data frame (wraps a logical plan node).

    ``rep_nodes`` tracks which plan nodes the user pinned to REP via
    :meth:`replicate` — the set survives joins/aggregates so a broadcast
    dimension table stays broadcast inside a larger plan."""

    def __init__(self, node: ir.Node, rep_nodes: frozenset = frozenset()):
        self.node = node
        self._rep_nodes = frozenset(rep_nodes)

    @property
    def _replicated(self) -> bool:
        return self.node.id in self._rep_nodes

    def _wrap(self, node: ir.Node) -> "DataFrame":
        return DataFrame(node, self._rep_nodes)

    def _rw(self, e) -> Expr:
        """Resolve string comparisons / isna against this frame's logical
        schema (applied wherever an expression attaches to the plan)."""
        e = as_expr(e)
        return _rewrite_strings(
            e, {n.id: n.schema for n in ir.topo_order(self.node)})

    # -- schema ---------------------------------------------------------------
    @property
    def schema(self) -> dict[str, np.dtype]:
        return self.node.schema

    @property
    def columns(self) -> list[str]:
        return list(self.node.schema)

    @property
    def dtypes(self) -> dict[str, Any]:
        """Logical dtypes by column (pandas ``df.dtypes`` analogue): plain
        ``np.dtype`` for numeric columns, :class:`DType` for category and
        nullable columns (repr'd ``category[str]``, ``float32?``, ...)."""
        return dict(self.node.schema)

    # -- expression building ---------------------------------------------------
    def __getitem__(self, key):
        if isinstance(key, str):
            return ColRef(self.node.id, key)
        if isinstance(key, Expr):                       # df[pred] -> filter
            return self._wrap(ir.Filter(self.node, self._rw(key)))
        if isinstance(key, (list, tuple)):              # df[["a","b"]] -> project
            cols = {k: ColRef(self.node.id, k) for k in key}
            return self._wrap(ir.Project(self.node, cols))
        raise TypeError(key)

    def __getattr__(self, name: str):
        """Column access as attributes: ``df.x`` is ``df["x"]``.  Methods and
        real attributes win (this hook only fires when normal lookup fails);
        columns shadowed by a method name need the subscript form."""
        try:
            node = object.__getattribute__(self, "node")
        except AttributeError:
            raise AttributeError(name) from None
        if not name.startswith("_") and name in node.schema:
            return ColRef(node.id, name)
        raise AttributeError(
            f"DataFrame has no attribute or column {name!r} "
            f"(columns: {list(node.schema)})")

    def __setitem__(self, name: str, value):
        """In-place column assignment, ``df["c"] = expr`` — the paper's
        ``df[:c] = ...``.  Rebinds this wrapper to a Project over the old
        node; previously built expressions stay valid (columns are resolved
        by name at evaluation)."""
        if not isinstance(name, str):
            raise TypeError(f"column name must be a str, got {name!r}")
        cols = {k: ColRef(self.node.id, k) for k in self.node.schema}
        cols[name] = self._rw(value)
        new = ir.Project(self.node, cols)
        if self.node.id in self._rep_nodes:
            self._rep_nodes = self._rep_nodes | {new.id}
        self.node = new

    def with_column(self, name: str, e) -> "DataFrame":
        """Attach a derived column (non-mutating form of ``df[name] = e``)."""
        return self.assign(**{name: e})

    def assign(self, **exprs) -> "DataFrame":
        """pandas-style ``df.assign(z=df.x * 2, w=lambda d: d.x + d.y)``:
        returns a new frame with the given columns added (or replaced).
        Values may be expressions, scalars, arrays, or callables taking the
        frame."""
        cols = {k: ColRef(self.node.id, k) for k in self.node.schema}
        for name, e in exprs.items():
            if callable(e) and not isinstance(e, Expr):
                e = e(self)
            cols[name] = self._rw(e)
        return self._wrap(ir.Project(self.node, cols))

    def rename(self, mapping: dict[str, str] | None = None, *,
               columns: dict[str, str] | None = None) -> "DataFrame":
        """Rename columns; accepts the mapping positionally or as the
        pandas-style ``columns=`` keyword."""
        mapping = mapping if mapping is not None else (columns or {})
        cols = {mapping.get(k, k): ColRef(self.node.id, k) for k in self.node.schema}
        return self._wrap(ir.Project(self.node, cols))

    def select(self, *names: str) -> "DataFrame":
        return self[list(names)]

    def drop(self, columns, *more: str) -> "DataFrame":
        """Drop columns: ``df.drop("a")``, ``df.drop(["a", "b"])`` or
        ``df.drop(columns=[...])``."""
        dropped = set(ir.as_keys(columns)) | set(more)
        missing = dropped - set(self.node.schema)
        if missing:
            raise KeyError(f"drop: {sorted(missing)} not in columns "
                           f"{list(self.node.schema)}")
        return self[[c for c in self.node.schema if c not in dropped]]

    # -- null / dtype surface (docs/dtypes.md) ---------------------------------
    def astype(self, dtype) -> "DataFrame":
        """Cast columns, pandas-style: ``df.astype(np.float64)`` (all
        columns) or ``df.astype({"x": np.int32})``.  Category columns can't
        be cast on device (decode with ``to_numpy()``), casting TO category
        happens at ingest, and nullable columns must be ``fillna``'d before
        a cast to a dtype with no null representation."""
        sch = self.node.schema
        mapping = dict(dtype) if isinstance(dtype, dict) \
            else {c: dtype for c in sch}
        exprs: dict[str, Expr] = {c: ColRef(self.node.id, c) for c in sch}
        dts = dict(sch)
        for c, t in mapping.items():
            if c not in sch:
                raise KeyError(f"astype: no column {c!r}")
            dt = sch[c]
            wants_cat = (isinstance(t, str) and t == "category") \
                or is_category(t)
            if wants_cat:
                if is_category(dt):
                    continue
                raise TypeError(
                    f"astype: column {c!r} -> category needs host-side "
                    "dictionary encoding; rebuild the input with hf.table() "
                    "or hf.from_pandas()")
            if is_category(dt):
                raise TypeError(
                    f"astype: column {c!r} is category[str]; decode with "
                    "to_numpy() instead of casting on device")
            target = np.dtype(t)
            if dt == target and not is_nullable(dt):
                continue
            if is_nullable(dt) and not np.issubdtype(target, np.floating):
                raise TypeError(
                    f"astype: column {c!r} is nullable ({dt!r}) and "
                    f"{target} has no null representation — fillna() first")
            exprs[c] = Cast(ColRef(self.node.id, c), target)
            dts[c] = (DType(target, nullable=True)
                      if is_nullable(dt) else target)
        return self._wrap(ir.Project(self.node, exprs, dts))

    def fillna(self, value, subset=None) -> "DataFrame":
        """Replace nulls: a scalar (applied to every nullable column, or to
        ``subset``), or a dict column -> fill value.  Filling a category
        column with a string outside its dictionary extends the dictionary.
        The filled columns come back non-nullable."""
        sch = self.node.schema
        if isinstance(value, dict):
            targets = dict(value)
        else:
            cols = ir.as_keys(subset) if subset is not None else tuple(sch)
            targets = {c: value for c in cols}
        exprs: dict[str, Expr] = {c: ColRef(self.node.id, c) for c in sch}
        dts = dict(sch)
        changed = False
        for c, v in targets.items():
            if c not in sch:
                raise KeyError(f"fillna: no column {c!r}")
            dt = sch[c]
            if not is_nullable(dt):
                continue
            col = ColRef(self.node.id, c)
            if is_category(dt):
                if not isinstance(v, str):
                    raise TypeError(
                        f"fillna: column {c!r} is category[str]; the fill "
                        f"value must be a string, got {v!r}")
                cats = categories_of(dt)
                if v in cats:
                    exprs[c] = fn_expr(_fill_code_fn(cats.index(v)), col)
                    dts[c] = DType(CODE_DTYPE, cats)
                else:
                    newcats = union_categories(cats, (v,))
                    lut = recode_map(cats, newcats)
                    exprs[c] = fn_expr(
                        _recode_fn(lut, fill=newcats.index(v)), col)
                    dts[c] = DType(CODE_DTYPE, newcats)
            else:
                exprs[c] = fn_expr(
                    _fill_nan_fn(float(v)), col)
                dts[c] = physical_dtype(dt)
            changed = True
        if not changed:
            return self
        return self._wrap(ir.Project(self.node, exprs, dts))

    def dropna(self, subset=None) -> "DataFrame":
        """Drop rows holding a null in any (or any ``subset``) column —
        a Filter on the in-band null tests, collective-free."""
        cols = ir.as_keys(subset) if subset is not None \
            else tuple(self.node.schema)
        sch = self.node.schema
        missing = set(cols) - set(sch)
        if missing:
            raise KeyError(f"dropna: {sorted(missing)} not in columns "
                           f"{list(sch)}")
        preds = []
        for c in cols:
            dt = sch[c]
            if not is_nullable(dt):
                continue
            col = ColRef(self.node.id, c)
            if is_category(dt):
                preds.append(BinOp("ge", col, _code_const(0)))
            elif np.issubdtype(physical_dtype(dt), np.floating):
                preds.append(UnOp("not", UnOp("isna", col)))
        if not preds:
            return self
        return self._wrap(ir.Filter(
            self.node, _ft.reduce(lambda a, b: BinOp("and", a, b), preds)))

    def isna(self) -> "DataFrame":
        """Per-cell null mask, one bool column per input column."""
        cols = {c: self._rw(UnOp("isna", ColRef(self.node.id, c)))
                for c in self.node.schema}
        return self._wrap(ir.Project(self.node, cols))

    def notna(self) -> "DataFrame":
        cols = {c: UnOp("not", self._rw(UnOp("isna", ColRef(self.node.id, c))))
                for c in self.node.schema}
        return self._wrap(ir.Project(self.node, cols))

    def _recode(self, targets: dict[str, tuple], nullable: dict[str, bool]
                ) -> "DataFrame":
        """Re-encode category columns against new (superset) dictionaries —
        the merge/concat unification step.  Identity for empty targets."""
        if not targets:
            return self
        sch = self.node.schema
        exprs: dict[str, Expr] = {c: ColRef(self.node.id, c) for c in sch}
        dts = dict(sch)
        for c, newcats in targets.items():
            dt = sch[c]
            cats = categories_of(dt)
            nb = nullable.get(c, is_nullable(dt))
            if cats != newcats:
                exprs[c] = fn_expr(_recode_fn(recode_map(cats, newcats)),
                                   ColRef(self.node.id, c))
            dts[c] = DType(CODE_DTYPE, newcats, nullable=nb)
        new = ir.Project(self.node, exprs, dts)
        rep = self._rep_nodes | ({new.id} if self._replicated else set())
        return DataFrame(new, frozenset(rep))

    # -- relational verbs -------------------------------------------------------
    def merge(self, right: "DataFrame", on, how: str = "inner",
              suffix: str = "_r") -> "DataFrame":
        """Equi-join; ``on`` is a name, a (left_name, right_name) pair, or a
        list of names / pairs for composite (multi-column) keys.

        how="left" keeps unmatched left rows; right float columns NaN-fill,
        right category columns null-code-fill, and right int columns
        zero-fill with a ``_matched`` int column distinguishing real zeros
        (docs/dtypes.md).

        String (category) keys join by dictionary code: both sides recode
        onto the union dictionary first, then the join plans exactly like an
        int-key join — same exchanges, same sorts, same packed bytes."""
        lo, ro = _parse_on(on)
        if how not in ("inner", "left"):
            raise ValueError(how)
        left, rgt = self, right
        lsch, rsch = left.node.schema, rgt.node.schema
        ltgt: dict[str, tuple] = {}
        rtgt: dict[str, tuple] = {}
        for lk, rk in zip(lo, ro):
            ldt, rdt = lsch.get(lk), rsch.get(rk)
            if ldt is None or rdt is None:
                continue                    # ir.Join reports the missing key
            if is_category(ldt) != is_category(rdt):
                raise TypeError(
                    f"merge: key {lk!r}/{rk!r} is category[str] on one side "
                    "and numeric on the other — encode both sides the same "
                    "way at ingest")
            if is_category(ldt) and \
                    categories_of(ldt) != categories_of(rdt):
                u = union_categories(categories_of(ldt), categories_of(rdt))
                ltgt[lk] = u
                rtgt[rk] = u
        left = left._recode(ltgt, {})
        rgt = rgt._recode(rtgt, {})
        rep = left._rep_nodes | rgt._rep_nodes
        node = ir.Join(left.node, rgt.node, lo, ro, suffix, how)
        if left._replicated and rgt._replicated:
            rep = rep | {node.id}
        return DataFrame(node, rep)

    def groupby(self, by) -> "GroupBy":
        """Group-by proxy: ``df.groupby("k").agg(total=("x", "sum"))``.
        ``by`` is a column name or a tuple/list of names (composite key)."""
        return GroupBy(self, by)

    def head(self, n: int = 5) -> "DataFrame":
        """First ``n`` rows in global (shard-concatenation) order — no data
        movement, just per-shard count clamps; partitioning and ordering
        survive, so a downstream verb on the same keys stays elided."""
        return self._wrap(ir.Limit(self.node, n))

    def limit(self, n: int) -> "DataFrame":
        """SQL-style alias of :meth:`head`."""
        return self.head(n)

    def sort(self, by, ascending: bool = True) -> "DataFrame":
        """Global sort; ``by`` is a column name or a tuple/list of names
        (lexicographic, most-significant first)."""
        return self._wrap(ir.Sort(self.node, ir.as_keys(by), ascending))

    def sort_values(self, by, ascending: bool = True) -> "DataFrame":
        """pandas-style alias of :meth:`sort`."""
        return self.sort(by, ascending)

    def repartition(self, by) -> "DataFrame":
        """Hash-partition rows across shards by key columns (Spark/Dask
        ``repartition``) — a pure layout verb: same rows, new placement.

        The planner inserts one hash exchange on ``by`` — elided entirely
        when the input is already hash-partitioned on (a superset-compatible
        form of) those keys.  Chained with :meth:`persist`, the materialized
        Scan carries the hash layout, so later ``groupby``/``merge``/``over``
        on the same keys plan zero exchanges."""
        keys = ir.as_keys(by)
        missing = set(keys) - set(self.node.schema)
        if missing:
            raise KeyError(f"repartition: {sorted(missing)} not in columns "
                           f"{list(self.node.schema)}")
        return self._wrap(ir.Repartition(self.node, by=keys))

    def sort_within_partitions(self, by, ascending: bool = True) -> "DataFrame":
        """Sort rows by ``by`` within each shard — no data movement (Spark's
        ``sortWithinPartitions``).  Partitioning is untouched; the per-shard
        order becomes part of the layout :meth:`persist` captures, so a
        persisted frame feeds segment kernels with zero local sorts.

        Only ascending order is supported (the shard-local sort primitive is
        ascending-only, matching ``sort``'s local path)."""
        if not ascending:
            raise ValueError(
                "sort_within_partitions: only ascending=True is supported")
        keys = ir.as_keys(by)
        missing = set(keys) - set(self.node.schema)
        if missing:
            raise KeyError(
                f"sort_within_partitions: {sorted(missing)} not in columns "
                f"{list(self.node.schema)}")
        return self._wrap(ir.Repartition(self.node, sort_by=keys))

    def over(self, partition_by, order_by=None) -> "Over":
        """Partitioned window context (SQL ``OVER (PARTITION BY ... ORDER BY
        ...)``): ``df.over("g", order_by="t").cumsum(df.x)``.  See
        docs/window_functions.md for the plan shapes."""
        return Over(self, partition_by, order_by)

    def replicate(self) -> "DataFrame":
        """Pin this frame to REP (broadcast) — small dimension tables."""
        return DataFrame(self.node,
                         frozenset(n.id for n in ir.topo_order(self.node)))

    # -- execution ---------------------------------------------------------------
    def _force_rep(self) -> set[int]:
        return set(self._rep_nodes)

    def _execute(self, cfg: ExecConfig, keep: Sequence[str] | None = None,
                 ) -> tuple[Lowered, DTable]:
        """Lower + run under the unified retry policy (runtime/retry.py):
        per-op capacity escalation from the overflow attribution vector
        (``cfg.retry_scope="global"`` restores legacy slack-doubling), the
        kernel / packed-exchange / stats degradation ladders, and a
        structured event log carried on the returned DTable (``.events``)
        and in the per-fingerprint store :meth:`explain` renders.
        Shared by :meth:`collect` and :meth:`persist`."""
        from ..runtime import retry as _rt
        policy = _rt.RetryPolicy(max_retries=max(cfg.auto_retry, 0),
                                 scope=getattr(cfg, "retry_scope", "op"))

        def run_once(c):
            lowered, _ = lower(self.node, c, set(keep) if keep else None,
                               force_rep=self._force_rep())
            return lowered, lowered()

        lowered, t, events, cfg = policy.execute(run_once, cfg)
        if events:
            _rt.record_events(lowered.root, events)
        if cfg.adaptive_stats:
            from . import stats as _st
            if not t.overflow:
                # feed realized per-shard counts back into the
                # per-fingerprint stats store: a repeated run of this exact
                # plan sizes PartialAgg from the true group count and lowers
                # the salting threshold if skew materialized.
                _st.record_realized(lowered.root, np.asarray(t.counts))
            else:
                # record the FAILURE's observed requirement so the next
                # adaptive run sizes the site correctly up front.
                for op_id, rec in (t.overflow_ops or {}).items():
                    if rec["kind"] in ("partial_agg", "segment_agg"):
                        _st.record_failure(lowered.pplan.ops[op_id].node,
                                           rec["req_shards"])
        return lowered, t

    def collect(self, cfg: ExecConfig | None = None,
                keep: Sequence[str] | None = None) -> DTable:
        """Execute the plan and return the materialized DTable."""
        return self._execute(cfg or ExecConfig(), keep)[1]

    def persist(self, cfg: ExecConfig | None = None, *,
                name: str = "persist") -> "DataFrame":
        """Execute ONCE and return a new DataFrame over the materialized
        result, carrying the layout the plan produced.

        The returned frame's Scan records the root op's partitioning
        (hash/range keys, direction, global sortedness) and per-shard
        ordering plus the 1D_VAR carrier (per-shard counts + capacity), so:

          * its device shards re-enter later executions directly — no host
            gather, no re-pad;
          * downstream ``groupby``/``merge``/``over``/``sort`` on the
            persisted keys plan zero exchanges and zero sorts (the plan
            census pins this, tests/test_api_v2.py).

        Hash/range claims are shard-count-bound: re-executing under a
        different device count falls back to a host gather and a plain
        block scan (correct, just not elided).  Replicated results re-enter
        as host tables pinned REP — a persisted dimension table keeps
        broadcasting.
        """
        cfg = cfg or ExecConfig()
        lowered, t = self._execute(cfg)
        if t.overflow:
            # collect() returns the flagged table for the caller to inspect;
            # baking truncated shards into a reusable frame would silently
            # drop rows from every later query.  The typed error names the
            # offending plan op and the cap that would have sufficed.
            from .errors import CapacityOverflow
            attempts = max(cfg.auto_retry, 0) + 1
            ops = t.overflow_ops or {}
            if ops:
                op_id, rec = max(ops.items(),
                                 key=lambda kv: kv[1]["cap_req"])
                raise CapacityOverflow(
                    op_id=op_id, op=rec["op"],
                    observed_est=rec["cap_req"], cap=rec["cap"],
                    attempts=attempts,
                    message=(
                        "persist(): capacity overflow survived the "
                        f"auto-retries at op #{op_id} ({rec['op']}): observed "
                        f"requirement ~{rec['cap_req']} rows > planned cap "
                        f"{rec['cap']} — raise ExecConfig.auto_retry or "
                        "pre-size via ExecConfig.cap_overrides"
                        f"[{op_id}] = ({rec['cap_req']}, "
                        f"{rec['bucket_req']})"))
            raise CapacityOverflow(
                attempts=attempts,
                message=(
                    "persist(): capacity overflow survived the auto-retries "
                    "— raise ExecConfig.shuffle_slack/join_expansion/"
                    "auto_retry"))
        root_op = lowered.pplan.root_op
        layout = ir.ScanLayout(
            kind=root_op.part.kind, partitioned_by=root_op.part.keys,
            ascending=root_op.part.ascending,
            globally_sorted=root_op.part.globally_sorted,
            sorted_by=root_op.order.keys,
            order_ascending=root_op.order.ascending,
            counts=np.asarray(t.counts, dtype=np.int32),
            capacity=int(t.capacity), nshards=int(t.nshards), dist=t.dist)
        if t.dist == D.REP:
            # replicated results are tiny by construction: re-enter as a
            # plain host table pinned REP, keeping the ordering contract.
            scan = ir.Scan(name, t.to_numpy(),
                           layout=_dc.replace(layout, kind="rep",
                                              counts=None))
            return DataFrame(scan, frozenset({scan.id}))
        scan = ir.Scan(name, dict(t.columns), layout=layout)
        return DataFrame(scan)

    def cache(self, cfg: ExecConfig | None = None, *,
              name: str = "cache") -> "DataFrame":
        """Alias of :meth:`persist` (Spark spelling)."""
        return self.persist(cfg, name=name)

    def lower(self, cfg: ExecConfig | None = None, keep: Sequence[str] | None = None,
              collect_block: bool = False) -> Lowered:
        lowered, _ = lower(self.node, cfg, set(keep) if keep else None,
                           collect_block=collect_block,
                           force_rep=self._force_rep())
        return lowered

    def to_numpy(self, cfg: ExecConfig | None = None, *,
                 decode: bool = True) -> dict[str, np.ndarray]:
        """Collect to host numpy.  Category columns decode back to string
        object arrays (``None`` for nulls); ``decode=False`` keeps the raw
        int32 dictionary codes."""
        out = self.collect(cfg).to_numpy()
        if decode:
            for c, dt in self.node.schema.items():
                if is_category(dt) and c in out:
                    out[c] = dict_decode(out[c], categories_of(dt))
        return out

    def collect_matrix(self, cols: Sequence[str], cfg: ExecConfig | None = None):
        """Matrix assembly (the paper's transpose(typed_hcat) pattern): returns
        a row-sharded (rows, k) float32 matrix + row count, rebalanced to
        1D_BLOCK as ML algorithms require."""
        import jax.numpy as jnp
        lowered, _ = lower(self.node, cfg, set(cols), collect_block=True,
                           force_rep=self._force_rep())
        t = lowered()
        mat = jnp.stack([t.columns[c].astype(jnp.float32) for c in cols], axis=1)
        return mat, t.counts, t.capacity

    def _plan(self, cfg: ExecConfig):
        """Shared planning prologue (optimize -> infer -> rebalance ->
        physical plan) for explain()/physical_plan().  Mirrors lower()'s
        sequence under the same config; a plain collect() executes this
        plan (collect(keep=...) / collect_matrix() additionally prune
        columns or append a root rebalance, which introspection omits)."""
        from . import optimizer as opt
        from . import physical_plan as pp
        from . import stats as st
        root = self.node
        if cfg.optimize_plan:
            root, _ = opt.optimize(root)
        info = D.infer(root, force_rep=self._force_rep(),
                       broadcast_join=cfg.broadcast_join)
        root = D.insert_rebalance(root, info)
        # Introspection always carries a stats context so explain() can
        # annotate estimated rows/bytes per exchange; it only changes
        # DECISIONS (salting, cheap side, auto caps) under
        # cfg.adaptive_stats — plans stay byte-identical with adaptive off.
        sctx = st.analyze(root, cfg)
        return root, info, pp.plan_physical(root, info.dists, cfg, stats=sctx)

    def physical_plan(self, cfg: ExecConfig | None = None):
        """The property-driven physical plan (core/physical_plan.py) this
        frame would execute: op list with partitioning/ordering annotations,
        plus ``counts()`` / ``shuffle_count()`` for introspection — the hook
        the exchange-elision tests and benchmarks use."""
        _root, _info, pplan = self._plan(cfg or ExecConfig())
        return pplan

    def explain(self, cfg: ExecConfig | None = None) -> str:
        """Logical plan with distribution annotations, followed by the
        physical plan: one line per operator with its provided partitioning
        and ordering, exchange/sort insertions made explicit, and a leading
        shuffle/sort census.  Exchanges carry estimated rows/bytes from the
        sampled statistics pass, and a trailing line compares the root's
        estimate against REALIZED counts when a previous adaptive run of
        this exact plan fingerprint recorded them."""
        from . import stats as st
        root, info, pplan = self._plan(cfg or ExecConfig())
        sch = ", ".join(f"{k}:{dt}" for k, dt in self.node.schema.items())
        txt = (ir.plan_str(root, info.dists) + "\nschema: " + sch
               + "\n\n" + pplan.render())
        est = pplan.root_op.rows_est
        tail = []
        if est is not None:
            tail.append(f"estimated output rows ~{int(est)}")
        rl = st.realized_for(root)
        if rl is not None:
            tail.append(
                f"realized (previous run): {rl['rows']} rows over "
                f"{rl['nshards']} shards, per-shard max/mean "
                f"{rl['max']}/{rl['mean']:.1f}")
        if tail:
            txt += "\nstats: " + "; ".join(tail)
        from ..runtime import retry as _rt
        evs = _rt.events_for(root)
        if evs:
            txt += "\nevents (previous run):\n" + "\n".join(
                "  " + e.render() for e in evs)
        return txt

    def __repr__(self):
        return f"DataFrame({list(self.node.schema)})\n{ir.plan_str(self.node)}"


# pandas-spelled aliases for the named-agg table (everything else matches).
_AGG_ALIASES = {"product": "prod", "size": "count", "average": "mean"}


class GroupBy:
    """Deferred group-by: ``df.groupby(keys)`` then :meth:`agg` (or a
    whole-frame sugar method).  Aggregation specs accept three spellings:

      * pandas named-agg tuples: ``agg(total=("x", "sum"))`` — the column
        may also be an expression: ``agg(hits=(df.x > 0, "sum"))``;
      * AggExpr objects: ``agg(total=hf.sum_(df.x))``;
      * row count: ``agg(n="count")`` (or the :meth:`size` sugar).

    Available fns: sum, mean, count, min, max, prod, any, all, var, std,
    first, nunique (``product``/``size``/``average`` alias the obvious
    ones).  Output rows come back hash-partitioned on the keys and sorted by
    them within each shard — the layout a following :meth:`DataFrame.persist`
    captures."""

    def __init__(self, df: DataFrame, by, select: tuple[str, ...] | None = None):
        self.df = df
        self.keys = ir.as_keys(by)
        self._select = select
        missing = set(self.keys) - set(df.node.schema)
        if missing:
            raise KeyError(f"groupby: {sorted(missing)} not in columns "
                           f"{list(df.node.schema)}")

    def __getitem__(self, cols) -> "GroupBy":
        """Column selection on the proxy — ``df.groupby("k")["x"].sum()``
        (pandas SeriesGroupBy/DataFrameGroupBy spelling).  Accepts a name or
        a list/tuple of names; the whole-frame sugar methods (:meth:`sum`,
        :meth:`mean`, ...) then aggregate only the selected columns."""
        sel = (cols,) if isinstance(cols, str) else tuple(cols)
        if not sel:
            raise ValueError("groupby[...]: empty column selection")
        bad = [c for c in sel if not isinstance(c, str)]
        if bad:
            raise TypeError(f"groupby[...]: column names must be str, "
                            f"got {bad!r}")
        missing = set(sel) - set(self.df.node.schema)
        if missing:
            raise KeyError(f"groupby[...]: {sorted(missing)} not in columns "
                           f"{list(self.df.node.schema)}")
        return GroupBy(self.df, self.keys, select=sel)

    # fns with no meaning on dictionary codes (a code sum is garbage);
    # min/max/first/count/nunique stay valid — code order is lexicographic.
    _NUMERIC_ONLY = ("sum", "mean", "var", "std", "prod", "any", "all")

    def _check_cat(self, name: str, fn: str, e) -> None:
        if fn not in self._NUMERIC_ONLY or not isinstance(e, ColRef):
            return
        dt = self.df.node.schema.get(e.name)
        if is_category(dt):
            raise TypeError(
                f"agg {name}: {fn!r} over category[str] column {e.name!r} "
                "has no meaning (dictionary codes aren't numbers); use "
                "min/max/first/count/nunique, or fillna+astype first")

    def _spec(self, name: str, a) -> AggExpr:
        if isinstance(a, AggExpr):
            self._check_cat(name, a.fn, a.expr)
            e = self.df._rw(a.expr) if a.expr is not None else None
            return AggExpr(a.fn, e, a.skipna)
        if isinstance(a, str):
            fn = _AGG_ALIASES.get(a, a)
            if fn == "count":
                return AggExpr("count", None)
            raise TypeError(
                f"agg {name}={a!r}: bare strings only spell 'count'/'size'; "
                f"use a (column, fn) tuple")
        if isinstance(a, tuple) and len(a) == 2:
            col, fn = a
            fn = _AGG_ALIASES.get(fn, fn)
            if not isinstance(fn, str) or fn not in AGG_FNS:
                raise TypeError(f"agg {name}: unknown fn {fn!r}; "
                                f"valid: {AGG_FNS} (+ aliases "
                                f"{tuple(_AGG_ALIASES)})")
            if isinstance(col, str) and col not in self.df.node.schema:
                raise KeyError(f"agg {name}: no column {col!r}")
            if fn == "count":
                # ("x", "count") counts non-null x when x is nullable
                # (pandas count); otherwise it degenerates to the row count
                # and keeps the expr-free form (no prep column on the wire).
                if isinstance(col, str) and \
                        is_nullable(self.df.node.schema.get(col)):
                    return AggExpr("count", ColRef(self.df.node.id, col))
                return AggExpr("count", None)
            e = col if isinstance(col, Expr) else ColRef(self.df.node.id, col)
            self._check_cat(name, fn, e)
            return AggExpr(fn, self.df._rw(e))
        raise TypeError(f"agg {name}: expected (column, fn), an AggExpr or "
                        f"'count', got {a!r}")

    def agg(self, **aggs) -> DataFrame:
        if not aggs:
            raise ValueError("agg() needs at least one name=(column, fn) spec")
        specs = {name: self._spec(name, a) for name, a in aggs.items()}
        # pandas groupby(dropna=True) default: null keys form no group.
        # Columns resolve by name at evaluation, so specs built against the
        # pre-drop node stay valid over the filtered child.
        sch = self.df.node.schema
        base = self.df
        if any(is_nullable(sch[k]) for k in self.keys):
            base = self.df.dropna(subset=self.keys)
        node = ir.Aggregate(base.node, self.keys, specs)
        rep = base._rep_nodes | ({node.id} if base._replicated else set())
        return DataFrame(node, frozenset(rep))

    aggregate = agg

    def size(self, name: str = "size") -> DataFrame:
        """Row count per group (pandas ``.size()``)."""
        return self.agg(**{name: AggExpr("count", None)})

    def _apply_all(self, fn: str, skipna: bool = True) -> DataFrame:
        if self._select is not None:
            cols = [c for c in self._select if c not in self.keys]
        else:
            cols = [c for c in self.df.node.schema if c not in self.keys]
        if fn in self._NUMERIC_ONLY:
            # pandas numeric_only: whole-frame sugar skips category columns
            # (an explicit agg spec on one raises instead).
            sch = self.df.node.schema
            cols = [c for c in cols if not is_category(sch[c])]
        if not cols:
            return self.size(name="count")
        return self.agg(**{c: AggExpr(fn, ColRef(self.df.node.id, c),
                                      skipna=skipna)
                           for c in cols})

    def transform(self, fn: str | None = None, **aggs) -> DataFrame:
        """Broadcast per-group aggregates back onto the rows (pandas
        ``groupby().transform``): aggregate, then join the result back on
        the group keys — every original row and column survives, with the
        group statistic alongside.

        Two spellings: ``transform("mean")`` applies the fn to every
        (selected) non-key column as ``<col>_<fn>``;
        ``transform(z=("x", "sum"))`` names outputs like :meth:`agg`.

        The broadcast join shares the groupby's keys, so under
        ``adaptive_stats`` a hot group rides the salted-join path and the
        tiny aggregated side replicates instead of pinning one shard.
        """
        if fn is not None:
            if aggs:
                raise TypeError(
                    "transform: pass a single fn OR name=(column, fn) "
                    "specs, not both")
            f = _AGG_ALIASES.get(fn, fn)
            if f not in AGG_FNS:
                raise TypeError(f"transform: unknown fn {fn!r}; valid: "
                                f"{AGG_FNS} (+ aliases {tuple(_AGG_ALIASES)})")
            if self._select is not None:
                cols = [c for c in self._select if c not in self.keys]
            else:
                cols = [c for c in self.df.node.schema if c not in self.keys]
            if not cols:
                raise ValueError("transform: no value columns to aggregate")
            aggs = {f"{c}_{f}": (c, f) for c in cols}
        if not aggs:
            raise ValueError(
                "transform() needs a fn or at least one name=(column, fn)")
        clash = sorted(set(aggs) & set(self.df.node.schema))
        if clash:
            raise ValueError(f"transform: output names {clash} collide "
                             f"with existing columns")
        return self.df.merge(self.agg(**aggs), on=list(self.keys))

    def head(self, n: int = 5) -> DataFrame:
        """First ``n`` rows per group, pandas ``groupby().head``: fused as
        ``row_number() <= n`` riding the grouped-sort layout the segment
        machinery already establishes — ONE hash exchange total (elided
        entirely on a frame persisted on the keys), and the filter itself
        is collective-free.  Row selection matches pandas exactly: the
        block exchange and stable local sort preserve each group's original
        arrival order."""
        if n < 0:
            raise ValueError(f"head: n must be >= 0, got {n}")
        w = row_number(self.df, list(self.keys), None, out="__rn__")
        return w[w["__rn__"] <= n].drop("__rn__")

    def sum(self, skipna: bool = True) -> DataFrame:
        return self._apply_all("sum", skipna)

    def mean(self, skipna: bool = True) -> DataFrame:
        return self._apply_all("mean", skipna)

    def min(self, skipna: bool = True) -> DataFrame:
        return self._apply_all("min", skipna)

    def max(self, skipna: bool = True) -> DataFrame:
        return self._apply_all("max", skipna)

    def prod(self, skipna: bool = True) -> DataFrame:
        return self._apply_all("prod", skipna)

    def any(self, skipna: bool = True) -> DataFrame:
        return self._apply_all("any", skipna)

    def all(self, skipna: bool = True) -> DataFrame:
        return self._apply_all("all", skipna)

    def count(self) -> DataFrame:
        return self._apply_all("count")

    def nunique(self) -> DataFrame:
        return self._apply_all("nunique")


# ---------------------------------------------------------------------------
# constructors
# ---------------------------------------------------------------------------


def table(columns: dict[str, Any], name: str = "t") -> DataFrame:
    """Create a data frame from host/device arrays (DataSource analogue).

    Host columns go through ingest coercion (docs/dtypes.md): string /
    object-of-string arrays (``None``/``NaN`` holes allowed) are
    dictionary-encoded into int32 codes with a ``category[str]`` dtype;
    float columns holding NaN and object columns of numbers with ``None``
    holes become nullable; datetime/complex/structured inputs raise an
    actionable error.  Device (jax) arrays pass through untouched — they are
    assumed clean, numeric, and possibly mid-computation.  So do
    ``jax.ShapeDtypeStruct`` columns: such an abstract table plans, lowers
    and compiles at its size (``Lowered.hlo_text`` / ``_prepare``) but holds
    no data to execute."""
    import jax
    lens = {k: v.shape[0] if isinstance(v, jax.ShapeDtypeStruct) else len(v)
            for k, v in columns.items()}
    if len(set(lens.values())) > 1:
        raise ValueError(f"column length mismatch: {lens}")
    cols: dict[str, Any] = {}
    sch: dict[str, Any] = {}
    for k, v in columns.items():
        if isinstance(v, (jax.Array, jax.ShapeDtypeStruct)):
            cols[k] = v
            sch[k] = np.dtype(v.dtype)
            continue
        cols[k], sch[k] = coerce_column(k, v)
    return DataFrame(ir.Scan(name, cols, sch))


def from_pandas(df, name: str = "t") -> DataFrame:
    """Build a frame from a pandas DataFrame (duck-typed, no pandas import):
    columns feed :func:`table`'s ingest coercion, so object/string columns
    dictionary-encode and ``NaN``/``None``/``pd.NA`` holes become nulls."""
    if not hasattr(df, "columns") or not hasattr(df, "__getitem__"):
        raise TypeError(
            f"from_pandas expects a pandas DataFrame, got {type(df).__name__}")
    cols = {}
    for c in df.columns:
        s = df[c]
        cols[str(c)] = s.to_numpy() if hasattr(s, "to_numpy") else np.asarray(s)
    return table(cols, name)


def _parse_on(on) -> tuple[tuple[str, ...], tuple[str, ...]]:
    """Normalize the join key spec to (left_keys, right_keys) tuples.

    Accepted forms:
      "k"                       one key, same name both sides
      ("lk", "rk")              one key pair (historical form — a 2-tuple of
                                strings is a PAIR, not two key columns)
      ["k1", "k2", ...]         composite key, same names both sides
      [("a","ca"), "b", ...]    composite key, per-position pair or shared name
    """
    if isinstance(on, str):
        return (on,), (on,)
    # only a literal 2-TUPLE of strings is the historical pair form; a LIST
    # of two names (["k1","k2"]) is a composite key on shared names.
    if isinstance(on, tuple) and len(on) == 2 \
            and all(isinstance(x, str) for x in on):
        return (on[0],), (on[1],)
    lo, ro = [], []
    for item in on:
        if isinstance(item, str):
            lo.append(item)
            ro.append(item)
        else:
            l, r = item
            lo.append(l)
            ro.append(r)
    if not lo:
        raise ValueError("join requires at least one key column")
    return tuple(lo), tuple(ro)


# ---------------------------------------------------------------------------
# free-function shims (pre-v2 spellings; thin delegations to the fluent API)
# ---------------------------------------------------------------------------


def join(left: DataFrame, right: DataFrame, on, suffix: str = "_r",
         how: str = "inner") -> DataFrame:
    """Shim for :meth:`DataFrame.merge` (the historical spelling)."""
    return left.merge(right, on, how=how, suffix=suffix)


def aggregate(df: DataFrame, by, **aggs) -> DataFrame:
    """Shim for ``df.groupby(by).agg(...)``; ``by`` is a column name or a
    tuple/list of names (composite key).  Accepts the same specs as
    :meth:`GroupBy.agg` (AggExpr objects or pandas named-agg tuples); any
    number of ``nunique`` aggregations may be mixed in."""
    return df.groupby(by).agg(**aggs)


def concat(*dfs: DataFrame) -> DataFrame:
    """UNION ALL.  Column names must match; logical dtypes unify — category
    columns recode onto the union dictionary, and a column nullable in any
    part comes out nullable (ir.Concat reports part 0's schema, so the
    unified dtypes ride a Project override when parts disagree)."""
    schemas = [tuple(d.node.schema) for d in dfs]
    if len(set(schemas)) > 1:
        raise ValueError(f"schema mismatch in concat: {schemas}")
    parts = list(dfs)
    targets: list[dict[str, tuple]] = [{} for _ in parts]
    nullflags: list[dict[str, bool]] = [{} for _ in parts]
    over: dict[str, Any] = {}
    for c in schemas[0]:
        dts = [d.node.schema[c] for d in parts]
        flags = [is_category(dt) for dt in dts]
        if any(flags):
            if not all(flags):
                raise TypeError(
                    f"concat: column {c!r} is category[str] in some parts "
                    "and numeric in others — encode every part the same way")
            u = categories_of(dts[0])
            for dt in dts[1:]:
                u = union_categories(u, categories_of(dt))
            nb = any(is_nullable(dt) for dt in dts)
            for i, dt in enumerate(dts):
                if categories_of(dt) != u or is_nullable(dt) != nb:
                    targets[i][c] = u
                    nullflags[i][c] = nb
            over[c] = DType(CODE_DTYPE, u, nullable=nb)
        elif any(is_nullable(dt) for dt in dts) \
                and not is_nullable(dts[0]):
            over[c] = as_nullable(dts[0])
    parts = [d._recode(t, nf)
             for d, t, nf in zip(parts, targets, nullflags)]
    node = ir.Concat(tuple(d.node for d in parts))
    rep = frozenset().union(*(d._rep_nodes for d in parts))
    if all(d._replicated for d in parts):
        rep = rep | {node.id}
    if over:
        sch = node.schema
        proj = ir.Project(node, {c: ColRef(node.id, c) for c in sch},
                          {c: over.get(c, sch[c]) for c in sch})
        if node.id in rep:
            rep = rep | {proj.id}
        node = proj
    return DataFrame(node, frozenset(rep))


def cumsum(df: DataFrame, e, out: str = "cumsum", *,
           partition_by=None, order_by=None) -> DataFrame:
    """Distributed cumulative sum (MPI_Exscan analogue).

    With ``partition_by``, the sum restarts at every group boundary
    (``SUM(...) OVER (PARTITION BY ... ORDER BY ...)``) and rows come back
    hash-partitioned on the group keys, sorted by (partition, order) keys
    within each shard — the grouped layout, not input order."""
    return DataFrame(ir.Window(df.node, "cumsum", df._rw(e), out,
                               partition_by=_over_keys(partition_by),
                               order_by=_over_keys(order_by)),
                     df._rep_nodes)


def stencil(df: DataFrame, e, weights: Sequence[float], *, scale: float = 1.0,
            center: int | None = None, out: str = "stencil",
            partition_by=None, order_by=None, exact: bool = False) -> DataFrame:
    """1-D stencil: out[i] = sum_j w[j]/scale * x[i+j-center].

    SMA == stencil(x, [1,1,1], scale=3); WMA == stencil(x, [1,2,1], scale=4).
    With ``partition_by``, taps never cross a group boundary (the zero-border
    convention applies per group) — TPCx-BB Q26-style grouped moving
    averages.  ``exact=True`` renormalizes border windows by the weight mass
    of the taps that actually contributed (see :func:`rolling_mean`)."""
    w = tuple(float(x) / scale for x in weights)
    c = len(w) // 2 if center is None else center
    return DataFrame(ir.Window(df.node, "stencil", df._rw(e), out,
                               weights=w, center=c, exact=exact,
                               partition_by=_over_keys(partition_by),
                               order_by=_over_keys(order_by)),
                     df._rep_nodes)


def sma(df: DataFrame, e, window: int = 3, out: str = "sma", *,
        partition_by=None, order_by=None) -> DataFrame:
    return stencil(df, e, [1.0] * window, scale=float(window), out=out,
                   partition_by=partition_by, order_by=order_by)


def wma(df: DataFrame, e, weights: Sequence[float], out: str = "wma", *,
        partition_by=None, order_by=None) -> DataFrame:
    return stencil(df, e, weights, scale=float(sum(weights)), out=out,
                   partition_by=partition_by, order_by=order_by)


def lag(df: DataFrame, e, n: int = 1, out: str = "lag", *,
        partition_by=None, order_by=None) -> DataFrame:
    """SQL lag(): out[i] = x[i-n] across the distributed order (paper Table 1
    mentions SQL's lag/lead as the window-function alternative to stencils —
    here they ARE stencils: a one-hot window with offset).  Borders -> 0;
    with ``partition_by`` the border is the group edge."""
    return stencil(df, e, [1.0] + [0.0] * n, center=n, out=out,
                   partition_by=partition_by, order_by=order_by)


def lead(df: DataFrame, e, n: int = 1, out: str = "lead", *,
         partition_by=None, order_by=None) -> DataFrame:
    """SQL lead(): out[i] = x[i+n]; borders -> 0 (group edges when
    partitioned)."""
    return stencil(df, e, [0.0] * n + [1.0], center=0, out=out,
                   partition_by=partition_by, order_by=order_by)


def rolling_sum(df: DataFrame, e, window: int, out: str = "rolling_sum", *,
                partition_by=None, order_by=None) -> DataFrame:
    """Trailing rolling sum: out[i] = sum of x over rows [i-window+1 .. i].

    A one-sided stencil (center = window-1), so leading borders — the global
    start, or each group start when partitioned — contribute zeros."""
    return stencil(df, e, [1.0] * window, center=window - 1, out=out,
                   partition_by=partition_by, order_by=order_by)


def rolling_mean(df: DataFrame, e, window: int, out: str = "rolling_mean", *,
                 partition_by=None, order_by=None,
                 exact: bool = False) -> DataFrame:
    """Trailing rolling mean over rows [i-window+1 .. i].

    Default (``exact=False``, the zero-padded fast path): the first
    window-1 rows of the series — or of each group when partitioned —
    divide a zero-padded partial sum by the FULL window, per the stencil
    border convention.  ``exact=True`` divides by the number of rows that
    actually contributed instead (pandas ``rolling(window,
    min_periods=1).mean()``); it costs a second pass over the window mask —
    and, for the global form, a second halo exchange — which is why the
    padded form stays the default."""
    return stencil(df, e, [1.0] * window, scale=float(window),
                   center=window - 1, out=out, exact=exact,
                   partition_by=partition_by, order_by=order_by)


def _rank_df(df: DataFrame, kind: str, partition_by, order_by,
             out: str, ascending: bool = True) -> DataFrame:
    pk, ok = _over_keys(partition_by), _over_keys(order_by)
    node = df.node
    if not pk and ok:
        # GLOBAL window (no PARTITION BY): equal order-key tuples must be
        # adjacent across the shard-concatenated stream, so sort first.
        # The planner makes an already-globally-sorted input (leaderboard:
        # ``sort_values(...).persist()`` then rank) a FULL no-op — the rank
        # itself is a per-shard-count exscan, never a second global sort.
        node = ir.Sort(node, ok, ascending)
    return DataFrame(ir.Window(node, kind, None, out,
                               partition_by=pk, order_by=ok),
                     df._rep_nodes)


def rank(df: DataFrame, partition_by, order_by, out: str = "rank", *,
         ascending: bool = True) -> DataFrame:
    """SQL RANK() OVER ([PARTITION BY ...] ORDER BY ...): 1-based; equal
    order-key tuples share a rank, with gaps after ties.

    ``partition_by=None`` ranks GLOBALLY over ``order_by`` (``ascending``
    picks the direction, SQL ``ORDER BY ... DESC``): the engine sorts first
    — elided entirely when the input is already globally sorted that way —
    and computes ranks with a per-shard-count exscan plus boundary-run
    reconciliation (no second global pass)."""
    return _rank_df(df, "rank", partition_by, order_by, out, ascending)


def dense_rank(df: DataFrame, partition_by, order_by,
               out: str = "dense_rank", *,
               ascending: bool = True) -> DataFrame:
    """SQL DENSE_RANK(): ties share a rank, no gaps.  ``partition_by=None``
    ranks globally (see :func:`rank`)."""
    return _rank_df(df, "dense_rank", partition_by, order_by, out, ascending)


def row_number(df: DataFrame, partition_by, order_by=None,
               out: str = "row_number", *,
               ascending: bool = True) -> DataFrame:
    """SQL ROW_NUMBER(): 1-based position within the group (ties broken by
    the stable sort, so equal order keys number deterministically by
    post-exchange arrival order).

    ``partition_by=None`` numbers rows GLOBALLY: with ``order_by`` the
    stream is sorted first (no-op when already sorted), without it rows
    number in shard-concatenation arrival order — either way the numbers
    come from an exclusive scan of the per-shard counts, zero shuffles."""
    return _rank_df(df, "row_number", partition_by, order_by, out, ascending)


class Over:
    """Fluent handle for partitioned windows: ``df.over(partition_by=...,
    order_by=...)`` then any window verb — the SQL ``OVER`` clause as an
    object.  Each method returns a new DataFrame with the window column
    appended; results come back in the grouped (hash-partitioned, locally
    sorted) layout — which :meth:`DataFrame.persist` captures, so repeated
    windows over the same keys plan zero exchanges."""

    def __init__(self, df: DataFrame, partition_by, order_by=None):
        self.df = df
        self.partition_by = ir.as_keys(partition_by)
        self.order_by = _over_keys(order_by)

    def _kw(self):
        return dict(partition_by=self.partition_by, order_by=self.order_by or None)

    def cumsum(self, e, out: str = "cumsum") -> DataFrame:
        return cumsum(self.df, e, out, **self._kw())

    def stencil(self, e, weights, *, scale: float = 1.0,
                center: int | None = None, out: str = "stencil",
                exact: bool = False) -> DataFrame:
        return stencil(self.df, e, weights, scale=scale, center=center,
                       out=out, exact=exact, **self._kw())

    def sma(self, e, window: int = 3, out: str = "sma") -> DataFrame:
        return sma(self.df, e, window, out, **self._kw())

    def wma(self, e, weights, out: str = "wma") -> DataFrame:
        return wma(self.df, e, weights, out, **self._kw())

    def lag(self, e, n: int = 1, out: str = "lag") -> DataFrame:
        return lag(self.df, e, n, out, **self._kw())

    def lead(self, e, n: int = 1, out: str = "lead") -> DataFrame:
        return lead(self.df, e, n, out, **self._kw())

    def rolling_sum(self, e, window: int, out: str = "rolling_sum") -> DataFrame:
        return rolling_sum(self.df, e, window, out, **self._kw())

    def rolling_mean(self, e, window: int, out: str = "rolling_mean", *,
                     exact: bool = False) -> DataFrame:
        return rolling_mean(self.df, e, window, out, exact=exact, **self._kw())

    def rank(self, out: str = "rank") -> DataFrame:
        return rank(self.df, self.partition_by, self.order_by, out)

    def dense_rank(self, out: str = "dense_rank") -> DataFrame:
        return dense_rank(self.df, self.partition_by, self.order_by, out)

    def row_number(self, out: str = "row_number") -> DataFrame:
        return row_number(self.df, self.partition_by, self.order_by, out)


def udf(fn, *args) -> UDF:
    """Lift a jax-traceable elementwise function into an expression."""
    return fn_expr(fn, *args)


def explain(df: DataFrame, cfg: ExecConfig | None = None) -> str:
    return df.explain(cfg)
