"""Multi-query serving: a long-lived Session over ONE device mesh.

The paper's compiler model — and every PR before this one — is
one-query-one-process: build the plan, compile the SPMD program, run,
exit.  A serving deployment amortizes all of that across queries instead.
A :class:`Session` owns the mesh for its lifetime and provides:

  * **Shared-table registry** — ``session.register("item", df)`` persists
    the frame once (device shards + layout claims) and hands every later
    query the SAME layout-carrying scan via ``session.table("item")``.
    Frames persisted at a different shard count re-enter through
    :func:`~repro.runtime.reshard.reshard` — an on-device split/merge, no
    host gather.
  * **Plan cache** — compiled executables keyed by the *shape* plan
    fingerprint (``stats.plan_fingerprint(node, scans="shape")``: structure
    + dictionary-aware schemas + layout geometry, NO table identity) plus
    the ExecConfig signature.  A hit replays the compiled ``shard_map``
    executable and merely **rebinds** the scan buffers (``Lowered``'s
    ``scan_nodes`` path), so the same query shape over a different
    registered table costs zero lowers and zero compiles.  LRU eviction at
    ``cache_capacity``; hit/miss/eviction counters via :meth:`stats`.
  * **Concurrent admission** — ``submit()`` is thread-safe and returns a
    ticket; host-side planning/lowering for distinct queries overlaps in a
    small worker pool while a mesh lock serializes device execution
    (SPMD collectives cannot interleave).  ``admission`` bounds queued
    queries; each finished query carries a :class:`QueryRecord` with the
    seconds of each phase, cache outcome, retry events, and the plan's
    collective count.  Each phase is also a host span on the profiler's
    clock (``hf.query``, ``hf.plan``, ``hf.lock``, ... — core/spans.py).
  * **Stats persistence** — the session scopes its own
    :class:`~repro.core.stats.StatsStore` (realized row counts + retry
    events) and persists it as ``<session_dir>/stats.json``, so a
    restarted server plans with yesterday's feedback.  A corrupt sidecar
    raises :class:`~repro.core.errors.StatsError` unless
    ``recover_stats=True`` quarantines it and starts cold.

Failure behaviour follows the PR 9 taxonomy: a cache-hit execution that
overflows (the cached capacities were sized for a smaller table) or trips
an invariant/kernel error falls back to the MISS path — replan + the full
retry ladder — and the refreshed entry replaces the stale one.  See
docs/serving.md.
"""
from __future__ import annotations

import dataclasses as _dc
import os
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Optional

import numpy as np

from ..core import errors as err
from ..core import ir
from ..core import stats as _st
from ..core.api import DataFrame
from ..core.lower import ExecConfig, Lowered, lower
from ..core.spans import span, timed
from . import retry as _rt
from .reshard import reshard as _reshard

_MONO = time.monotonic


def cfg_signature(cfg: ExecConfig, P: int) -> tuple:
    """Hashable signature of every plan-shaping ExecConfig lever.

    The mesh object itself is excluded (not hashable, and two meshes of the
    same shape compile identically); its shard count ``P`` stands in.  Dict
    levers (cap_overrides, kernel_fallbacks) canonicalize to sorted tuples.
    """
    parts: list = [("P", P)]
    for f in _dc.fields(cfg):
        if f.name == "mesh":
            continue
        v = getattr(cfg, f.name)
        if isinstance(v, dict):
            v = tuple(sorted(v.items()))
        elif isinstance(v, (list, set)):
            v = tuple(sorted(v))
        elif not isinstance(v, (str, int, float, bool, tuple, type(None))):
            v = repr(v)
        parts.append((f.name, v))
    return tuple(parts)


# the phases of an execution (Session._execute, Lowered.__call__): exec_s
# is their sum
EXEC_PHASES = ("lock", "prepare", "run", "check")
# Session.stats(): counts and phase seconds summed over finished queries
_PHASE_TOTALS = ("queue", "plan", "exec") + EXEC_PHASES
_TOTALS = ("queries", "compiles", "collectives") + tuple(
    f"{p}_s" for p in _PHASE_TOTALS)


@dataclass
class QueryRecord:
    """Per-query serving record (``DTable.query_record`` of the answer).

    Seconds on the monotonic clock, each also a host span ``hf.<phase>``
    (docs/serving.md): ``queue_s`` from ``submit()`` to a worker's start
    (admission and the pool's queue); ``plan_s`` the fingerprint, cache
    lookup and scan rebind, plus lowering and compiling on a miss;
    ``lock_s`` the wait for the mesh lock; ``prepare_s`` the inputs placed
    on the device; ``run_s`` dispatch until the overflow flags are on the
    host; ``check_s`` the host-side overflow and invariant checks.
    ``exec_s`` is ``lock_s + prepare_s + run_s + check_s``, summed over
    every execution of the query (a hit that falls back, retries).
    ``DTable.to_numpy`` adds ``readback_s`` and ``readback_bytes``."""

    qid: int
    fingerprint: str
    cache: str = "miss"             # "hit" | "miss" | "hit_fallback"
    queue_s: float = 0.0
    plan_s: float = 0.0
    exec_s: float = 0.0
    lock_s: float = 0.0
    prepare_s: float = 0.0
    run_s: float = 0.0
    check_s: float = 0.0
    readback_s: float = 0.0
    readback_bytes: int = 0
    collectives: int = 0            # plan's all_to_all count per execution
    compiles: int = 0               # NEW jit entries this query caused
    events: tuple = ()
    # the compiled plan that produced the answer (op_scopes(), hlo_text())
    plan: Optional[Lowered] = field(default=None, repr=False, compare=False)

    def add_exec(self, phases: dict) -> None:
        """Add one execution's phase seconds (keys of ``EXEC_PHASES``)."""
        for name, s in phases.items():
            setattr(self, f"{name}_s", getattr(self, f"{name}_s") + s)
        self.exec_s += sum(phases.values())


@dataclass
class _CacheEntry:
    lowered: Lowered
    scan_ids: tuple                 # pre-optimization scan ids, topo order
    rebindable: bool                # post-opt scans map 1:1 onto pre-opt


class PlanCache:
    """LRU map: (shape fingerprint, ExecConfig signature) -> compiled plan."""

    def __init__(self, capacity: int = 64):
        self.capacity = int(capacity)
        self._d: OrderedDict = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self._lock = threading.Lock()

    def get(self, key) -> Optional[_CacheEntry]:
        with self._lock:
            e = self._d.get(key)
            if e is None:
                self.misses += 1
                return None
            self._d.move_to_end(key)
            self.hits += 1
            return e

    def put(self, key, entry: _CacheEntry) -> None:
        with self._lock:
            self._d[key] = entry
            self._d.move_to_end(key)
            while len(self._d) > self.capacity:
                self._d.popitem(last=False)
                self.evictions += 1

    def __len__(self) -> int:
        return len(self._d)

    def plans(self) -> list[Lowered]:
        """The cached compiled plans, least recently used first."""
        with self._lock:
            return [e.lowered for e in self._d.values()]


def _topo_scans(node: ir.Node) -> list[ir.Scan]:
    return [n for n in ir.topo_order(node) if isinstance(n, ir.Scan)]


class Session:
    """A long-lived serving session over one device mesh (docs/serving.md).

    >>> sess = Session(cfg)
    >>> sess.register("item", item_df)          # persist once
    >>> t = sess.collect(q26(sess.table("store_sales"), sess.table("item")))
    >>> sess.stats()["plan_cache"]["hits"]
    """

    def __init__(self, cfg: ExecConfig | None = None,
                 session_dir: str | None = None, *,
                 cache_capacity: int = 64, admission: int = 8,
                 workers: int = 4, recover_stats: bool = False):
        self.cfg = cfg or ExecConfig()
        self.mesh = self.cfg.get_mesh()
        self.P = int(np.prod([self.mesh.shape[a] for a in self.cfg.axes]))
        if self.cfg.mesh is None:
            # pin the session's mesh into its config so every plan/reshard
            # built through the session targets the same devices.
            self.cfg = _dc.replace(self.cfg, mesh=self.mesh)
        self.session_dir = session_dir
        self._sidecar = (os.path.join(session_dir, "stats.json")
                         if session_dir else None)
        self.store = self._load_store(recover_stats)
        # the session's store becomes the process-current store for its
        # lifetime (module-level record_realized/record_events land in it
        # from any worker thread); close() restores the previous one.
        self._prev_store = _st.use_store(self.store)
        self.plan_cache = PlanCache(cache_capacity)
        self._tables: dict[str, DataFrame] = {}
        self._tables_lock = threading.Lock()
        self._mesh_lock = threading.Lock()
        self._admit = threading.BoundedSemaphore(max(admission, 1))
        self._pool = ThreadPoolExecutor(
            max_workers=max(workers, 1), thread_name_prefix="hf-serve")
        # running totals over finished queries (stats())
        self._totals = dict.fromkeys(_TOTALS, 0)
        self._totals_lock = threading.Lock()
        self._qid = 0
        self._register_collectives = 0
        self._closed = False

    # -- lifecycle -----------------------------------------------------------

    def _load_store(self, recover: bool) -> _st.StatsStore:
        if not self._sidecar or not os.path.exists(self._sidecar):
            return _st.StatsStore()
        try:
            return _st.StatsStore.load(self._sidecar)
        except err.StatsError:
            if not recover:
                raise
            # quarantine the corrupt sidecar (keep it for inspection) and
            # start cold — recover_stats is the operator's explicit opt-in.
            os.replace(self._sidecar, self._sidecar + ".corrupt")
            return _st.StatsStore()

    def save_stats(self) -> None:
        if self._sidecar:
            self.store.save(self._sidecar)

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._pool.shutdown(wait=True)
        self.save_stats()
        _st.use_store(self._prev_store)

    def __enter__(self) -> "Session":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- shared-table registry ----------------------------------------------

    def register(self, name: str, df: DataFrame, *,
                 partition_by=None, sort_by=None) -> DataFrame:
        """Persist ``df`` once under ``name`` and share its layout-carrying
        scan with every later query.

        ``partition_by``/``sort_by`` request a layout (one on-device
        exchange / local sort) before persisting.  An already-persisted
        frame at a different shard count is resharded on device (split or
        merge, never a host gather)."""
        with span("register"):
            node = df.node
            q = df
            if isinstance(node, ir.Scan) and node.layout is not None \
                    and node.layout.counts is not None:
                if node.layout.nshards != self.P:
                    q = _reshard(df, self.P, self.cfg, name=name)
                if partition_by or sort_by:
                    q = self._relayout(q, partition_by, sort_by, name)
            else:
                if partition_by:
                    q = q.repartition(partition_by)
                if sort_by:
                    q = q.sort_within_partitions(sort_by)
                q = self._persist(q, name)
        with self._tables_lock:
            self._tables[name] = q
        return q

    def _relayout(self, df, partition_by, sort_by, name):
        q = df
        if partition_by:
            q = q.repartition(partition_by)
        if sort_by:
            q = q.sort_within_partitions(sort_by)
        return self._persist(q, name)

    def _persist(self, df: DataFrame, name: str) -> DataFrame:
        with self._mesh_lock:
            out = df.persist(self.cfg, name=name)
        # registration cost (collectives) is charged to the session, not to
        # the steady-state query mix (the serve smoke's pass-1 total): a
        # host-only re-lower of the plan persist() just ran yields the
        # collective count (it cannot fail where that lowering succeeded).
        low, _ = lower(df.node, self.cfg, force_rep=df._force_rep())
        self._register_collectives += low.pplan.collective_count()
        return out

    def table(self, name: str) -> DataFrame:
        with self._tables_lock:
            if name not in self._tables:
                raise KeyError(
                    f"no table {name!r} registered (have "
                    f"{sorted(self._tables)})")
            return self._tables[name]

    def tables(self) -> dict[str, DataFrame]:
        with self._tables_lock:
            return dict(self._tables)

    # -- query execution -----------------------------------------------------

    def submit(self, df: DataFrame, cfg: ExecConfig | None = None) -> Future:
        """Thread-safe asynchronous admission: returns a Future resolving to
        the DTable.  Host-side planning/lowering overlaps across queries;
        device execution serializes on the mesh lock.  At most ``admission``
        queries are queued/in flight; further submits block."""
        if self._closed:
            raise RuntimeError("session is closed")
        submitted = _MONO()
        self._admit.acquire()

        def run():
            try:
                return self._run_query(df, cfg or self.cfg, submitted)
            finally:
                self._admit.release()

        return self._pool.submit(run)

    def collect(self, df: DataFrame, cfg: ExecConfig | None = None):
        """Synchronous execute-through-the-session (admission + cache)."""
        return self.submit(df, cfg).result()

    def _next_qid(self) -> int:
        with self._totals_lock:
            self._qid += 1
            return self._qid

    @staticmethod
    def _rep_key(df: DataFrame) -> tuple:
        """Positional REP pins per scan (``df.replicate()`` changes the plan
        without changing the IR structure, so it must key the cache)."""
        rep = df._force_rep()
        return tuple(n.id in rep for n in _topo_scans(df.node))

    def _run_query(self, df: DataFrame, cfg: ExecConfig, submitted: float):
        t0 = _MONO()
        qid = self._next_qid()
        with span("query", qid=qid):
            with span("plan"):
                fp = _st.plan_fingerprint(df.node, scans="shape")
                key = (fp, cfg_signature(cfg, self.P), self._rep_key(df))
                entry = self.plan_cache.get(key)
                hit = entry is not None and entry.rebindable
                scan_nodes = self._rebind(df, entry.lowered) if hit else None
            rec = QueryRecord(qid=qid, fingerprint=fp, queue_s=t0 - submitted,
                              plan_s=_MONO() - t0)
            if hit:
                t = self._try_hit(entry.lowered, scan_nodes, rec)
                if t is not None:
                    self._finish(rec, t)
                    return t
                rec.cache = "hit_fallback"
            t = self._run_miss(df, cfg, key, rec)
            self._finish(rec, t)
            return t

    @staticmethod
    def _rebind(df: DataFrame, lowered: Lowered) -> dict | None:
        """This query's scans by the cached plan's scan ids (positional);
        None when the counts differ (the entry cannot serve the query)."""
        new_scans = _topo_scans(df.node)
        if len(new_scans) != len(lowered.scans):
            return None
        return {str(s.id): new_scans[i] for i, s in enumerate(lowered.scans)}

    def _execute(self, lowered: Lowered, rec: QueryRecord, **kw):
        """Run ``lowered`` under the mesh lock; the wait for the lock and
        the execution's phases are added to ``rec``."""
        phases: dict = {}
        with timed("lock", phases):
            self._mesh_lock.acquire()
        try:
            return lowered(phases=phases, **kw)
        finally:
            self._mesh_lock.release()
            rec.add_exec(phases)

    def _try_hit(self, lowered: Lowered, scan_nodes, rec: QueryRecord):
        """Replay the cached executable with this query's scan buffers.
        Returns None when the entry cannot serve this query (falls back to
        the miss path, which replaces the entry)."""
        if scan_nodes is None:
            return None
        before = lowered.compiles
        try:
            t = self._execute(lowered, rec, scan_nodes=scan_nodes)
        except (ValueError, err.KernelBackendError, err.PlanInvariantError):
            return None
        if getattr(t, "overflow", False) or getattr(
                t, "invariant_failures", ()):
            # cached capacities were sized for a different table: replan
            return None
        rec.cache = "hit"
        rec.plan = lowered
        rec.collectives = lowered.pplan.collective_count()
        rec.compiles = lowered.compiles - before
        return t

    def _run_miss(self, df: DataFrame, cfg: ExecConfig, key, rec: QueryRecord):
        """Full plan + retry-ladder execution; caches the survivor."""
        policy = _rt.RetryPolicy(max_retries=max(cfg.auto_retry, 0),
                                 scope=getattr(cfg, "retry_scope", "op"))

        def run_once(c):
            # lowering and compiling (host-side) run outside the mesh lock
            # so other queries' planning and compiles overlap; execution
            # serializes.
            ta = _MONO()
            with span("plan"):
                with span("lower"):
                    lowered, _ = lower(df.node, c, force_rep=df._force_rep())
                with span("compile"):
                    lowered.compile()
            rec.plan_s += _MONO() - ta
            return lowered, self._execute(lowered, rec)

        lowered, t, events, cfg2 = policy.execute(run_once, cfg)
        if events:
            _rt.record_events(lowered.root, events)
        if cfg2.adaptive_stats and not t.overflow:
            _st.record_realized(lowered.root, np.asarray(t.counts))
        rec.plan = lowered
        rec.collectives = lowered.pplan.collective_count()
        rec.compiles = lowered.compiles
        rec.events = tuple(events)
        if not getattr(t, "overflow", False):
            self.plan_cache.put(key, self._make_entry(df, lowered))
        self.save_stats()
        return t

    def _make_entry(self, df: DataFrame, lowered: Lowered) -> _CacheEntry:
        # ``lowered.scans`` is the optimized plan's scans in topo order; the
        # optimizer rewrites scan NODES (column pruning mints new ids) but
        # preserves count and relative order, so a later query with the same
        # shape fingerprint maps its scans onto the cached ones positionally.
        # A plan whose optimization dropped or duplicated scans is cached
        # but not rebindable (hits would mis-wire tables: treat as miss).
        pre_ids = tuple(s.id for s in _topo_scans(df.node))
        post_ids = [s.id for s in lowered.scans]
        rebindable = len(post_ids) == len(pre_ids) == len(set(post_ids))
        return _CacheEntry(lowered, pre_ids, rebindable)

    def _finish(self, rec: QueryRecord, t) -> None:
        rec.events = rec.events or tuple(getattr(t, "events", ()) or ())
        t.query_record = rec
        with self._totals_lock:
            tot = self._totals
            tot["queries"] += 1
            tot["compiles"] += rec.compiles
            tot["collectives"] += rec.collectives
            for p in _PHASE_TOTALS:
                tot[f"{p}_s"] += getattr(rec, f"{p}_s")

    # -- introspection -------------------------------------------------------

    def stats(self) -> dict:
        """Plan-cache counters, and counts and phase seconds summed over
        the queries finished so far (``phase_s``: ``queue``, ``plan``,
        ``exec`` and its parts ``lock``, ``prepare``, ``run``, ``check``)."""
        with self._totals_lock:
            tot = dict(self._totals)
        pc = self.plan_cache
        return {
            "P": self.P,
            "queries": tot["queries"],
            "plan_cache": {"hits": pc.hits, "misses": pc.misses,
                           "evictions": pc.evictions, "size": len(pc),
                           "capacity": pc.capacity},
            "compiles": tot["compiles"],
            "collectives": tot["collectives"],
            "register_collectives": self._register_collectives,
            "tables": sorted(self._tables),
            "phase_s": {p: tot[f"{p}_s"] for p in _PHASE_TOTALS},
        }

    def explain(self, df: DataFrame, cfg: ExecConfig | None = None) -> str:
        """Cache-aware EXPLAIN: the plan plus this session's cache outcome
        for the query's key and the last recorded retry events."""
        cfg = cfg or self.cfg
        fp = _st.plan_fingerprint(df.node, scans="shape")
        key = (fp, cfg_signature(cfg, self.P),
               tuple(sorted(n.id in df._force_rep()
                            for n in _topo_scans(df.node))))
        with self.plan_cache._lock:
            cached = key in self.plan_cache._d
        prev = _st.use_store(self.store)
        try:
            from ..core.api import explain as _explain
            body = _explain(df, cfg)
        finally:
            _st.use_store(prev)
        evs = self.store.events.get(_st.plan_fingerprint(df.node), ())
        lines = [f"session: P={self.P} plan_cache="
                 f"{'HIT' if cached else 'MISS'} fingerprint={fp[:12]}",
                 body]
        if evs:
            lines.append("last run events:")
            lines.extend(f"  {e.render()}" for e in evs)
        return "\n".join(lines)
