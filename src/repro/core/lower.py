"""Lowering: optimized logical plan -> physical plan -> ONE jitted SPMD program.

This is where the paper's end-to-end claim is realized: the entire plan —
relational operators, window analytics, UDFs and free array computation —
executes inside a single ``jax.shard_map`` region under a single ``jax.jit``,
so XLA fuses across relational boundaries exactly as CGen+icc fused the
generated C++.  There is no runtime scheduler and no master (paper §2.2).

The per-shard program is no longer derived node-by-node from the logical
plan: lowering first runs the property-driven physical planner
(core/physical_plan.py), which decides where hash exchanges and local sorts
are actually REQUIRED, and this module merely executes the resulting op list.
Capacity planning also lives with the physical plan — an elided exchange
means smaller buffers, not just fewer collectives.
"""
from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from . import distribution as D
from . import errors as err
from . import ir, physical as phys
from . import physical_plan as pp
from ..kernels import registry as kreg
from .dtypes import NULL_CODE, categories_of, is_category, physical_dtype
from .expr import ExternalArray, evaluate, nulltag_for
from .table import DTable, pad_to


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------


@dataclass
class ExecConfig:
    """Execution configuration (capacity planning + physical choices)."""

    mesh: Any = None                  # jax Mesh; default: all local devices, axis "data"
    axes: tuple[str, ...] = ("data",)
    # capacity policy: "safe" bounds every buffer by the worst case (tests);
    # otherwise capacities are input_cap * slack and overflow is flagged.
    safe_capacities: bool = True
    shuffle_slack: float = 2.0
    join_expansion: float = 1.5
    # physical choices (§Perf levers)
    exscan_method: str = "allgather"  # or "ladder"
    broadcast_join: bool = True       # beyond-paper: REP side joins without shuffle
    # use_pallas: the ONE kernel-backend lever.  "off" runs every hot-path
    # primitive as its lax composition (ref backend); "interpret" runs the
    # Pallas kernels under the interpreter (CPU CI, numerics debugging);
    # "compiled" compiles them for the accelerator (TPU).  Empty string
    # defers to $HIFRAMES_USE_PALLAS, defaulting to "off".  Backends are a
    # numerics swap only — the physical plan is identical in all modes.
    use_pallas: str = ""
    # deprecated alias for use_pallas="interpret" (the pre-registry bool).
    use_kernels: bool = False
    optimize_plan: bool = True
    # property-driven exchange/sort elision (core/physical_plan.py); False
    # restores the exchange-per-operator baseline — the A/B lever for
    # benchmarks and a safety valve.
    elide_exchanges: bool = True
    # -- shuffle engine v2 levers (both A/B-gated like elide_exchanges) -----
    # packed_exchange: ship ALL columns of an exchange as ONE word-packed
    # (P, bucket, W) uint32 payload — exactly 2 all_to_all per exchange
    # (counts + payload) instead of 1 + n_columns.  False restores the
    # per-column-collective baseline.
    packed_exchange: bool = True
    # partial_agg: split a shuffling aggregate with decomposable agg fns
    # into PartialAgg -> HashExchange -> FinalAgg, so each shard ships at
    # most its DISTINCT local key groups instead of all raw rows.
    partial_agg: bool = True
    # agg_group_cap: optional user bound on distinct groups per shard; when
    # set, PartialAgg buffers (and the post-partial exchange bucket) shrink
    # to it.  Overflow-flagged and doubled by the collect() retry loop.
    agg_group_cap: int | None = None
    # capacity-overflow auto-retry (runtime/ft.py semantics, built into
    # collect): replan with doubled expansion, at most this many times.
    auto_retry: int = 3
    # -- adaptive statistics (core/stats.py; docs/adaptive_planning.md) -----
    # adaptive_stats: build a sampled StatsContext per plan and let it make
    # planner DECISIONS: salted skew joins, cheaper-side re-exchange for
    # mixed-alignment joins, and PartialAgg auto-capacity from the
    # distinct-count estimate (plus realized feedback from previous runs of
    # the same plan fingerprint).  Off by default: plans are byte-identical
    # to the stats-blind planner.  explain() annotates estimates either way.
    adaptive_stats: bool = False
    # salt_threshold: sampled key frequency above which a join key counts as
    # a heavy hitter and gets salted across salt_factor sub-partitions.
    # Halved automatically when realized feedback shows shard skew.
    salt_threshold: float = 0.1
    salt_factor: int = 8
    # stats_sample: rows sampled per base table (even-position, like
    # sample_sort's splitter sampling).
    stats_sample: int = 256
    # stats_cap_slack: headroom multiplier on SAMPLED estimates when they
    # size buffers (realized feedback is exact and gets none).  Doubled by
    # the overflow-retry loop alongside shuffle_slack.
    stats_cap_slack: float = 2.0
    # -- execution guardrails (docs/robustness.md) --------------------------
    # validate: in-flight invariant checks — row-count conservation and a
    # packed-word checksum across every exchange, post-sort monotonicity,
    # category-code range.  All checks are per-shard locals reduced on the
    # host: they add ZERO collectives and change ZERO plans (census-gated).
    # None defers to $HIFRAMES_VALIDATE (default off).
    validate: Any = None
    # fault_inject: a runtime.faults.FaultPlan with deterministic injection
    # points (force-overflow an op, fail a kernel backend, poison a stats
    # estimate, corrupt an exchange payload).  None = no injection.
    fault_inject: Any = None
    # retry_scope: "op" escalates only the overflowed capacity site(s) via
    # cap_overrides (strictly fewer retries + smaller buffers on skew);
    # "global" restores the legacy slack-doubling across all four knobs.
    retry_scope: str = "op"
    # cap_overrides: {op_id: (cap_floor, bucket_floor)} applied as floors in
    # compute_capacities — written by runtime.retry.RetryPolicy, not users.
    cap_overrides: Any = None
    # kernel_fallbacks: {kernel name: mode} per-kernel backend overrides —
    # the degradation-ladder state (kernels/registry.downgrade) driven by
    # RetryPolicy on KernelBackendError.  None = all kernels on use_pallas.
    kernel_fallbacks: Any = None

    def __post_init__(self):
        if not self.use_pallas:
            self.use_pallas = os.environ.get("HIFRAMES_USE_PALLAS", "off")
        if self.use_kernels and self.use_pallas == "off":
            self.use_pallas = "interpret"
        if self.use_pallas not in kreg.MODES:
            raise ValueError(
                f"use_pallas must be one of {kreg.MODES}, "
                f"got {self.use_pallas!r}")
        if self.validate is None:
            self.validate = os.environ.get(
                "HIFRAMES_VALIDATE", "0").lower() in ("1", "true", "yes", "on")
        else:
            self.validate = bool(self.validate)
        if self.retry_scope not in ("op", "global"):
            raise ValueError(
                f"retry_scope must be 'op' or 'global', got {self.retry_scope!r}")

    def get_mesh(self) -> Mesh:
        if self.mesh is not None:
            return self.mesh
        devs = np.array(jax.devices())
        return Mesh(devs.reshape((len(devs),)), ("data",))


# ---------------------------------------------------------------------------
# executor
# ---------------------------------------------------------------------------


def _cnt_tag(scan_id: int) -> str:
    """Reserved ext-group tag carrying a persisted scan's (P,) count vector
    (kept out of the scans group so the shard_map signature stays stable)."""
    return f"__cnt:{scan_id}"


class Lowered:
    """A compiled physical plan: callable on (possibly fresh) source arrays."""

    def __init__(self, root: ir.Node, cfg: ExecConfig, dists: dict[int, str],
                 pplan: pp.PhysicalPlan):
        self.root = root
        self.cfg = cfg
        self.dists = dists
        self.pplan = pplan
        fault = getattr(cfg, "fault_inject", None)
        fallbacks = getattr(cfg, "kernel_fallbacks", None)
        # wrap only when something can go wrong at the kernel layer: pallas
        # backends (typed KernelBackendError), per-kernel fallbacks, or an
        # injected kernel fault.  The default off-mode path keeps the cached
        # KernelSet untouched.
        need_wrap = (cfg.use_pallas != "off" or bool(fallbacks)
                     or (fault is not None
                         and getattr(fault, "fail_kernel", "")))
        # kernel name -> (mode, fn, abstract args) of every Pallas kernel the
        # trace called: a program that fails to compile is blamed on the
        # first of them that fails to compile alone (_blame_kernel).
        self.traced_kernels: dict = {}
        self.kernels = kreg.resolve_with(
            cfg.use_pallas, fallbacks,
            wrap=(_kernel_wrap(fault, self.traced_kernels)
                  if need_wrap else None))
        self.mesh = cfg.get_mesh()
        self.platform = self.mesh.devices.flat[0].platform
        self.P = int(np.prod([self.mesh.shape[a] for a in cfg.axes]))
        self.events: list = []   # degradation events picked up by RetryPolicy
        self.compiles = 0        # jit-cache misses (plan-cache hit => stays 0)
        self._build()

    # -- input marshalling ---------------------------------------------------

    def _gather_inputs(self):
        scans = [n for n in ir.topo_order(self.root) if isinstance(n, ir.Scan)]
        exts: dict[str, Any] = {}
        ext_caps: dict[str, int] = {}
        for n in ir.topo_order(self.root):
            for e in _node_exprs(n):
                for sub in _walk_expr(e):
                    if isinstance(sub, ExternalArray):
                        exts[sub.tag] = sub.array
                        child = n.children[0] if n.children else n
                        ext_caps[sub.tag] = self.pplan.final_op(child).cap
        self._ext_caps = ext_caps
        return scans, exts

    def _build(self):
        cfg, mesh, axes = self.cfg, self.mesh, self.cfg.axes
        scans, exts = self._gather_inputs()
        self.scans, self.exts = scans, exts
        # persisted scans whose device shards re-enter directly (no host
        # round-trip): their per-shard valid counts ride in as a sharded
        # (P,) vector instead of being derived from a block row count.  The
        # vector travels in the ext input group under a reserved tag, so the
        # shard_map signature (scans, ext) stays stable.
        self.dev_scans = {s.id for s in scans
                          if s.layout is not None
                          and s.layout.device_valid(self.P)
                          and self.dists[s.id] != D.REP}

        in_specs = {"scans": {}, "ext": {}}
        for s in scans:
            rep = self.dists[s.id] == D.REP
            spec = P() if rep else P(axes)
            in_specs["scans"][str(s.id)] = {c: spec for c in s.columns}
            if s.id in self.dev_scans:
                in_specs["ext"][_cnt_tag(s.id)] = P(axes)
        for tag in exts:
            in_specs["ext"][tag] = P(axes)

        out_specs = {"cols": {c: P(axes) for c in self.root.schema},
                     "count": P(axes), "overflow": P(axes),
                     "ovf_req": P(axes)}

        root = self.root
        pplan = self.pplan
        kernels = self.kernels
        Pn = self.P
        validate = bool(getattr(cfg, "validate", False))
        fault = getattr(cfg, "fault_inject", None)

        # -- per-op failure attribution: the static capacity-site table.
        # per_shard emits one (flag, requirement-estimate) pair per site, in
        # this order; __call__ reduces them host-side into DTable.overflow_ops
        # so the retry policy can escalate exactly the op that overflowed.
        self.sites = _capacity_sites(pplan)
        forced = (fault.take_overflow_sites(pplan.ops)
                  if fault is not None else frozenset())
        corrupt = (fault.corrupt_sites(pplan.ops, cfg.packed_exchange)
                   if fault is not None else frozenset())

        # -- ExecConfig.validate: static check tables.  Flag checks emit one
        # per-shard bool; pair checks emit (in, out) uint32 scalars reduced
        # host-side — no collectives, no plan change (census-gated).
        self.val_flags_meta: list[tuple[str, int, str]] = []
        self.val_pairs_meta: list[tuple[str, int, str]] = []
        if validate:
            for op in pplan.ops:
                if isinstance(op, (pp.HashExchange, pp.SampleSort,
                                   pp.RebalanceOp)):
                    self.val_pairs_meta.append(
                        ("rowcount", op.op_id, type(op).__name__))
                    self.val_pairs_meta.append(
                        ("checksum", op.op_id, type(op).__name__))
                if isinstance(op, pp.LocalSort):
                    self.val_flags_meta.append(
                        ("monotonic", op.op_id, op.keys[0]))
                elif isinstance(op, pp.SampleSort):
                    self.val_flags_meta.append(
                        ("monotonic", op.op_id, op.node.by[0]))
            for c, dt in root.schema.items():
                if is_category(dt):
                    self.val_flags_meta.append(
                        ("code_range", pplan.root_id, c))
            out_specs["val_flags"] = P(axes)
            out_specs["val_pairs"] = P(axes)
        n_codes = {c: len(categories_of(dt))
                   for c, dt in root.schema.items() if is_category(dt)}

        def per_shard(inputs):
            rank = phys.my_rank(axes)
            env: dict[int, tuple[dict, Any]] = {}
            flags = []
            reqs = []
            vflags = []
            vpairs = []
            ext = {f"ext:{t}": v for t, v in inputs["ext"].items()}

            def flag(op, ovf, req):
                """Record one capacity site: overflow flag + this shard's
                requirement estimate (rows), with fault injection applied."""
                if op.op_id in forced:
                    ovf = jnp.logical_or(ovf, jnp.bool_(True))
                flags.append(ovf)
                reqs.append(jnp.asarray(req, jnp.float32).reshape(()))

            def pre_exchange(op, cols, cnt):
                if not validate:
                    return None
                return (cnt.astype(jnp.uint32), _checksum_u32(cols, cnt))

            def post_exchange(op, pre, out, cnt2):
                if op.op_id in corrupt:
                    # deterministic payload corruption: bump row 0 of the
                    # first non-bool column on every shard with rows.
                    name = next((k for k in sorted(out)
                                 if out[k].dtype != jnp.bool_), None)
                    if name is not None:
                        v = out[name]
                        bump = jnp.where(cnt2 > 0, jnp.ones((), v.dtype),
                                         jnp.zeros((), v.dtype))
                        out = dict(out)
                        out[name] = v.at[0].add(bump)
                if validate:
                    vpairs.append((pre[0], cnt2.astype(jnp.uint32)))
                    vpairs.append((pre[1], _checksum_u32(out, cnt2)))
                return out

            for op in pplan.ops:
                n = op.node
                ax = axes if op.dist != D.REP else ()

                if isinstance(op, pp.Source):
                    cols = inputs["scans"][str(n.id)]
                    if _cnt_tag(n.id) in inputs["ext"]:
                        # persisted device shards: this shard's valid count
                        # arrives sharded off the (P,) layout vector.
                        cnt = inputs["ext"][_cnt_tag(n.id)][0].astype(jnp.int32)
                    else:
                        rows = inputs["rows"][str(n.id)]   # static int
                        if op.dist == D.REP:
                            cnt = jnp.int32(rows)
                        else:
                            cnt = jnp.clip(rows - rank * op.cap, 0,
                                           op.cap).astype(jnp.int32)
                    res = (dict(cols), cnt)

                elif isinstance(op, pp.Compact):
                    cols, cnt = env[op.inputs[0]]
                    env_e = dict(cols)
                    env_e.update(ext)
                    pred = evaluate(n.pred, env_e)
                    keep = pred & phys.valid_mask(
                        cnt, next(iter(cols.values())).shape[0])
                    out, cnt2, ovf = phys.compact(cols, keep, op.cap,
                                                  kernels=kernels)
                    flag(op, ovf, jnp.sum(keep.astype(jnp.int32)))
                    res = (out, cnt2)

                elif isinstance(op, pp.Map):
                    cols, cnt = env[op.inputs[0]]
                    env_e = dict(cols)
                    env_e.update(ext)
                    cache: dict = {}
                    out = {}
                    for name, e in n.cols.items():
                        v = evaluate(e, env_e, cache)
                        cap = next(iter(cols.values())).shape[0]
                        out[name] = jnp.broadcast_to(v, (cap,)) if v.ndim == 0 else v
                    res = (out, cnt)

                elif isinstance(op, pp.WindowOp):
                    cols, cnt = env[op.inputs[0]]
                    env_e = dict(cols)
                    env_e.update(ext)
                    x = (evaluate(n.expr, env_e)
                         if n.expr is not None else None)
                    if n.partition_by:
                        # grouped layout established upstream (hash exchange
                        # + local sort, possibly elided): segment kernels,
                        # no collectives.
                        pk = tuple(cols[k] for k in n.partition_by)
                        if n.kind == "cumsum":
                            tag = nulltag_for(n.expr, n.children[0].schema)
                            col = phys.segment_cumsum(x, pk, cnt,
                                                      kernels=kernels,
                                                      nulltag=tag)
                        elif n.kind == "stencil":
                            col = phys.segment_stencil1d(x, pk, cnt,
                                                         n.weights, n.center,
                                                         exact=n.exact,
                                                         kernels=kernels)
                        else:
                            ok = tuple(cols[k] for k in n.order_by)
                            col = phys.segment_rank(pk, ok, cnt, n.kind,
                                                    kernels=kernels)
                    elif n.kind in ("rank", "dense_rank", "row_number"):
                        # global ranking: per-shard-count exscan + tiny
                        # boundary gathers, no row movement (planner enforces
                        # cross-shard tie adjacency for rank/dense_rank).
                        ok = tuple(cols[k] for k in (n.order_by or ()))
                        cap_w = next(iter(cols.values())).shape[0]
                        col = phys.global_rank(ok, cnt, cap_w, n.kind, ax,
                                               method=cfg.exscan_method,
                                               kernels=kernels)
                    elif n.kind == "cumsum":
                        tag = nulltag_for(n.expr, n.children[0].schema)
                        nullm = phys.null_mask(x, tag)
                        if nullm is not None:   # pandas: nulls stay null,
                            x = jnp.where(nullm, jnp.zeros((), x.dtype), x)
                        col = phys.dist_cumsum(x, cnt, ax,
                                               method=cfg.exscan_method,
                                               kernels=kernels)
                        if nullm is not None:   # the running total skips them
                            col = jnp.where(
                                nullm,
                                phys.null_value(col.dtype, tag).astype(col.dtype),
                                col)
                    else:
                        col = phys.stencil1d(x, cnt, n.weights, n.center, ax,
                                             kernels=kernels, exact=n.exact)
                    out = dict(cols)
                    out[n.out] = col
                    res = (out, cnt)

                elif isinstance(op, pp.HashExchange):
                    cols, cnt = env[op.inputs[0]]
                    # shuffle_by_key inlined so the routing hashes also feed
                    # the per-op requirement estimate (max destination load)
                    # without a second hash pass.
                    cap_in = next(iter(cols.values())).shape[0]
                    dest = (phys.hash_keys(cols, op.keys)
                            % np.uint32(Pn)).astype(jnp.int32)
                    valid = phys.valid_mask(cnt, cap_in)
                    hist = jnp.zeros((Pn,), jnp.int32).at[dest].add(
                        valid.astype(jnp.int32))
                    pre = pre_exchange(op, cols, cnt)
                    out, cnt2, ovf = phys.exchange(
                        cols, cnt, dest, axes=axes,
                        bucket_cap=op.bucket, cap_out=op.cap,
                        kernels=kernels, packed=cfg.packed_exchange)
                    flag(op, ovf, jnp.max(hist))
                    out = post_exchange(op, pre, out, cnt2)
                    res = (out, cnt2)

                elif isinstance(op, pp.LocalSort):
                    cols, cnt = env[op.inputs[0]]
                    out, _ = phys.local_sort(cols, cnt, op.keys)
                    if validate:
                        vflags.append(_mono_violation(out[op.keys[0]], cnt))
                    res = (out, cnt)

                elif isinstance(op, pp.MergeJoin):
                    lcols, lcnt = env[op.inputs[0]]
                    rcols, rcnt = env[op.inputs[1]]
                    lon, ron = n.left_on, n.right_on
                    if op.salted:
                        # join on keys+salt: each (probe, build) key match
                        # agrees on exactly one salt (see pp.SaltOp).
                        lon = lon + (phys.SALT_COL,)
                        ron = ron + (phys.SALT_COL,)
                    smap = {c: n.right_out_name(c) for c in rcols
                            if c not in ron}
                    out, cnt2, ovf = phys.merge_join(
                        lcols, lcnt, rcols, rcnt, lon, ron,
                        cap_out=op.cap, r_suffix_map=smap, how=n.how,
                        null_fill=_join_null_fill(n))
                    lf = lcnt.astype(jnp.float32)
                    flag(op, ovf,
                         jnp.maximum(lf * rcnt.astype(jnp.float32), lf))
                    out.pop(phys.SALT_COL, None)    # strip probe-side salt
                    res = (out, cnt2)

                elif isinstance(op, pp.SaltOp):
                    cols, cnt = env[op.inputs[0]]
                    if op.build:
                        out, cnt2, ovf = phys.salt_build(
                            cols, cnt, op.keys, op.hot, op.R,
                            cap_out=op.cap, kernels=kernels)
                        flag(op, ovf,
                             jnp.float32(op.R) * cnt.astype(jnp.float32))
                    else:
                        out, cnt2 = phys.salt_probe(cols, cnt, op.keys,
                                                    op.hot, op.R)
                    res = (out, cnt2)

                elif isinstance(op, pp.AggPrep):
                    cols, cnt = env[op.inputs[0]]
                    env_e = dict(cols)
                    env_e.update(ext)
                    cache = {}
                    key0 = cols[n.key[0]]
                    out = {k: cols[k] for k in n.key}
                    for name, agg in n.aggs.items():
                        arr = (evaluate(agg.expr, env_e, cache)
                               if agg.expr is not None
                               else jnp.zeros_like(key0, dtype=jnp.int32))
                        if arr.ndim == 0:
                            arr = jnp.broadcast_to(arr, key0.shape)
                        out["__v_" + name] = arr
                    res = (out, cnt)

                elif isinstance(op, pp.PartialAgg):
                    cols, cnt = env[op.inputs[0]]
                    tags = _agg_nulltags(n)
                    values = {name: (agg.fn, cols["__v_" + name],
                                     agg.skipna, tags[name])
                              if tags[name] is not None
                              else (agg.fn, cols["__v_" + name])
                              for name, agg in n.aggs.items()}
                    keys = tuple(cols[k] for k in n.key)
                    out, n_seg, ovf = phys.partial_aggregate(
                        keys, cnt, values, cap_out=op.cap, kernels=kernels)
                    flag(op, ovf, _distinct_runs(keys, cnt))
                    res = (_restore_key_names(out, n.key), n_seg)

                elif isinstance(op, pp.SegmentAgg):
                    cols, cnt = env[op.inputs[0]]
                    keys = tuple(cols[k] for k in n.key)
                    tags = _agg_nulltags(n)
                    if op.from_partials:
                        fns = {name: (agg.fn, agg.skipna, tags[name])
                               if tags[name] is not None else agg.fn
                               for name, agg in n.aggs.items()}
                        out, n_seg, ovf = phys.final_aggregate(
                            keys, cnt, fns,
                            cols, cap_out=op.cap, kernels=kernels)
                    else:
                        values = {name: (agg.fn, cols["__v_" + name],
                                         agg.skipna, tags[name])
                                  if tags[name] is not None
                                  else (agg.fn, cols["__v_" + name])
                                  for name, agg in n.aggs.items()}
                        out, n_seg, ovf = phys.segment_aggregate(
                            keys, cnt, values, cap_out=op.cap,
                            kernels=kernels,
                            presorted=(op.nunique_ride,)
                            if op.nunique_ride else ())
                    flag(op, ovf, _distinct_runs(keys, cnt))
                    res = (_restore_key_names(out, n.key), n_seg)

                elif isinstance(op, pp.SampleSort):
                    cols, cnt = env[op.inputs[0]]
                    pre = pre_exchange(op, cols, cnt)
                    out, cnt2, ovf = phys.sample_sort(
                        cols, cnt, n.by, axes=ax, bucket_cap=op.bucket,
                        cap_out=op.cap, ascending=n.ascending,
                        pre_sorted=op.pre_sorted, kernels=kernels,
                        packed=cfg.packed_exchange)
                    flag(op, ovf, cnt)
                    out = post_exchange(op, pre, out, cnt2)
                    if validate:
                        vflags.append(_mono_violation(
                            out[n.by[0]], cnt2, ascending=n.ascending))
                    res = (out, cnt2)

                elif isinstance(op, pp.LimitOp):
                    cols, cnt = env[op.inputs[0]]
                    out, cnt2 = phys.limit(cols, cnt, n.n, ax, cap_out=op.cap)
                    res = (out, cnt2)

                elif isinstance(op, pp.RebalanceOp):
                    cols, cnt = env[op.inputs[0]]
                    pre = pre_exchange(op, cols, cnt)
                    out, cnt2, ovf = phys.rebalance(
                        cols, cnt, axes=axes, bucket_cap=op.bucket,
                        cap_out=op.cap, kernels=kernels,
                        packed=cfg.packed_exchange)
                    flag(op, ovf, cnt)
                    out = post_exchange(op, pre, out, cnt2)
                    res = (out, cnt2)

                elif isinstance(op, pp.ConcatOp):
                    parts = [env[i] for i in op.inputs]
                    out, cnt, ovf = phys.concat(parts, op.cap, kernels=kernels)
                    flag(op, ovf,
                         functools.reduce(
                             jnp.add, [c.astype(jnp.float32)
                                       for _, c in parts]))
                    res = (out, cnt)

                else:
                    raise TypeError(op)

                env[op.op_id] = res

            cols, cnt = env[pplan.root_id]
            if validate:
                for kind, _oid, cname in self.val_flags_meta:
                    if kind != "code_range":
                        continue
                    colv = cols[cname]
                    validr = phys.valid_mask(cnt, colv.shape[0])
                    vflags.append(jnp.any(
                        validr & ((colv < NULL_CODE)
                                  | (colv >= n_codes[cname]))))

            assert len(flags) == len(self.sites), (len(flags), self.sites)
            outd = {"cols": {k: cols[k] for k in root.schema},
                    "count": cnt.reshape(1),
                    "overflow": (jnp.stack(flags) if flags
                                 else jnp.zeros((1,), jnp.bool_)),
                    "ovf_req": (jnp.stack(reqs) if reqs
                                else jnp.zeros((1,), jnp.float32))}
            if validate:
                assert len(vflags) == len(self.val_flags_meta)
                assert len(vpairs) == len(self.val_pairs_meta)
                outd["val_flags"] = (jnp.stack(vflags) if vflags
                                     else jnp.zeros((1,), jnp.bool_))
                outd["val_pairs"] = (
                    jnp.stack([jnp.stack([a, b]) for a, b in vpairs])
                    if vpairs else jnp.zeros((1, 2), jnp.uint32))
            return outd

        # rows are static python ints — closed over, not traced.
        self._per_shard = per_shard
        self._in_specs = in_specs
        self._out_specs = out_specs

    # -- public call -----------------------------------------------------------

    def _prepare(self, scan_arrays=None, scan_nodes=None,
                 abstract: bool = False):
        """Marshal inputs and return the (cached) jitted shard_map callable.

        Host inputs are padded and placed by shard: each device receives
        only its block (``jax.device_put`` with the input's
        ``NamedSharding``), replicated tables go to every device.  With
        ``abstract`` — or for columns given as ``jax.ShapeDtypeStruct`` (an
        abstract table) — host inputs come back as sharded shapes instead,
        so ``fn.lower(...)`` compiles the plan at that size without moving
        data: :meth:`compile`, and the compile rehearsal for a chip that is
        described and not attached.

        The jit is cached per source-row signature: rebuilding the closure on
        every call would otherwise retrace+recompile per execution (measured
        as a 50x CPU slowdown in the benchmark harness).

        ``scan_nodes`` rebinds a scan to ANOTHER ir.Scan's buffers (by this
        plan's scan id, str-keyed) — the session plan cache's sanctioned path
        for re-executing a cached trace over a different same-shape table.
        For persisted device scans the substitute must carry a device layout
        with the same shard count and capacity, so the shard_map signature
        (and hence the compiled executable) is reused byte-identical.
        """
        if self.platform == "tpu" and \
                "interpret" in self.kernels.kernel_modes.values():
            raise ValueError(
                "use_pallas='interpret' emulates the Pallas kernels on the "
                "host and cannot execute on a TPU; use 'compiled' or 'off'")
        mesh, Pn = self.mesh, self.P

        def place(a, spec):
            sharding = NamedSharding(mesh, spec)
            if abstract or isinstance(a, jax.ShapeDtypeStruct):
                return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                            sharding=sharding)
            return jax.device_put(a, sharding)

        inputs = {"scans": {}, "ext": {}, "rows": {}}
        for s in self.scans:
            sub = scan_nodes.get(str(s.id)) if scan_nodes else None
            overridden = scan_arrays is not None and str(s.id) in scan_arrays
            src = scan_arrays[str(s.id)] if overridden else (
                sub.columns if sub is not None else s.columns)
            lay = s.layout
            if s.id in self.dev_scans:
                if overridden:
                    raise ValueError(
                        "cannot override columns of a persisted scan "
                        f"({s.name!r}): its buffers carry a device layout; "
                        "rebuild the input with hf.table(...) instead")
                if sub is not None:
                    slay = sub.layout
                    if (slay is None or not slay.device_valid(Pn)
                            or int(slay.capacity) != int(lay.capacity)):
                        raise ValueError(
                            f"scan rebind for {s.name!r}: substitute must be "
                            f"persisted at P={Pn} with capacity "
                            f"{lay.capacity} (got "
                            f"{None if slay is None else (slay.nshards, slay.capacity)})")
                    missing = [c for c in s.columns if c not in src]
                    if missing:
                        raise ValueError(
                            f"scan rebind for {s.name!r}: substitute lacks "
                            f"columns {missing}")
                    lay = slay
                # persisted device shards: feed the (P*cap,) arrays and the
                # (P,) count vector straight through — no host round-trip,
                # no padding pass.  The jit key is the (static) capacity,
                # negated to stay disjoint from host-scan row counts, so a
                # same-capacity rebind reuses the compiled executable.
                inputs["scans"][str(s.id)] = {c: src[c] for c in s.columns}
                inputs["ext"][_cnt_tag(s.id)] = place(
                    np.asarray(lay.counts, dtype=np.int32), P(self.cfg.axes))
                inputs["rows"][str(s.id)] = -int(lay.capacity) - 1
                continue
            if sub is not None:
                lay = sub.layout
                src = {c: src[c] for c in s.columns}
            if lay is not None and lay.counts is not None and not overridden:
                # shard-count mismatch: gather the valid prefixes on the
                # host and re-enter as a plain block table (layout claims
                # were already dropped at planning time).
                src = lay.gather_host(src)
            rows = _length(next(iter(src.values())))
            cap = self.pplan.final_op(s).cap
            rep = self.dists[s.id] == D.REP
            n_pad = rows if rep else Pn * cap
            specs = self._in_specs["scans"][str(s.id)]
            inputs["scans"][str(s.id)] = {
                c: place(_padded(v, n_pad, abstract), specs[c])
                for c, v in src.items()}
            inputs["rows"][str(s.id)] = rows
        for tag, arr in self.exts.items():
            inputs["ext"][tag] = place(
                _padded(arr, Pn * self._ext_caps[tag], abstract),
                self._in_specs["ext"][tag])

        rows_static = dict(inputs["rows"])
        key = tuple(sorted(rows_static.items()))
        if not hasattr(self, "_jit_cache"):
            self._jit_cache = {}
        if key not in self._jit_cache:
            def wrapped(scan_cols, ext_cols):
                return self._per_shard({"scans": scan_cols, "ext": ext_cols,
                                        "rows": rows_static})

            shard_fn = jax.shard_map(
                wrapped, mesh=mesh,
                in_specs=(self._in_specs["scans"], self._in_specs["ext"]),
                out_specs=self._out_specs, check_vma=False)
            self._jit_cache[key] = jax.jit(shard_fn)
            self.compiles += 1
        return self._jit_cache[key], inputs

    def compile(self, scan_nodes=None):
        """Compile the plan for its inputs' shapes without moving any data;
        the next call with those shapes runs the compiled program.  Returns
        the ``jax.stages.Compiled`` (``as_text()``, ``memory_analysis()``).
        A serving session compiles here, outside its device lock, so that
        compiles of concurrent queries overlap."""
        fn, inputs = self._prepare(scan_nodes=scan_nodes, abstract=True)
        lowered = self._typed(lambda: fn.lower(inputs["scans"], inputs["ext"]))
        return self._typed(lowered.compile)

    def hlo_text(self, optimized: bool = True) -> str:
        """The (optimized) HLO of the whole plan — used by the UDF-identity
        benchmark (paper Fig. 10) and by EXPLAIN-style tooling."""
        if optimized:
            return self.compile().as_text()
        fn, inputs = self._prepare(abstract=True)
        return self._typed(
            lambda: fn.lower(inputs["scans"], inputs["ext"])).as_text()

    def __call__(self, scan_arrays: dict[str, dict[str, np.ndarray]] | None = None,
                 scan_nodes=None):
        """Execute.  scan_arrays overrides source columns by scan id (str);
        scan_nodes rebinds scans to other same-shape tables (plan cache)."""
        fn, inputs = self._prepare(scan_arrays, scan_nodes)
        out = self._typed(lambda: fn(inputs["scans"], inputs["ext"]))
        cap = self.pplan.root_op.cap
        flags = np.asarray(out["overflow"]).reshape(self.P, -1)
        reqs = np.asarray(out["ovf_req"]).reshape(self.P, -1)
        overflow_ops = self._attribute_overflow(flags, reqs)
        failures = self._check_invariants(out, overflow_ops)
        return DTable(columns=out["cols"], counts=out["count"],
                      capacity=cap, nshards=self.P, dist=self.dists[self.root.id],
                      overflow=bool(flags.any()),
                      overflow_ops=overflow_ops,
                      invariant_failures=failures)

    def _typed(self, step):
        """Run a lower/compile/execute ``step``.  The TPU compiler refuses a
        Pallas kernel while the whole program lowers or compiles — after
        the kernel's own call returned inside ``_kernel_wrap`` — so a
        failure here is blamed on the first traced kernel that also fails to
        compile alone and re-raised as a typed KernelBackendError (which
        the retry ladder steps down).  Anything else propagates as is."""
        try:
            return step()
        except err.HiFramesError:
            raise
        except Exception as e:
            blame = self._blame_kernel()
            if blame is None:
                raise
            name, mode, cause = blame
            raise err.KernelBackendError(name, mode, cause) from e

    def _blame_kernel(self):
        """``(name, mode, error)`` of the first traced kernel whose program
        alone fails to lower or compile on this mesh's device; None when
        every one compiles (the failure lies elsewhere)."""
        device = SingleDeviceSharding(self.mesh.devices.flat[0])
        for name, (mode, fn, args) in list(self.traced_kernels.items()):
            leaves, tree = jax.tree.flatten(args)
            slots = [i for i, x in enumerate(leaves)
                     if isinstance(x, jax.ShapeDtypeStruct)]

            def alone(*arrays, leaves=leaves, tree=tree, slots=slots, fn=fn):
                full = list(leaves)
                for i, a in zip(slots, arrays):
                    full[i] = a
                a, k = jax.tree.unflatten(tree, full)
                return fn(*a, **k)

            shapes = [jax.ShapeDtypeStruct(leaves[i].shape, leaves[i].dtype,
                                           sharding=device) for i in slots]
            try:
                jax.jit(alone).lower(*shapes).compile()
            except Exception as e:   # any refusal of the kernel's program
                return name, mode, e
        return None

    def _attribute_overflow(self, flags: np.ndarray,
                            reqs: np.ndarray) -> dict[int, dict]:
        """Reduce per-shard (flag, requirement) vectors to the per-op
        attribution record the retry policy escalates from."""
        overflow_ops: dict[int, dict] = {}
        for i, (op_id, kind, rule, strategy) in enumerate(self.sites):
            if not flags[:, i].any():
                continue
            vals = reqs[:, i].astype(np.float64)
            cap_req = {"max": float(vals.max()),
                       "sum": float(vals.sum()),
                       "block": float(np.ceil(vals.sum() / max(self.P, 1)))
                       }[rule]
            op = self.pplan.ops[op_id]
            overflow_ops[op_id] = {
                "kind": kind, "op": type(op).__name__, "strategy": strategy,
                "cap": int(op.cap), "bucket": int(op.bucket),
                "cap_req": int(np.ceil(cap_req)),
                "bucket_req": int(np.ceil(float(vals.max()))),
                "req_shards": vals,     # per-shard requirement estimates
            }
        return overflow_ops

    def _check_invariants(self, out, overflow_ops) -> tuple:
        """Host-side reduction of the ExecConfig.validate check outputs."""
        fails: list[err.InvariantFailure] = []
        if self.val_flags_meta:
            vf = np.asarray(out["val_flags"]).reshape(self.P, -1)
            for i, (kind, opid, detail) in enumerate(self.val_flags_meta):
                if vf[:, i].any():
                    fails.append(err.InvariantFailure(kind, opid, detail))
        if self.val_pairs_meta:
            vp = np.asarray(out["val_pairs"]).reshape(
                self.P, len(self.val_pairs_meta), 2).astype(np.uint64)
            for i, (kind, opid, detail) in enumerate(self.val_pairs_meta):
                if opid in overflow_ops:
                    continue    # clamped rows legitimately break conservation
                a, b = int(vp[:, i, 0].sum()), int(vp[:, i, 1].sum())
                if kind == "checksum":
                    a &= 0xFFFFFFFF
                    b &= 0xFFFFFFFF
                if a != b:
                    fails.append(err.InvariantFailure(
                        kind, opid, f"{detail}: in={a} out={b}"))
        return tuple(fails)


def _capacity_sites(pplan: pp.PhysicalPlan) -> list[tuple[int, str, str, str]]:
    """The static capacity-site table for per-op overflow attribution: one
    entry per overflow-flagged buffer, in per-shard flag order —
    ``(op_id, kind, reduce-rule, escalation-strategy)``.

    The reduce rule maps per-shard requirement estimates to a global cap
    requirement: "max" for per-shard buffers, "sum" for exchange receive
    totals, "block" for evenly re-split rows.  Strategy "abs" sites report a
    true upper bound, so ONE retry at that size heals; "double" sites
    (join/salt expansion) only know a worst-case product and escalate
    geometrically instead.
    """
    sites = []
    for op in pplan.ops:
        rep = op.dist == D.REP
        if isinstance(op, pp.Compact):
            sites.append((op.op_id, "compact", "max", "abs"))
        elif isinstance(op, pp.HashExchange):
            sites.append((op.op_id, "exchange",
                          "max" if rep else "sum", "abs"))
        elif isinstance(op, pp.MergeJoin):
            sites.append((op.op_id, "join", "max", "double"))
        elif isinstance(op, pp.SaltOp):
            if op.build:
                sites.append((op.op_id, "salt", "max", "double"))
        elif isinstance(op, pp.PartialAgg):
            sites.append((op.op_id, "partial_agg", "max", "abs"))
        elif isinstance(op, pp.SegmentAgg):
            sites.append((op.op_id, "segment_agg", "max", "abs"))
        elif isinstance(op, pp.SampleSort):
            sites.append((op.op_id, "sort", "max" if rep else "sum", "abs"))
        elif isinstance(op, pp.RebalanceOp):
            sites.append((op.op_id, "rebalance",
                          "max" if rep else "block", "abs"))
        elif isinstance(op, pp.ConcatOp):
            sites.append((op.op_id, "concat", "max", "abs"))
    return sites


def _kernel_wrap(fault, traced: dict):
    """Registry ``wrap`` hook: type kernel-backend failures raised while
    tracing as KernelBackendError, honor FaultPlan.fail_kernel injection, and
    record each Pallas kernel's abstract call in ``traced`` (name -> (mode,
    fn, args)) so that a compile failure of the whole program can be blamed
    on the kernel that causes it (``Lowered._typed``)."""
    def wrap(name, mode, fn):
        injected = fault is not None and fault.kernel_fails(name, mode)
        if mode == "off" and not injected:
            return fn
        def call(*a, **k):
            if injected:
                raise err.KernelBackendError(
                    name, mode, "injected fault (FaultPlan.fail_kernel)")
            if mode != "off":
                traced.setdefault(name, (mode, fn, _abstract((a, k))))
            try:
                return fn(*a, **k)
            except err.HiFramesError:
                raise
            except Exception as e:
                raise err.KernelBackendError(name, mode, e) from e
        return call
    return wrap


def _abstract(tree):
    """Arrays (and tracers) -> ShapeDtypeStruct; static leaves unchanged."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        if isinstance(x, (jax.Array, np.ndarray)) else x, tree)


def _length(col) -> int:
    return col.shape[0] if hasattr(col, "shape") else len(col)


def _padded(col, n: int, abstract: bool = False):
    """A host column zero-padded to ``n`` rows or, for an abstract column
    or with ``abstract``, the shape it would have on the device."""
    if abstract or isinstance(col, jax.ShapeDtypeStruct):
        a = col if hasattr(col, "dtype") else np.asarray(col)
        return jax.ShapeDtypeStruct((n,) + tuple(a.shape[1:]),
                                    jax.dtypes.canonicalize_dtype(a.dtype))
    return pad_to(np.asarray(col), n)


def _checksum_u32(cols: dict, cnt) -> jax.Array:
    """Order-invariant uint32 payload checksum of the valid prefix: the
    word-packed columns (a pure bitcast, so float payload bits survive
    exactly), masked to valid rows, summed mod 2**32.  Exchanges permute
    rows across shards, so the host-side sum over shards is conserved."""
    cap = next(iter(cols.values())).shape[0]
    valid = phys.valid_mask(cnt, cap)
    words, _ = phys.pack_columns({k: cols[k] for k in sorted(cols)})
    w = jnp.where(valid[:, None], words, jnp.zeros((), words.dtype))
    return jnp.sum(w, dtype=jnp.uint32)


def _mono_violation(col, cnt, ascending: bool = True) -> jax.Array:
    """True iff an adjacent pair inside the valid prefix is out of order.
    NaN-lenient: comparisons with NaN are False, so null floats never flag."""
    cap = col.shape[0]
    if cap < 2:
        return jnp.zeros((), jnp.bool_)
    pair_valid = phys.valid_mask(cnt, cap)[1:]   # pair (i-1, i) needs i < cnt
    a, b = col[:-1], col[1:]
    bad = (b < a) if ascending else (b > a)
    return jnp.any(bad & pair_valid)


def _distinct_runs(keys: tuple, cnt) -> jax.Array:
    """Exact count of key runs in the valid prefix of sorted key columns —
    the true PartialAgg/SegmentAgg output requirement (NaN keys each count
    as their own run: a safe upper bound)."""
    cap = keys[0].shape[0]
    if cap < 2:
        return (cnt > 0).astype(jnp.int32)
    valid = phys.valid_mask(cnt, cap)
    neq = functools.reduce(
        jnp.logical_or, [k[1:] != k[:-1] for k in keys])
    return (jnp.sum((neq & valid[1:]).astype(jnp.int32))
            + (cnt > 0).astype(jnp.int32))


def _agg_nulltags(n: ir.Aggregate) -> dict[str, str | None]:
    """Per-output null tag for an Aggregate's value expressions, decided
    from the child's LOGICAL schema (None = exact pre-null code path)."""
    sch = n.children[0].schema
    return {name: nulltag_for(agg.expr, sch) for name, agg in n.aggs.items()}


def _join_null_fill(n: ir.Join) -> dict[str, Any] | None:
    """Unmatched-row fill values for a left join's right columns, from the
    right child's logical schema: null code for categories, NaN for floats
    (matching the nullable output schema ir.Join declares); int columns
    keep the legacy zero-fill + ``_matched`` flag."""
    if n.how != "left":
        return None
    fill: dict[str, Any] = {}
    for c, dt in n.children[1].schema.items():
        if c in n.right_on:
            continue
        if is_category(dt):
            fill[c] = NULL_CODE
        elif np.issubdtype(physical_dtype(dt), np.floating):
            fill[c] = np.nan
    return fill or None


def _restore_key_names(out: dict, key: tuple[str, ...]) -> dict:
    """Segment-aggregation outputs name key columns ``__key<i>__`` in key
    order; restore the real names, keeping them FIRST (schema order)."""
    renamed = {k: out.pop(f"__key{i}__") for i, k in enumerate(key)}
    renamed.update(out)
    return renamed


def _node_exprs(n: ir.Node):
    if isinstance(n, ir.Filter):
        yield n.pred
    elif isinstance(n, ir.Project):
        yield from n.cols.values()
    elif isinstance(n, ir.Aggregate):
        for a in n.aggs.values():
            if a.expr is not None:
                yield a.expr
    elif isinstance(n, ir.Window):
        if n.expr is not None:
            yield n.expr


def _walk_expr(e):
    yield e
    for c in e.children:
        yield from _walk_expr(c)


def lower(root: ir.Node, cfg: ExecConfig | None = None,
          keep: set[str] | None = None, collect_block: bool = False,
          force_rep: set[int] = frozenset()) -> tuple[Lowered, dict]:
    """optimize -> infer distributions -> insert rebalance -> plan physical
    ops (exchange/sort elision) -> plan capacities -> build executor.

    Kernel backends (``cfg.use_pallas``) play no part here: the physical
    plan is backend-oblivious; ``Lowered`` resolves the registry when it
    builds the per-shard program.
    """
    from . import optimizer as opt

    cfg = cfg or ExecConfig()
    stats: dict = {}
    if cfg.optimize_plan:
        root, stats = opt.optimize(root, keep)
    info = D.infer(root, force_rep=force_rep,
                   broadcast_join=cfg.broadcast_join)
    root = D.insert_rebalance(root, info, collect_block=collect_block)
    mesh = cfg.get_mesh()
    Pn = int(np.prod([mesh.shape[a] for a in cfg.axes]))
    order = ir.topo_order(root)
    source_rows = {n.id: pp.scan_rows(n)
                   for n in order if isinstance(n, ir.Scan)}
    sctx = None
    events: list = []
    if cfg.adaptive_stats:
        from . import stats as st
        try:
            sctx = st.analyze(root, cfg)
        except Exception as e:   # degradation ladder: adaptive -> static
            events.append({"kind": "degrade_stats",
                           "detail": f"adaptive -> static planning: {e}"})
            sctx = None
    pplan = pp.plan_physical(root, info.dists, cfg, stats=sctx)
    pp.plan_capacities(pplan, Pn, cfg, source_rows)
    lowered = Lowered(root, cfg, info.dists, pplan)
    lowered.events.extend(events)
    return lowered, stats
