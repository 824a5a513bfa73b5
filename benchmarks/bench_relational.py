"""Paper Fig. 8a — basic relational operations: filter / join / aggregate.

Baselines (the Pandas/Julia roles are played by eager NumPy — sequential,
no compilation; Spark cannot run here):
  numpy-eager     sequential host baseline
  hiframes        compiled single-jit plan (this paper)
  hiframes+kern   same, hot loops through the Pallas kernels (interpret on CPU)

The paper's sizes (2B/0.5M/256M rows) are scaled to CPU-feasible defaults;
pass --scale to grow them.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

from repro import hiframes as hf
from repro.data import synth

from .common import report, timeit


def bench_filter(n):
    t = synth.relational_tables(n, n_keys=1000, seed=0)

    def np_eager():
        m = t["x"] < 0.5
        return {k: v[m] for k, v in t.items()}
    us_np = timeit(np_eager)

    df = hf.table(t)
    plan = df[df["x"] < 0.5].lower()
    us_hf = timeit(plan)
    report(f"fig8a_filter_numpy_n{n}", us_np, "")
    report(f"fig8a_filter_hiframes_n{n}", us_hf,
           f"speedup={us_np/us_hf:.2f}x")


def bench_join(n_left, n_right):
    rng = np.random.default_rng(1)
    left = {"id": rng.integers(0, n_right, n_left).astype(np.int32),
            "x": rng.normal(size=n_left).astype(np.float32)}
    right = {"cid": np.arange(n_right, dtype=np.int32),
             "w": rng.normal(size=n_right).astype(np.float32)}

    def np_eager():
        order = np.argsort(right["cid"])
        pos = np.searchsorted(right["cid"], left["id"], sorter=order)
        return right["w"][order[pos]]
    us_np = timeit(np_eager)

    plan = hf.join(hf.table(left, "l"), hf.table(right, "r"),
                   on=("id", "cid")).lower()
    us_hf = timeit(plan)
    report(f"fig8a_join_numpy_n{n_left}", us_np, "")
    report(f"fig8a_join_hiframes_n{n_left}", us_hf,
           f"speedup={us_np/us_hf:.2f}x")


def bench_aggregate(n):
    t = synth.relational_tables(n, n_keys=4096, seed=2)

    def np_eager():
        order = np.argsort(t["id"], kind="stable")
        sid = t["id"][order]
        sx = t["x"][order]
        bounds = np.flatnonzero(np.diff(sid)) + 1
        return np.add.reduceat(sx, np.concatenate([[0], bounds]))
    us_np = timeit(np_eager)

    df = hf.table(t)
    plan = hf.aggregate(df, "id", s=hf.sum_(df["x"]),
                        m=hf.mean(df["y"])).lower()
    us_hf = timeit(plan)
    report(f"fig8a_aggregate_numpy_n{n}", us_np, "")
    report(f"fig8a_aggregate_hiframes_n{n}", us_hf,
           f"speedup={us_np/us_hf:.2f}x")


def bench_aggregate_multikey(n):
    """Composite-key group-by: shuffles on the combined hash of two key
    columns and segment-aggregates over lexicographic runs — tracks the
    multi-key shuffle path introduced with composite-key support."""
    rng = np.random.default_rng(3)
    t = {"k1": rng.integers(0, 64, n).astype(np.int32),
         "k2": rng.integers(0, 64, n).astype(np.int32),
         "x": rng.normal(size=n).astype(np.float32)}

    def np_eager():
        packed = t["k1"].astype(np.int64) * 64 + t["k2"]
        order = np.argsort(packed, kind="stable")
        sp = packed[order]
        sx = t["x"][order]
        bounds = np.flatnonzero(np.diff(sp)) + 1
        return np.add.reduceat(sx, np.concatenate([[0], bounds]))
    us_np = timeit(np_eager)

    df = hf.table(t)
    plan = hf.aggregate(df, by=("k1", "k2"), s=hf.sum_(df["x"]),
                        c=hf.count()).lower()
    us_hf = timeit(plan)
    report(f"multikey_aggregate_numpy_n{n}", us_np, "")
    report(f"multikey_aggregate_hiframes_n{n}", us_hf,
           f"speedup={us_np/us_hf:.2f}x")


def bench_groupby_partialagg(n):
    """Map-side partial aggregation A/B (paper Fig. 10 axis: shuffle volume
    dominates group-by cost).  Low-cardinality keys are the favorable case:
    the partial stage collapses each shard's rows to <= n_keys partial rows
    before the exchange.  The derived field records the P=8 collective/byte
    census so the bench JSON captures the wire-volume delta, not just time."""
    n_keys = 64
    rng = np.random.default_rng(7)
    t = {"k": rng.integers(0, n_keys, n).astype(np.int32),
         "x": rng.normal(size=n).astype(np.float32)}
    df = hf.table(t)
    frame = hf.aggregate(df, "k", s=hf.sum_(df["x"]), c=hf.count(),
                         m=hf.mean(df["x"]))
    for tag, cfg in (("on", hf.ExecConfig(agg_group_cap=2 * n_keys)),
                     ("off", hf.ExecConfig(partial_agg=False))):
        census = frame.physical_plan(cfg).shuffle_census(P=8)
        us = timeit(frame.lower(cfg))
        report(f"fig10_groupby_partialagg_{tag}_n{n}", us,
               f"collectives={census['all_to_all']};"
               f"payload_bytes={census['payload_bytes']};rows={n}")


# Fig. 13 (repo extension) — zipf-skew join, salted vs stats-blind planning.
# Runs on a FIXED 8-device sub-mesh of this process's devices so the skew
# actually lands on shards regardless of how many the host has; both arms
# share data, compile cache state and machine noise.  The baseline gets
# shuffle_slack doubled iff default slack overflows the hot bucket (the
# steady state the overflow-retry loop reaches on this distribution); the
# salted arm runs adaptive defaults.
SKEW_DEVICES = 8


def bench_skew_join(n):
    if jax.device_count() < SKEW_DEVICES:
        print(f"fig13_skew_join: skipped (<{SKEW_DEVICES} devices)")
        return
    mesh = Mesh(np.array(jax.devices()[:SKEW_DEVICES]), ("data",))
    m = max(64, n // 50)
    rng = np.random.default_rng(13)
    k = rng.integers(0, m, n).astype(np.int32)
    k[: int(0.30 * n)] = 3          # one zipf-hot key: ~30% of all probe rows
    rng.shuffle(k)
    probe = {"k": k, "v": rng.normal(size=n).astype(np.float32)}
    dim = {"k": np.arange(m, dtype=np.int32),
           "w": rng.normal(size=m).astype(np.float32)}
    j = hf.table(probe, "probe").merge(hf.table(dim, "dim"), on="k")

    base_cfg = hf.ExecConfig(mesh=mesh, safe_capacities=False)
    if j.lower(base_cfg)().overflow:
        base_cfg = hf.ExecConfig(mesh=mesh, safe_capacities=False,
                                 shuffle_slack=4.0)
    rows = {}
    for tag, cfg in (("baseline", base_cfg),
                     ("salted", hf.ExecConfig(mesh=mesh, adaptive_stats=True,
                                              safe_capacities=False))):
        plan = j.lower(cfg)
        t = plan()                  # warmup/compile
        if t.overflow:
            raise RuntimeError(f"fig13 {tag} arm overflowed its buffers")
        c = np.asarray(t.counts, dtype=np.float64)
        us = timeit(lambda: np.asarray(plan().counts), warmup=0, repeat=5)
        rows[tag] = (us, c.max() / c.mean(), int(c.max()), cfg.shuffle_slack)
    us_b, r_b, mx_b, slack_b = rows["baseline"]
    us_s, r_s, mx_s, _ = rows["salted"]
    report(f"fig13_skew_join_baseline_n{n}", us_b,
           f"P={SKEW_DEVICES};occ_max_over_mean={r_b:.2f};max_shard={mx_b};"
           f"slack={slack_b:g}")
    report(f"fig13_skew_join_salted_n{n}", us_s,
           f"P={SKEW_DEVICES};occ_max_over_mean={r_s:.2f};max_shard={mx_s};"
           f"speedup={us_b/us_s:.2f}x")


def run(scale: float = 1.0):
    bench_filter(int(2_000_000 * scale))
    bench_join(int(500_000 * scale), int(50_000 * scale))
    bench_aggregate(int(1_000_000 * scale))
    bench_groupby_partialagg(int(1_000_000 * scale))
    bench_skew_join(int(400_000 * scale))


def run_multikey(scale: float = 1.0):
    """Composite-key suite (its own benchmarks/run.py entry, "multikey")."""
    bench_aggregate_multikey(int(1_000_000 * scale))
