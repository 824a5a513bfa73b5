"""The paper's own workload configurations (TPCx-BB-derived).

Scale factors follow the paper's evaluation section: the micro-benchmarks
use uniform tables (filter 2B rows / join 0.5M / aggregate 256M at paper
scale), Q05/Q25/Q26 use BigBench-like tables; Q05 adds the Zipf skew that
drives the paper's skew/OOM discussion.  ``scaled(sf)`` maps a TPCx-BB-ish
scale factor to row counts; the benchmark harness defaults to CPU-feasible
fractions of these.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class TpcxConfig:
    name: str
    store_sales_rows: int
    items: int
    customers: int
    clickstream_rows: int
    skew: float = 0.0            # zipf exponent-1 for wcs_item_sk / Q05

    def scaled(self, f: float) -> "TpcxConfig":
        return TpcxConfig(
            self.name,
            int(self.store_sales_rows * f), max(int(self.items * f), 16),
            max(int(self.customers * f), 16),
            int(self.clickstream_rows * f), self.skew)


# paper-scale reference points (Fig. 11 / Fig. 12 use SF 100..1000; Q26 at
# SF1000 has a 1.2B-row fact table)
SF100 = TpcxConfig("sf100", 120_000_000, 178_000, 990_000, 390_000_000)
SF1000 = TpcxConfig("sf1000", 1_200_000_000, 500_000, 5_000_000,
                    3_900_000_000)
Q05_SKEWED = TpcxConfig("q05skew", 120_000_000, 178_000, 990_000,
                        390_000_000, skew=1.1)

# The fractions of SF100 that chip_smoke.py serves on a v5e mesh of 1 or 4
# chips.  One chip holds all of SF100 (7.5 GiB peak), but Q26 runs for
# 220 s there and the smoke runs it three times, next to about 11 minutes
# of compiles: half keeps the smoke inside its 20-minute limit.  On four
# chips a registered table's shards each hold capacity for every row under
# safe_capacities, so the exchange buffers grow with P x rows: at half of
# SF100 the plans asked for more than a chip's 16 GiB; at a tenth (SF10)
# the leaderboard, the largest, needs about 7.5 GiB per chip (PERF.md).
V5E_SMOKE_FRACTION = {1: 0.5, 4: 0.1}

# CPU-feasible default used by benchmarks/bench_tpcx.py
LOCAL = TpcxConfig("local", 400_000, 20_000, 50_000, 400_000, skew=1.1)

MICRO = {
    # paper Fig. 8a row counts (scaled by the harness)
    "filter_rows": 2_000_000_000,
    "join_rows": 500_000,
    "aggregate_rows": 256_000_000,
    # Fig. 8b series length
    "analytics_rows": 256_000_000,
}
