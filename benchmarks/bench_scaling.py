"""Paper Fig. 12 — strong scaling of Q26 over 1, 2, 4 and 8 devices.

Each point runs the same plan in this process on a sub-mesh of the first d
devices (the stand-in for nodes); device counts the host lacks are
skipped.  The paper's point: HiFrames keeps scaling where Spark's master
bottleneck inverts it; our analogue is that the compiled SPMD plan has no
coordinator — scaling is bounded only by the collectives.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

from repro import hiframes as hf
from repro.data import synth

from .common import report, timeit


def run(scale: float = 1.0, devices=(1, 2, 4, 8)):
    rows = int(200_000 * scale)
    ss = synth.store_sales(rows, 5000, 20000, seed=10)
    it = synth.item(5000, seed=11)
    store_sales, item = hf.table(ss, "ss"), hf.table(it, "it")
    sale_items = hf.join(store_sales, item, on=("ss_item_sk", "i_item_sk"))
    c_i = hf.aggregate(sale_items, "ss_customer_sk",
                       c_i_count=hf.count(),
                       id1=hf.sum_(sale_items["i_class_id"] == 1))
    q26 = c_i[c_i["c_i_count"] > 2]
    base = None
    for d in devices:
        if d > jax.device_count():
            print(f"fig12_q26_scaling_p{d}: skipped (<{d} devices)")
            continue
        mesh = Mesh(np.array(jax.devices()[:d]), ("data",))
        plan = q26.lower(hf.ExecConfig(mesh=mesh))
        us = timeit(lambda: np.asarray(plan().counts), warmup=1, repeat=3)
        if base is None:
            base = us
        report(f"fig12_q26_scaling_p{d}", us,
               f"speedup_vs_p1={base/us:.2f}x")
