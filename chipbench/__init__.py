"""Chip benchmark of the HiFrames serving path (``python3 chipbench/run.py``).

Everything that belongs to one configuration, table, traffic mix, query,
answer limit or metric sits in a file of its own, found by the name that
``BENCHMARK.json`` (or a configuration's ``tables``) gives it:

    chipbench/configs/<config>.json     deployment: tables, scale, layout
    chipbench/tables/<table>.py         make(scale, spec, seed) -> columns,
                                        SEED_OFFSET, TINY: read by data.py
    chipbench/traffic/<mix>.json        query, loop, rate: read by loops.py
    chipbench/queries/<query>.py        the query, its numpy reference and
                                        optionally its control(host)
    chipbench/limits/<config>.<query>.json   limit of each number compared
    chipbench/metrics/<metric>.py       read(run) -> value or None; a
                                        metric ``<q>.<cells>`` without a file
                                        of its own is read by ``<q>.py``
"""
from __future__ import annotations

import importlib.util
import json
import os


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(root: str, kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` under ``root``; a metric split by the
    end-to-end metric it moves (``exec_ms.batch``, ``exec_ms.serve``) falls
    back to one reader for the quantity (``exec_ms.py``)."""
    d = os.path.join(root, "chipbench", kind)
    path = os.path.join(d, f"{name}.py")
    if kind == "metrics" and not os.path.exists(path):
        path = os.path.join(d, f"{name.split('.')[0]}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} file for {name!r} in {d}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
