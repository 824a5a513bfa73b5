"""Fixtures of the benchmark's CPU tests: a copy of the benchmark's files in
a temporary directory, with each configuration cut to the small scale its
tables' files declare (``TINY``), so a test can run the harness
(everything after the look for a chip) and add files beside the committed
ones."""
import json
import os
import shutil

import pytest

from chipbench import load_module

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
# the tiny cut of the store_sales x item configurations
TINY = {"store_sales_rows": 6000, "items": 120, "customers": 300}


def tiny_scale(root: str, tables) -> dict:
    """The union of the ``TINY`` scales that the files of ``tables`` declare
    under ``root``; two files that give one key different sizes are an
    error."""
    scale: dict = {}
    for name in tables:
        for k, v in load_module(root, "tables", name).TINY.items():
            if scale.setdefault(k, v) != v:
                raise ValueError(f"tables {list(tables)} disagree on {k!r}")
    return scale


def copy_bench(dst: str, tiny: bool = True) -> str:
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(dst, "chipbench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    if tiny:
        cdir = os.path.join(dst, "chipbench", "configs")
        for f in os.listdir(cdir):
            p = os.path.join(cdir, f)
            with open(p) as fh:
                cfg = json.load(fh)
            cfg["scale"] = tiny_scale(dst, cfg["tables"])
            with open(p, "w") as fh:
                json.dump(cfg, fh)
    return dst


@pytest.fixture
def tiny_root(tmp_path):
    return copy_bench(str(tmp_path))
