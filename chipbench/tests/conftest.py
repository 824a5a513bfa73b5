"""Fixtures of the benchmark's CPU tests: a copy of the benchmark's files in
a temporary directory, with its configurations cut to a few thousand rows,
so a test can run the harness (everything after the look for a chip) and
add files beside the committed ones."""
import json
import os
import shutil

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
TINY = {"store_sales_rows": 6000, "items": 120, "customers": 300}


def copy_bench(dst: str, scale: dict | None = TINY) -> str:
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(dst, "chipbench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    if scale is not None:
        cdir = os.path.join(dst, "chipbench", "configs")
        for f in os.listdir(cdir):
            p = os.path.join(cdir, f)
            with open(p) as fh:
                cfg = json.load(fh)
            cfg["scale"] = dict(scale)
            with open(p, "w") as fh:
                json.dump(cfg, fh)
    return dst


@pytest.fixture
def tiny_root(tmp_path):
    return copy_bench(str(tmp_path))
