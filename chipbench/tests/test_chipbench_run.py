"""The harness on the CPU: it refuses to measure without a TPU, finds new
files by name, and its comparison turns ``correct`` false when the timed
path is broken underneath it."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from chipbench import check, control
from chipbench import run as bench

from .conftest import REPO, TINY, copy_bench

CMD = ["chipbench/run.py", "--workload", "q26-batch", "--seed", "7",
       "--seconds", "1", "--trace", "0"]


def cpu_env(**extra):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


def test_exits_nonzero_without_a_tpu():
    p = subprocess.run([sys.executable, *CMD], cwd=REPO, env=cpu_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_exits_nonzero_beside_its_own_files_alone(tmp_path):
    root = copy_bench(str(tmp_path), scale=None)        # no src/ beside it
    p = subprocess.run([sys.executable, *CMD], cwd=root,
                       env=cpu_env(PYTHONPATH=""), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_help_lists_cells_and_metrics_on_disk():
    p = subprocess.run([sys.executable, "chipbench/run.py", "--help"],
                       cwd=REPO, env=cpu_env(), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0
    spec = read_json(os.path.join(REPO, "BENCHMARK.json"))
    for entry in spec["workloads"] + spec["end_to_end"] + spec["per_layer"]:
        assert entry["name"] in p.stdout


def read_json(path):
    with open(path) as f:
        return json.load(f)


def write_json(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def add_files(root):
    """A configuration, a traffic mix, a limit and a metric, as new files and
    new BENCHMARK.json entries; no committed file is edited."""
    b = os.path.join(root, "chipbench")
    cfg = read_json(os.path.join(b, "configs", "tpcxbb-sf1.json"))
    cfg.update(name="tiny-new", scale=dict(TINY, store_sales_rows=4000))
    write_json(os.path.join(b, "configs", "tiny-new.json"), cfg)
    write_json(os.path.join(b, "traffic", "per_item-closed.json"),
               {"query": "per_item", "loop": "closed", "warmup": 1})
    write_json(os.path.join(b, "limits", "tiny-new.per_item.json"),
               {"wrong_rows": 0, "paid_rel_err": 1e-4})
    with open(os.path.join(b, "metrics", "answers_n.py"), "w") as f:
        f.write("def read(run):\n    return float(len(run.answered))\n")
    spec_p = os.path.join(root, "BENCHMARK.json")
    spec = read_json(spec_p)
    spec["configs"].append({"name": "tiny-new", "source": "test",
                            "file": "chipbench/configs/tiny-new.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "new-cell", "config": "tiny-new",
                              "traffic": "per_item-closed", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "answers_n", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "setup_s",
                              "workloads": ["new-cell"]})
    write_json(spec_p, spec)


def committed_files(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "chipbench")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = fh.read()
    return out


def test_new_files_are_found_by_name(tiny_root):
    before = committed_files(tiny_root)
    add_files(tiny_root)
    after = committed_files(tiny_root)
    assert all(after[k] == v for k, v in before.items())
    assert "new-cell" in bench.listing(tiny_root)
    assert "answers_n" in bench.listing(tiny_root)
    cell = bench.Cell(tiny_root, "new-cell")
    out = bench.measure(cell, 11, 0.5, False, jax.devices())
    assert out["correct"] and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s"}
    # the per-layer reader is a plain function of the run
    run = bench.Run(cell, 0.5)
    assert cell.metrics["per_layer"][0]["name"] == "answers_n"
    assert bench.load_module(tiny_root, "metrics", "answers_n").read(run) == 0
    # a metric split by the cells it serves is read by its quantity's file
    assert bench.load_module(tiny_root, "metrics",
                             "exec_ms.batch").__file__.endswith("exec_ms.py")


@pytest.mark.parametrize("workload", ["q26-batch", "per_item-serve"])
def test_sound_run_is_correct(tiny_root, workload):
    out = bench.measure(bench.Cell(tiny_root, workload), 2**31 + 3, 1.0,
                        False, jax.devices())
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    spec = read_json(os.path.join(tiny_root, "BENCHMARK.json"))
    names = {m["name"] for m in spec["end_to_end"]
             if workload in m.get("workloads", [workload])}
    # the CPU reports no memory statistics, so no peak
    assert set(out["metrics"]) == names - {"peak_hbm_gib"}


def alter_answer(monkeypatch):
    """An answer altered where it is produced: one more in the first row of
    every exact column of the program's result."""
    from repro.core import lower
    call = lower.Lowered.__call__

    def altered(self, *a, **k):
        t = call(self, *a, **k)
        for c in t.columns:
            if np.issubdtype(t.columns[c].dtype, np.integer) and \
                    not c.endswith("_sk"):
                t.columns[c] = t.columns[c].at[0].add(1)
            elif np.issubdtype(t.columns[c].dtype, np.floating):
                t.columns[c] = t.columns[c].at[0].multiply(1.5)
        return t
    monkeypatch.setattr(lower.Lowered, "__call__", altered)


def drop_half(monkeypatch):
    """Half of every shard's rows left out of each operator."""
    from repro.core import physical
    valid_mask = physical.valid_mask
    monkeypatch.setattr(physical, "valid_mask",
                        lambda count, n: valid_mask(count // 2, n))


@pytest.mark.parametrize("workload", ["q26-batch", "per_item-serve"])
@pytest.mark.parametrize("fault", [alter_answer, drop_half])
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, workload,
                                          fault):
    fault(monkeypatch)
    out = bench.measure(bench.Cell(tiny_root, workload), 5, 0.5, False,
                        jax.devices())
    assert out["correct"] is False
    assert out["failed"] > 0


FOUR_CHIPS = """
import json, sys
sys.path.insert(0, {repo!r})
import jax
from chipbench import run as bench
if {fault}:
    from repro.core import physical
    def local(cols, count, dest, *, axes, bucket_cap, cap_out, kernels=None,
              packed=True):
        keep = physical.valid_mask(count, dest.shape[0])
        return physical.compact(cols, keep, cap_out, kernels=kernels)
    physical.exchange = local
out = bench.measure(bench.Cell({root!r}, "q26-batch-4chip"), 9, 0.5, False,
                    jax.devices())
print(json.dumps(out["checks"]))
print(json.dumps(out["correct"]))
"""


def add_four_chip_cell(root):
    """``q26-batch-4chip`` over ``tpcxbb-sf1-4chip``, whose files are in
    ``chipbench/``, as BENCHMARK.json entries."""
    spec_p = os.path.join(root, "BENCHMARK.json")
    spec = read_json(spec_p)
    spec["configs"].append({"name": "tpcxbb-sf1-4chip", "source": "test",
                            "file": "chipbench/configs/tpcxbb-sf1-4chip.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "q26-batch-4chip",
                              "config": "tpcxbb-sf1-4chip",
                              "traffic": "q26-closed", "chips": 4,
                              "why": "test"})
    write_json(spec_p, spec)


@pytest.mark.parametrize("fault", [False, True], ids=["sound", "no_exchange"])
def test_exchange_left_out_is_not_correct(tiny_root, fault):
    """Four virtual CPU devices: the sound run is correct, the one whose
    exchange keeps every row on its own shard is not."""
    add_four_chip_cell(tiny_root)
    script = FOUR_CHIPS.format(repo=REPO, root=tiny_root, fault=fault)
    env = cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4",
                  PYTHONPATH=os.path.join(REPO, "src"))
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert json.loads(p.stdout.strip().splitlines()[-1]) is (not fault), \
        p.stdout


def test_controls_are_refused_at_a_small_size(tiny_root):
    """The bfloat16 sums and the stale snapshot break the committed limits;
    a float32 sum of the same rows stays inside them."""
    scale = {"store_sales_rows": 60000, "items": 120, "customers": 300}
    for workload in ("per_item-serve", "q26-batch"):
        cell = bench.Cell(REPO, workload)
        for seed in (1, 2, 3):
            numbers = control.readings(cell, seed, scale)
            ok, _ = check.judge(numbers, cell.limits, 0)
            assert not ok, (workload, seed, numbers)
