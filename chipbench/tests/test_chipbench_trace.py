"""The reduction from a profiler trace to device numbers: on hand-made
events, and on a small trace recorded on a TPU v5e chip."""
import os

import pytest

from chipbench import trace

HERE = os.path.dirname(os.path.abspath(__file__))


def hlo(name, opcode="fusion"):
    """A device event named as a TPU trace names it: its HLO instruction."""
    return f"%{name} = s32[8]{{0:T(1024)}} {opcode}(s32[8]{{0}} %p), x=1"


def planes_of(dev_events, host_events, devices=("0",)):
    planes = {f"/device:TPU:{d}": {"XLA Ops": list(dev_events)}
              for d in devices}
    planes["/host:CPU"] = {"python": list(host_events)}
    return planes


def test_busy_is_the_union_of_op_intervals():
    ops = [(hlo("sort.1", "sort"), 0.0, 100.0), (hlo("fusion.2"), 50.0, 150.0),
           (hlo("all-to-all.3", "all-to-all"), 300.0, 400.0),
           (hlo("while.5", "while"), 310.0, 390.0),
           (hlo("sort.4", "sort"), 320.0, 380.0)]
    r = trace.reduce(planes_of(ops, []), ["0"], 1e-6)
    assert r.busy_s == pytest.approx(250e-9)
    assert r.idle_share == pytest.approx(0.75)
    assert r.class_s["sort"] == pytest.approx(160e-9)
    assert r.class_s["all_to_all"] == pytest.approx(100e-9)
    assert r.class_s["loop"] == pytest.approx(80e-9)
    # the while loop contains sort.4: ranked by what it contains, not twice
    assert [k for k, _ in r.top_ops] == [
        "sort.1 sort s32[8]", "fusion.2 fusion s32[8]",
        "all-to-all.3 all-to-all s32[8]", "sort.4 sort s32[8]"]


def test_idle_gaps_are_named_by_the_host_spans_over_them():
    ops = [("a", 0.0, 100.0), ("b", 200.0, 300.0), ("c", 700.0, 800.0)]
    spans = [("bench.result", 0.0, 260.0), ("bench.to_numpy", 260.0, 400.0),
             ("bench.submit", 600.0, 650.0), ("other", 0.0, 900.0)]
    r = trace.reduce(planes_of(ops, spans), ["0"], 1e-6,
                     ["bench.result", "bench.to_numpy", "bench.submit"])
    assert dict(r.idle_gaps) == {"bench.result": pytest.approx(100e-9),
                                 "no span": pytest.approx(400e-9)}
    b = r.breakdown()
    assert b["idle_gaps"][0][0] == "no span"
    assert [k for k, _ in b["device_ops"]] == ["a", "b", "c"]   # not HLO


def test_times_are_averaged_over_the_cells_devices():
    ops = [(hlo("sort.1", "sort"), 0.0, 100.0)]
    planes = planes_of(ops, [], devices=("0", "1", "2"))
    planes["/device:TPU:1"]["XLA Ops"] = [(hlo("sort.1", "sort"), 0.0, 300.0)]
    r = trace.reduce(planes, ["0", "1"], 1e-6)
    assert r.n_devices == 2
    assert r.busy_s == pytest.approx(200e-9)
    assert r.class_s["sort"] == pytest.approx(200e-9)


def test_a_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError):
        trace.reduce(planes_of([], []), ["0"], 1.0)


# Recorded on one TPU v5e chip through the session with the benchmark's
# spans: Q26 once, then the per-item roll-up three times, over 20,000
# store_sales rows (300 items, 400 customers).  The window was 34.88 ms.
V5E_1CHIP = os.path.join(HERE, "data", "v5e_1chip_q26_per_item.xplane.pb")
SPANS = ["bench.submit", "bench.result", "bench.to_numpy"]


@pytest.fixture(scope="module")
def v5e_planes():
    return trace.read(V5E_1CHIP)


def test_chip_trace_busy_share_and_op_classes(v5e_planes):
    r = trace.reduce(v5e_planes, ["0"], 0.034880037, SPANS)
    assert r.n_devices == 1
    assert r.busy_s == pytest.approx(0.012858432, rel=1e-6)
    assert r.idle_share == pytest.approx(0.63135, abs=1e-4)
    # the four programs' own extents, read from another line of the trace
    modules = v5e_planes["/device:TPU:0"]["XLA Modules"]
    assert len(modules) == 4
    assert r.busy_s == pytest.approx(
        sum(e - s for _, s, e in modules) * 1e-9, rel=0.01)
    ops = v5e_planes["/device:TPU:0"]["XLA Ops"]
    assert r.class_s["sort"] == pytest.approx(0.000173976, rel=1e-6)
    assert r.class_s["sort"] == pytest.approx(
        sum(e - s for n, s, e in ops if " sort(" in n) * 1e-9)
    assert r.class_s["loop"] == pytest.approx(0.005794656, rel=1e-6)
    assert r.class_s["all_to_all"] == 0.0


def test_chip_trace_breakdown_names(v5e_planes):
    r = trace.reduce(v5e_planes, ["0"], 0.034880037, SPANS)
    b = r.breakdown()
    assert len(b["device_ops"]) == 10
    assert b["device_ops"][0][0] == "fusion.128 fusion kCustom s32[30450]"
    assert b["idle_gaps"][0][0] == "bench.to_numpy"
    assert all(0 < v < r.window_s for _, v in b["device_ops"])


# Recorded on four TPU v5e chips (2x2) through the session: Q26 once over
# 20,000 store_sales rows hash-partitioned on the item key; the group-by's
# exchange is two all-to-alls (counts, packed payload) on every chip.
V5E_4CHIP = os.path.join(HERE, "data", "v5e_4chip_q26.xplane.pb")


def test_four_chip_trace_exchange_time():
    planes = trace.read(V5E_4CHIP)
    r = trace.reduce(planes, ["0", "1", "2", "3"], 0.030593118, SPANS)
    assert r.n_devices == 4
    per_chip = []
    for d in "0123":
        ops = planes[f"/device:TPU:{d}"]["XLA Ops"]
        a2a = [(s, e) for n, s, e in ops if " all-to-all(" in n]
        assert len(a2a) == 2
        per_chip.append(sum(e - s for s, e in a2a) * 1e-9)
    assert r.class_s["all_to_all"] == pytest.approx(sum(per_chip) / 4)
    assert r.class_s["all_to_all"] == pytest.approx(2.8629e-05, rel=1e-6)
    assert r.busy_s == pytest.approx(0.0185973625, rel=1e-6)
    modules = [e - s for d in "0123"
               for _, s, e in planes[f"/device:TPU:{d}"]["XLA Modules"]]
    assert r.busy_s == pytest.approx(sum(modules) / 4 * 1e-9, rel=0.01)
    # one chip alone: its own numbers, not the mean
    one = trace.reduce(planes, ["0"], 0.030593118, SPANS)
    assert one.class_s["all_to_all"] == pytest.approx(per_chip[0])
