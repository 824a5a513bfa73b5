"""What the benchmark reads of the program's own records: QueryRecord
phases, operator scopes over device ops, and hf.* host spans in a trace;
on hand-made records and planes.  A program that records none of it (an
older one) reads as nothing, never as an error."""
import json
import os
from types import SimpleNamespace as NS

import pytest

from chipbench import phases, trace
from chipbench import run as bench

from .conftest import REPO

HERE = os.path.dirname(os.path.abspath(__file__))
RECORD_METRICS = {"queue_ms.serve": "queue_s", "plan_ms.serve": "plan_s",
                  "lock_ms.serve": "lock_s", "run_ms.serve": "run_s",
                  "readback_ms.serve": "readback_s",
                  "readback_ms.batch": "readback_s"}
OP_METRICS = {"join_op_ms.batch": "MergeJoin",
              "exchange_op_ms.4chip": "HashExchange"}


class Plan:
    def __init__(self, scopes):
        self.scopes = scopes
        self.calls = 0

    def op_scopes(self):
        self.calls += 1
        return self.scopes


def fake_run(records, top_ops=None):
    reqs = [NS(answer={}, record=r) for r in records]
    return NS(answered=reqs,
              trace=None if top_ops is None else NS(top_ops=top_ops))


def record(**kw):
    base = dict(queue_s=0.001, plan_s=0.002, lock_s=0.003, prepare_s=0.0005,
                run_s=0.02, check_s=0.0001, readback_s=0.004)
    base.update(kw)
    return NS(**base)


def test_mean_ms_of_a_record_field():
    run = fake_run([record(run_s=0.02), record(run_s=0.04), None])
    assert phases.mean_ms(run, "run_s") == pytest.approx(30.0)


def test_operator_seconds_by_scope():
    top = [("fusion.1 fusion kLoop s32[8]", 3.0), ("sort.2 sort s32[8]", 2.0),
           ("fusion.3 fusion kCustom s32[8]", 1.0), ("copy.4 copy s32[8]", 0.5)]
    sc = {"fusion.1": "MergeJoin.2", "fusion.3": "MergeJoin.2",
          "sort.2": "LocalSort.4"}
    assert phases.operator_s(top, sc) == {
        "MergeJoin.2": 4.0, "LocalSort.4": 2.0, "unattributed": 0.5}
    plan = Plan(sc)
    run = fake_run([record(plan=plan), record(plan=plan)], top)
    assert phases.operator_ms(run, "MergeJoin") == pytest.approx(2000.0)
    assert phases.operator_ms(run, "LocalSort") == pytest.approx(1000.0)
    assert phases.operator_ms(run, "HashExchange") is None
    assert plan.calls == 3          # two records of one plan: once a reading


@pytest.mark.parametrize("name", sorted(RECORD_METRICS) + sorted(OP_METRICS))
def test_new_readers_read_the_program_and_nothing_of_an_older_one(name):
    mod = bench.load_module(REPO, "metrics", name)
    top = [("fusion.1 fusion kLoop s32[8]", 0.5)]
    label = f"{OP_METRICS.get(name, 'Compact')}.3"
    now = fake_run([record(plan=Plan({"fusion.1": label}))] * 2, top)
    if name in RECORD_METRICS:
        assert mod.read(now) == pytest.approx(
            1e3 * getattr(now.answered[0].record, RECORD_METRICS[name]))
    else:
        assert mod.read(now) == pytest.approx(250.0)
    # a record with exec_s and plan_s alone, no plan: an older program's,
    # whose plan_s meant something else on a hit
    old = fake_run([NS(plan_s=0.002, exec_s=0.03)] * 2, top)
    assert mod.read(old) is None
    assert mod.read(fake_run([])) is None


def test_operators_read_nothing_when_programs_with_other_maps_answered():
    top = [("fusion.1 fusion kLoop s32[8]", 3.0)]
    one, same = Plan({"fusion.1": "MergeJoin.2"}), Plan({"fusion.1":
                                                       "MergeJoin.2"})
    run = fake_run([record(plan=one), record(plan=same)], top)
    assert phases.operator_ms(run, "MergeJoin") == pytest.approx(1500.0)
    other = Plan({"fusion.1": "HashExchange.6"})
    run = fake_run([record(plan=one), record(plan=other)], top)
    assert phases.scopes(run) is None
    assert phases.operator_ms(run, "MergeJoin") is None
    assert phases.operator_ms(run, "HashExchange") is None


def planes_of(dev_events, host_lines):
    planes = {"/device:TPU:0": {"XLA Ops": list(dev_events)}}
    planes["/host:CPU"] = {k: list(v) for k, v in host_lines.items()}
    return planes


def test_idle_phases_name_gaps_by_the_innermost_span():
    ops = [("a", 0.0, 100.0), ("b", 200.0, 300.0), ("c", 500.0, 600.0),
           ("d", 800.0, 900.0), ("e", 1400.0, 1500.0)]
    worker = [("hf.query", 0.0, 700.0), ("hf.plan", 0.0, 120.0),
              ("hf.lock", 120.0, 190.0), ("hf.run", 190.0, 650.0),
              ("hf.check", 650.0, 700.0)]
    reader = [("hf.readback", 720.0, 1000.0), ("bench.result", 0.0, 720.0)]
    r = phases.idle_phases(planes_of(ops, {"python3": worker,
                                           "python3#1": reader}), ["0"])
    # gaps: 100-200 (mid 150: lock), 300-500 (run), 600-800 (mid 700:
    # check ends at 700, readback from 720 -> none), 900-1400 (no span)
    assert dict(r) == {"hf.lock": pytest.approx(100e-9),
                       "hf.run": pytest.approx(200e-9),
                       phases.NO_QUERY: pytest.approx(700e-9)}
    assert r[0][0] == phases.NO_QUERY


def test_idle_phases_join_the_threads_spans():
    ops = [("a", 0.0, 100.0), ("b", 300.0, 400.0)]
    lines = {"python3": [("hf.query", 0.0, 500.0), ("hf.run", 50.0, 450.0)],
             "python3#1": [("hf.query", 100.0, 600.0),
                           ("hf.lock", 120.0, 500.0)]}
    r = phases.idle_phases(planes_of(ops, lines), ["0"])
    assert r == [("hf.lock+hf.run", pytest.approx(200e-9))]


def test_span_totals_sum_over_threads():
    lines = {"t": [("hf.run", 0.0, 1e9), ("other", 0.0, 5e9)],
             "u": [("hf.run", 0.0, 2e9), ("hf.plan", 0.0, 5e8)]}
    assert phases.span_totals(planes_of([], lines)) == {
        "hf.run": pytest.approx(3.0), "hf.plan": pytest.approx(0.5)}


# Recorded on one TPU v5e chip through the session, with the program's
# hf.* spans: Q26 once, then the per-item roll-up three times, over 20,000
# store_sales rows (300 items, 400 customers), each answer read to the host
# on the main thread.  Beside it, each plan's Lowered.op_scopes().
HF_TRACE = os.path.join(HERE, "data", "v5e_1chip_hf_spans.xplane.pb")
HF_SCOPES = os.path.join(HERE, "data", "v5e_1chip_hf_spans.scopes.json")
MS = 1e6                                            # ns


@pytest.fixture(scope="module")
def hf_planes():
    return phases.read(HF_TRACE)


def test_chip_trace_keeps_the_threads_apart(hf_planes):
    spans = phases.hf_spans(hf_planes)
    by_line = {k: {n for n, _, _ in v} for k, v in spans.items()}
    assert sorted(by_line.values(), key=len) == [
        {"hf.readback"},
        {"hf.query", "hf.plan", "hf.lock", "hf.prepare", "hf.run",
         "hf.check"}]
    totals = phases.span_totals(hf_planes)
    assert totals["hf.run"] == pytest.approx(0.019723559, rel=1e-6)
    assert totals["hf.readback"] == pytest.approx(0.00788776, rel=1e-6)


def test_chip_trace_every_program_runs_inside_hf_run(hf_planes):
    """The spans share the device's clock: each execution of a program
    (XLA Modules) lies inside one hf.run span, within 1 ms."""
    runs = [e for evs in phases.hf_spans(hf_planes).values()
            for e in evs if e[0] == "hf.run"]
    modules = hf_planes["/device:TPU:0"]["XLA Modules"]
    assert len(modules) == len(runs) == 4
    for _, s, e in modules:
        inside = [r for r in runs if r[1] - MS <= s and e <= r[2] + MS]
        assert len(inside) == 1, (s, e)


def test_chip_trace_idle_phases_cover_the_idle_time(hf_planes):
    idle = phases.idle_phases(hf_planes, ["0"])
    names = {n for n, _ in idle}
    assert names <= {"hf.query", "hf.plan", "hf.lock", "hf.prepare",
                     "hf.run", "hf.check", "hf.readback", phases.NO_QUERY}
    devs = trace.device_planes(hf_planes, {"0"})
    cover = trace.union((s, e) for _, s, e in devs["0"])
    holes = sum(s1 - e0 for (_, e0), (s1, _) in zip(cover, cover[1:]))
    assert sum(v for _, v in idle) == pytest.approx(holes * 1e-9)
    # the three gaps between programs, longest first: the first query's
    # end, then the next two queries' overflow and invariant checks
    assert idle[0] == ("hf.check", pytest.approx(0.009195166, rel=1e-6))


def test_chip_trace_device_time_is_put_down_to_operators(hf_planes):
    """Each program's ops are named by the plan whose op_scopes() know
    them; at least 95% of device-op time lies under a named operator."""
    with open(HF_SCOPES) as f:
        programs = list(json.load(f).values())
    ops = hf_planes["/device:TPU:0"]["XLA Ops"]
    seconds = {}
    for _, s, e in hf_planes["/device:TPU:0"]["XLA Modules"]:
        inside = [(trace.parse_op(n), t0, t1) for n, t0, t1 in ops
                  if s <= t0 and t1 <= e]
        top = [(short, t1 - t0) for (opcode, short), t0, t1 in inside
               if opcode not in trace.CONTAINERS]
        names = {short.split(" ", 1)[0] for short, _ in top}
        scopes = max(programs, key=lambda p: len(names & set(p)))
        for label, v in phases.operator_s(top, scopes).items():
            seconds[label.rsplit(".", 1)[0]] = \
                seconds.get(label.rsplit(".", 1)[0], 0.0) + v
    total = sum(seconds.values())
    assert seconds.get("unattributed", 0.0) / total < 0.05, seconds
    assert max(seconds, key=seconds.get) == "MergeJoin"
