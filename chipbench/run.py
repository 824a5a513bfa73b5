#!/usr/bin/env python3
"""Chip benchmark of the HiFrames serving path, one cell per run.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.  A run
makes the configuration's tables from ``--seed``, registers them in a
``Session`` on a mesh of the cell's chips, warms the mix's query, then
drives ``Session.submit(...).result().to_numpy()`` for ``--seconds``.  Once
the window has closed every answer is compared with the query's numpy
reference over the same tables.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics read
in a profiled window), ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: each compared number beside its limit.

It exits nonzero without a result line unless JAX finds a TPU with the
cell's chips.  JAX's persistent compilation cache is ``<checkout>/.jax_cache``
(or ``$JAX_COMPILATION_CACHE_DIR``), so only a checkout's first run of a
cell compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import check, data, load_json, load_module, loops  # noqa: E402

SPANS = ("submit", "result", "to_numpy")


class Cell:
    """One entry of ``workloads`` with everything its names point to."""

    def __init__(self, root: str, name: str):
        spec = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(cells)})")
        entry = cells[name]
        self.root, self.name = root, name
        self.chips = int(entry["chips"])
        cfg = {c["name"]: c for c in spec["configs"]}[entry["config"]]
        self.config = load_json(os.path.join(root, cfg["file"]))
        self.traffic = load_json(os.path.join(
            root, "chipbench", "traffic", f"{entry['traffic']}.json"))
        self.query = load_module(root, "queries", self.traffic["query"])
        self.limits = load_json(os.path.join(
            root, "chipbench", "limits",
            f"{entry['config']}.{self.traffic['query']}.json"))
        self.metrics = {g: [m for m in spec[g]
                            if name in m.get("workloads", [name])]
                        for g in ("end_to_end", "per_layer")}


# -- one run ------------------------------------------------------------------

class Run:
    """What a metric reader sees of a run (``chipbench/metrics/*.py``)."""

    def __init__(self, cell: Cell, seconds: float):
        self.cell, self.seconds = cell, seconds
        self.requests: list = []       # every request of the window
        self.setup_s = 0.0
        self.peak_bytes = 0
        self.trace = None              # trace.Reduced of a --trace 1 run

    @property
    def answered(self) -> list:
        return [r for r in self.requests if r.answer is not None]

    @property
    def in_window(self) -> list:
        """Answers on the host before the window closed."""
        return [r for r in self.answered if r.done <= self.seconds]


class CompileCount:
    """Backend compiles reported through ``jax.monitoring``."""

    def __init__(self, jax):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            self.n += 1


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def register(sess, hf, cell: Cell, host: dict) -> dict:
    """Register the configuration's host tables in ``sess`` with their
    layouts; returns the registered frames by name."""
    for name, lay in cell.config["tables"].items():
        df = hf.table(host[name], name)
        if lay.get("replicate"):
            df = df.replicate()
        sess.register(name, df, partition_by=lay.get("partition_by"))
    return {name: sess.table(name) for name in cell.config["tables"]}


def measure(cell: Cell, seed: int, seconds: float, traced: bool,
            devices: list, t_start: float = T_START) -> dict:
    """Everything of a run after the look for the chips; returns the
    result line's object."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro import hiframes as hf
    from repro.core.api import ExecConfig
    from repro.runtime.session import Session

    from chipbench import trace as trace_mod

    compiles = CompileCount(jax)
    mesh_devs = devices[:cell.chips]
    mesh = Mesh(np.array(mesh_devs), ("data",))
    phases = [("start-up", time.perf_counter())]
    host = data.make_tables(cell.root, cell.config["tables"],
                            cell.config["scale"], seed)
    phases.append(("tables", time.perf_counter()))
    run = Run(cell, seconds)
    sess = Session(ExecConfig(mesh=mesh))
    try:
        tables = register(sess, hf, cell, host)
        phases.append(("registration", time.perf_counter()))

        def make():
            return cell.query.build(hf, tables)

        warm = loops.warm(sess, make, int(cell.traffic.get("warmup", 2)))
        bad = [r.error for r in warm if r.error]
        if bad:
            raise RuntimeError(f"warm-up failed: {bad[0]}")
        phases.append(("warm-up", time.perf_counter()))
        log("set-up: " + ", ".join(
            f"{name} {t - t0:.3f} s" for (name, t), t0
            in zip(phases, [t_start] + [t for _, t in phases])) +
            f"; {compiles.n} backend compiles")
        misses0, compiles0 = sess.plan_cache.misses, compiles.n
        tdir = span = None
        if traced:
            tdir = tempfile.TemporaryDirectory(prefix="chipbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # keep bench.* spans, not every call
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(tdir.name, profiler_options=opts)

            def span(name):
                return jax.profiler.TraceAnnotation(f"bench.{name}")
        loop = cell.traffic["loop"]
        run.setup_s = time.perf_counter() - t_start
        t_trace0 = time.perf_counter()
        if loop == "closed":
            run.requests = loops.closed(sess, make, seconds,
                                        span or loops.no_span)
        elif loop == "open":
            dues = loops.arrivals(float(cell.traffic["rate_per_s"]), seconds,
                                  seed)
            run.requests = loops.open_loop(sess, make, dues, seconds,
                                           span or loops.no_span)
        else:
            raise ValueError(f"unknown loop {loop!r}")
        if traced:
            window_s = time.perf_counter() - t_trace0
            jax.profiler.stop_trace()
            run.trace = trace_mod.reduce(
                trace_mod.read(tdir.name), [str(d.id) for d in mesh_devs],
                window_s, [f"bench.{s}" for s in SPANS])
            tdir.cleanup()
        log(f"window: {len(run.requests)} requests, "
            f"{sum(r.record.compiles for r in run.answered if r.record)} "
            f"plan compiles and {compiles.n - compiles0} backend compiles, "
            f"{sess.plan_cache.misses - misses0} plan-cache misses "
            "(both should be 0)")
        late = [r.sent - r.due for r in run.requests]
        if loop == "open" and late:
            log(f"generator lateness: mean {1e3 * np.mean(late):.3f} ms, "
                f"p95 {1e3 * np.percentile(late, 95):.3f} ms, max "
                f"{1e3 * max(late):.3f} ms")
        run.peak_bytes = max(int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in mesh_devs)
    finally:
        sess.close()
        del sess
        gc.collect()

    t_cmp = time.perf_counter()
    want = cell.query.reference(host)
    numbers, bad = check.compare(cell.query,
                                 [r.answer for r in run.answered], want,
                                 cell.limits)
    failed = [r for r in run.requests if r.answer is None]
    correct, checks = check.judge(numbers, cell.limits, len(failed))
    log(f"comparing answers: {time.perf_counter() - t_cmp:.3f} s for "
        f"{len(run.answered)} answers")
    for r in failed[:3]:
        log(f"request {r.i} failed: {r.error}")

    group = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in cell.metrics[group]:
        v = load_module(cell.root, "metrics", m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    d0 = mesh_devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(mesh_devs), "memory_peak_bytes": run.peak_bytes}
    out = {"correct": bool(correct), "attempted": len(run.requests),
           "failed": len(failed) + bad,
           "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = run.trace.breakdown()
    out["checks"] = checks
    for name, e in checks.items():
        log(f"check {name}: {e['value']!r} (limit {e['limit']!r})")
    return out


# -- command line ---------------------------------------------------------------

def listing(root: str) -> str:
    """The cells, configurations, mixes and metrics found on disk."""
    lines = []
    try:
        spec = load_json(os.path.join(root, "BENCHMARK.json"))
    except (OSError, ValueError) as e:
        return f"(no readable BENCHMARK.json: {e})"
    lines.append("cells:")
    for w in spec["workloads"]:
        lines.append(f"  {w['name']}: {w['config']} x {w['traffic']}, "
                     f"{w['chips']} chip(s)")
    for g in ("end_to_end", "per_layer"):
        lines.append(f"{g} metrics:")
        for m in spec[g]:
            cells = ", ".join(m.get("workloads", ["every cell"]))
            lines.append(f"  {m['name']} [{m['unit']}, {m['better']}]: {cells}")
    for kind, ext in (("configs", ".json"), ("tables", ".py"),
                      ("traffic", ".json"), ("queries", ".py"),
                      ("metrics", ".py")):
        d = os.path.join(root, "chipbench", kind)
        names = sorted(f[:-len(ext)] for f in os.listdir(d)
                       if f.endswith(ext) and not f.startswith("_"))
        lines.append(f"{kind} on disk: {', '.join(names)}")
    return "\n".join(lines)


def parse(argv):
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0], epilog=listing(ROOT),
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: profile the window, report per-layer metrics")
    return ap.parse_args(argv)


def enable_compile_cache(jax) -> str:
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    args = parse(argv)
    cell = Cell(ROOT, args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"chipbench: no TPU (JAX found {devices[0].platform})")
        return 1
    if len(devices) < cell.chips:
        log(f"chipbench: {cell.chips} chips asked, {len(devices)} found")
        return 1
    log(f"compile cache: {enable_compile_cache(jax)}")
    out = measure(cell, args.seed, args.seconds, bool(args.trace), devices)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
