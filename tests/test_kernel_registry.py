"""Kernel registry: ref-vs-pallas parity sweep + the ``use_pallas`` lever.

Three contracts are pinned here:

  * PARITY — every registered primitive produces the same result from its
    ``ref`` (lax composition) and ``pallas`` (interpret-mode kernel)
    backends, swept over sizes (incl. zero-length and non-block-multiple),
    dtypes (f32/int32/bool in-process, f64 in an x64 subprocess) and, end to
    end, over 1/2/8 device shards with empty shards in the mix.  The sweep
    is registry-driven: a newly registered primitive without a case entry
    fails ``test_every_primitive_has_a_case``.
  * CENSUS GATE — ``use_pallas`` is a numerics-only lever: the planned
    exchanges, sorts and collective counts are identical across
    "off"/"interpret"/"compiled" (the planner never sees the mode).
  * LEVER — mode validation, the env default and the ``use_kernels``
    deprecation alias.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax.numpy as jnp
import pytest

from repro import hiframes as hf
from repro.kernels import registry as kreg

from test_physical_plan import run_sharded

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# per-primitive parity cases
# ---------------------------------------------------------------------------


def _seg_mask(rng, n):
    """Random 0/1 segment-start mask; position 0 is always a start."""
    m = (rng.random(n) < 0.15).astype(np.int32)
    if n:
        m[0] = 1
    return m


def _values(rng, n, dtype):
    if dtype == np.bool_:
        return rng.random(n) < 0.5
    if np.issubdtype(np.dtype(dtype), np.integer):
        return rng.integers(-50, 50, n).astype(dtype)
    return rng.normal(size=n).astype(dtype)


def _case_prefix_sum(rng, n, dtype):
    return (jnp.asarray(_values(rng, n, dtype)),)


def _case_segment_scan(rng, n, dtype):
    return (jnp.asarray(_values(rng, n, dtype)),
            jnp.asarray(_seg_mask(rng, n)))


def _case_segment_rank(rng, n, dtype):
    seg = _seg_mask(rng, n)
    # order starts are a superset of segment starts (the physical layer's
    # run_starts invariant: a partition head always heads an order run too)
    ordb = np.maximum(seg, (rng.random(n) < 0.3).astype(np.int32))
    return [(jnp.asarray(seg), jnp.asarray(ordb), kind)
            for kind in ("rank", "dense_rank", "row_number")]


def _case_segment_sums(rng, n, dtype):
    # caller contract (physical.segment_aggregate): seg_id = cumsum of run
    # starts over the VALID prefix — sorted, consecutive from 0, no gaps
    nvalid = n - n // 5
    starts = _seg_mask(rng, nvalid)
    sid_valid = (np.cumsum(starts) - 1 if nvalid
                 else np.zeros(0, np.int64)).astype(np.int32)
    nseg = int(sid_valid[-1]) + 1 if nvalid else 1
    valid = np.arange(n) < nvalid
    # invalid tail rows route to the overflow segment, like the caller does
    sid = np.concatenate([sid_valid,
                          np.full(n - nvalid, nseg, np.int32)])
    return (jnp.asarray(_values(rng, n, dtype)), jnp.asarray(sid),
            jnp.asarray(valid), nseg)


def _case_bucket_scatter(rng, n, dtype):
    P = 8
    dest = rng.integers(0, P, n).astype(np.int32)
    if n > 4:           # some invalid rows (dest == P, slot is don't-care)
        dest[rng.choice(n, size=n // 6, replace=False)] = P
    return (jnp.asarray(dest), P)


_W3 = (0.25, 0.5, 0.25)


def _case_stencil1d(rng, n, dtype):
    ext = np.zeros(n + len(_W3) - 1, dtype)
    ext[1:1 + n] = _values(rng, n, dtype)
    return (jnp.asarray(ext), _W3)


def _case_stencil1d_exact(rng, n, dtype):
    ext, _ = _case_stencil1d(rng, n, dtype)
    ext_m = np.zeros(n + len(_W3) - 1, dtype)
    ext_m[1:1 + n] = 1
    return (ext, jnp.asarray(ext_m), _W3)


def _case_segment_stencil(rng, n, dtype):
    k = len(_W3)
    center = 1
    ext = np.zeros(n + k - 1, dtype)
    ext[center:center + n] = _values(rng, n, dtype)
    seg = _seg_mask(rng, n)
    sid = np.cumsum(seg) - 1 if n else np.zeros(0, np.int64)
    ext_s = np.full(n + k - 1, -2, np.int32)
    ext_s[center:center + n] = sid
    return (jnp.asarray(ext), jnp.asarray(ext_s), _W3, center, False)


# name -> (case builder, dtypes swept in-process).  A builder may return one
# arg tuple or a list of them (static-arg variants, e.g. rank kinds).
CASES = {
    "prefix_sum":      (_case_prefix_sum, (np.int32, np.float32)),
    "segment_scan":    (_case_segment_scan, (np.int32, np.float32)),
    "segment_rank":    (_case_segment_rank, (np.int32,)),
    "segment_sums":    (_case_segment_sums, (np.float32,)),
    "bucket_scatter":  (_case_bucket_scatter, (np.int32,)),
    "stencil1d":       (_case_stencil1d, (np.float32,)),
    "stencil1d_exact": (_case_stencil1d_exact, (np.float32,)),
    "segment_stencil": (_case_segment_stencil, (np.float32,)),
}

SIZES = (0, 1, 7, 257, 2048, 5000)     # incl. empty + non-block-multiple


def test_every_primitive_has_a_case():
    """Registering a primitive without a parity case fails the sweep."""
    assert set(kreg.names()) == set(CASES)


def _assert_same(a, b):
    """Integer/bool results must match exactly; floats get tolerances sized
    for the backends' different summation orders (the ref scans are cumsum
    differences, the kernels accumulate directly)."""
    a = a if isinstance(a, tuple) else (a,)
    b = b if isinstance(b, tuple) else (b,)
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        ra, rb = np.asarray(ra), np.asarray(rb)
        assert ra.shape == rb.shape
        if np.issubdtype(ra.dtype, np.floating):
            np.testing.assert_allclose(ra, rb, rtol=1e-4, atol=1e-3)
        else:
            np.testing.assert_array_equal(ra, rb)


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("name", sorted(CASES))
def test_parity_ref_vs_interpret(name, n):
    build, dtypes = CASES[name]
    ref = getattr(kreg.resolve("off"), name)
    pal = getattr(kreg.resolve("interpret"), name)
    for dtype in dtypes:
        rng = np.random.default_rng(hash((name, n, np.dtype(dtype).num)) % 2**31)
        variants = build(rng, n, dtype)
        if not isinstance(variants, list):
            variants = [variants]
        for args in variants:
            a, b = ref(*args), pal(*args)
            if name == "bucket_scatter":
                slot_a, cnt_a = a
                slot_b, cnt_b = b
                np.testing.assert_array_equal(np.asarray(cnt_a),
                                              np.asarray(cnt_b))
                dest = np.asarray(args[0])
                valid = dest < args[1]
                np.testing.assert_array_equal(np.asarray(slot_a)[valid],
                                              np.asarray(slot_b)[valid])
            else:
                _assert_same(a, b)


def test_parity_bool_values_via_physical_layer():
    """Bool columns route through int32 casts in the physical layer; pin the
    cumsum/aggregate results rather than raw-kernel bool inputs."""
    from repro.core import physical as phys
    rng = np.random.default_rng(5)
    n = 400
    x = jnp.asarray(rng.random(n) < 0.5)
    keys = (jnp.asarray(np.sort(rng.integers(0, 9, n)).astype(np.int32)),)
    off = kreg.resolve("off")
    itp = kreg.resolve("interpret")
    a = phys.segment_cumsum(x, keys, jnp.int32(n), kernels=off)
    b = phys.segment_cumsum(x, keys, jnp.int32(n), kernels=itp)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_parity_f64_subprocess():
    """float64 sweep needs jax_enable_x64, which is process-global — run the
    scan/sum primitives in a child interpreter."""
    script = textwrap.dedent("""
        import numpy as np
        import jax
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp
        from repro.kernels import registry as kreg
        rng = np.random.default_rng(11)
        n = 700
        x = jnp.asarray(rng.normal(size=n))          # float64
        assert x.dtype == jnp.float64
        seg = (rng.random(n) < 0.2).astype(np.int32); seg[0] = 1
        off, itp = kreg.resolve("off"), kreg.resolve("interpret")
        a = off.prefix_sum(x); b = itp.prefix_sum(x)
        assert a.dtype == x.dtype
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
        a = off.segment_scan(x, jnp.asarray(seg))
        b = itp.segment_scan(x, jnp.asarray(seg))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
        print("X64_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, f"stderr:\n{res.stderr[-4000:]}"
    assert "X64_OK" in res.stdout


# ---------------------------------------------------------------------------
# end-to-end: lever flips numerics only
# ---------------------------------------------------------------------------


def _pipeline(n=1200, seed=3):
    rng = np.random.default_rng(seed)
    t = {"k": rng.integers(0, 13, n).astype(np.int32),
         "t": rng.integers(0, 10_000, n).astype(np.int32),
         "x": rng.normal(size=n).astype(np.float32)}
    df = hf.table(t)
    w = df.over("k", order_by="t")
    return (w.cumsum(df.x, out="cs")
             .over("k", order_by="t").rank(out="r")
             .groupby("k").agg(s=("x", "sum"), n="count")
             .sort_values("k"))


def test_e2e_off_vs_interpret_single_device():
    frame = _pipeline()
    a = frame.collect(hf.ExecConfig(use_pallas="off")).to_numpy()
    b = frame.collect(hf.ExecConfig(use_pallas="interpret")).to_numpy()
    assert set(a) == set(b)
    for c in a:
        np.testing.assert_allclose(a[c], b[c], rtol=2e-5, atol=2e-5)


_E2E_BODY = """
    import numpy as np
    rng = np.random.default_rng(3)
    n = 1600
    t = {"k": rng.integers(0, 13, n).astype(np.int32),
         "t": rng.integers(0, 10_000, n).astype(np.int32),
         "x": rng.normal(size=n).astype(np.float32)}
    df = hf.table(t)
    # filter thresholds: a normal mix AND an all-drop predicate, so some
    # shards run the segment kernels over count=0 valid prefixes
    for thresh in (0.0, 1e9):
        frame = (df[df.x > -float(thresh)]
                   .over("k", order_by="t").cumsum(df.x, out="cs")
                   .over("k", order_by="t").rank(out="r")
                   .groupby("k").agg(s=("x", "sum"), n="count")
                   .sort_values("k"))
        outs = {}
        for mode in ("off", "interpret"):
            outs[mode] = frame.collect(hf.ExecConfig(use_pallas=mode)).to_numpy()
        for c in outs["off"]:
            np.testing.assert_allclose(outs["off"][c], outs["interpret"][c],
                                       rtol=2e-5, atol=2e-5)
"""


@pytest.mark.parametrize("devices", [2, 8])
def test_e2e_off_vs_interpret_sharded(devices):
    run_sharded(_E2E_BODY, devices)


# ---------------------------------------------------------------------------
# census gate: planning is backend-oblivious
# ---------------------------------------------------------------------------


def test_census_identical_across_modes():
    frame = _pipeline()
    ref = None
    for mode in kreg.MODES:
        cfg = hf.ExecConfig(use_pallas=mode)
        plan = frame.physical_plan(cfg)
        sig = (plan.counts(), plan.collective_count(),
               plan.shuffle_row_bytes(), plan.shuffle_count())
        if ref is None:
            ref = sig
        assert sig == ref, f"use_pallas={mode!r} changed the plan: {sig} != {ref}"


def test_census_identical_with_repartition_and_stencil():
    rng = np.random.default_rng(9)
    n = 500
    df = hf.table({"k": rng.integers(0, 5, n).astype(np.int32),
                   "x": rng.normal(size=n).astype(np.float32)})
    frame = (df.repartition("k").sort_within_partitions("k")
               .over("k").rolling_mean(df.x, 4, exact=True))
    ref = None
    for mode in kreg.MODES:
        plan = frame.physical_plan(hf.ExecConfig(use_pallas=mode))
        sig = (plan.counts(), plan.collective_count())
        ref = ref or sig
        assert sig == ref


# ---------------------------------------------------------------------------
# the lever itself
# ---------------------------------------------------------------------------


def test_use_kernels_alias(monkeypatch):
    monkeypatch.delenv("HIFRAMES_USE_PALLAS", raising=False)
    assert hf.ExecConfig().use_pallas == "off"
    assert hf.ExecConfig(use_kernels=True).use_pallas == "interpret"
    # explicit use_pallas wins over the alias
    assert hf.ExecConfig(use_kernels=True,
                         use_pallas="compiled").use_pallas == "compiled"


def test_bad_mode_rejected():
    with pytest.raises(ValueError, match="use_pallas"):
        hf.ExecConfig(use_pallas="gpu")
    with pytest.raises(ValueError):
        kreg.resolve("nope")


def test_env_default(monkeypatch):
    monkeypatch.setenv("HIFRAMES_USE_PALLAS", "interpret")
    assert hf.ExecConfig().use_pallas == "interpret"
    monkeypatch.setenv("HIFRAMES_USE_PALLAS", "off")
    assert hf.ExecConfig().use_pallas == "off"


@pytest.mark.parametrize("mode,platform,nxt", [
    ("compiled", "tpu", "off"),          # no interpreter rung on a TPU
    ("compiled", "cpu", "interpret"),
    ("interpret", "cpu", "off"),
    ("interpret", "tpu", "off"),
    ("off", "tpu", None),
    ("off", "cpu", None),
])
def test_downgrade_ladder(mode, platform, nxt):
    assert kreg.downgrade(mode, platform) == nxt


def test_registry_shape():
    ks = kreg.resolve("interpret")
    assert "KernelSet" in repr(ks)
    with pytest.raises(AttributeError, match="no kernel"):
        ks.not_a_kernel
    spec = kreg.get("prefix_sum")
    assert spec.name == "prefix_sum" and callable(spec.ref)
    with pytest.raises(ValueError, match="already registered"):
        kreg.register("prefix_sum", ref=lambda x: x, pallas=lambda x: x)
