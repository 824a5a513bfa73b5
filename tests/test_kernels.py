"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles,
plus slow python-loop oracles for the fused segment kernels (the registry
parity sweep in test_kernel_registry.py compares backends against each other;
these pin both against first-principles loops)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.hash_partition import ops as hp_ops, ref as hp_ref
from repro.kernels.segment_rank import ops as rk_ops, ref as rk_ref
from repro.kernels.segment_reduce import ops as sr_ops, ref as sr_ref
from repro.kernels.segment_scan import ops as ss_ops, ref as ss_ref
from repro.kernels.stencil1d import ops as st_ops, ref as st_ref
from repro.kernels.stream_compact import ops as sc_ops, ref as sc_ref

RNG = np.random.default_rng(11)


@pytest.mark.parametrize("n", [1, 7, 100, 2048, 5000])
@pytest.mark.parametrize("K", [1, 3, 5, 7, 200])
def test_stencil_shapes(n, K):
    ext = RNG.normal(size=n + K - 1).astype(np.float32)
    w = RNG.normal(size=K).tolist()
    got = np.asarray(st_ops.stencil1d(jnp.asarray(ext), w))
    ref = np.asarray(st_ref.stencil1d_ref(jnp.asarray(ext), w))
    np.testing.assert_allclose(got, ref, atol=1e-4)


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
@pytest.mark.parametrize("n", [1, 100, 2048, 4096, 9999])
def test_prefix_sum_shapes(n, dtype):
    if dtype == np.int32:
        x = RNG.integers(-5, 5, n).astype(dtype)
    else:
        x = RNG.normal(size=n).astype(dtype)
    got = np.asarray(sc_ops.prefix_sum(jnp.asarray(x)))
    ref = np.cumsum(x).astype(dtype)
    if dtype == np.int32:
        np.testing.assert_array_equal(got, ref)
    else:
        np.testing.assert_allclose(got, ref, atol=1e-3)


@pytest.mark.parametrize("n,cap", [(100, 60), (100, 200), (2048, 1024)])
def test_compact(n, cap):
    vals = RNG.normal(size=n).astype(np.float32)
    keep = RNG.random(n) < 0.5
    got, cnt = sc_ops.compact(jnp.asarray(vals), jnp.asarray(keep), cap)
    ref, rcnt = sc_ref.compact_ref(jnp.asarray(vals), jnp.asarray(keep), cap)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert int(cnt) == int(rcnt)


@pytest.mark.parametrize("n,nseg", [(50, 5), (2000, 37), (4096, 200), (5000, 1)])
def test_segment_sums(n, nseg):
    rng = np.random.default_rng(n * 31 + nseg)   # deterministic per-case
    seg = np.sort(rng.integers(0, nseg, n)).astype(np.int32)
    vals = rng.normal(size=n).astype(np.float32)
    valid = np.arange(n) < (n - n // 10)
    # contract: seg ids are consecutive 0..k-1 over the VALID prefix (this is
    # how the aggregate lowering constructs them); invalid rows repeat the
    # last valid id so the array stays sorted.
    _, seg = np.unique(seg[valid], return_inverse=True)
    k = int(seg.max()) + 1 if len(seg) else 1
    seg2 = np.concatenate([seg, np.full(n - valid.sum(), seg[-1] if len(seg)
                                        else 0)]).astype(np.int32)
    got = np.asarray(sr_ops.segment_sums(jnp.asarray(vals), jnp.asarray(seg2),
                                         jnp.asarray(valid), k))
    ref = np.asarray(sr_ref.segment_sums_ref(jnp.asarray(vals), jnp.asarray(seg2),
                                             jnp.asarray(valid), k))
    np.testing.assert_allclose(got, ref, atol=2e-3)


@pytest.mark.parametrize("P", [2, 8, 64, 256])
@pytest.mark.parametrize("n", [10, 1000, 3000])
def test_bucket_ranks(P, n):
    d = RNG.integers(0, P + 1, n).astype(np.int32)   # P marks invalid
    r1, c1 = hp_ops.bucket_ranks(jnp.asarray(d), P)
    r2, c2 = hp_ref.bucket_ranks_ref(jnp.asarray(d), P)
    m = d < P
    np.testing.assert_array_equal(np.asarray(r1)[m], np.asarray(r2)[m])
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


def test_bucket_ranks_are_stable_slots():
    """ranks must be a stable enumeration within each bucket."""
    d = np.array([1, 0, 1, 1, 0, 2, 1], np.int32)
    r, c = hp_ops.bucket_ranks(jnp.asarray(d), 3)
    r = np.asarray(r)
    np.testing.assert_array_equal(r, [0, 0, 1, 2, 1, 0, 3])
    np.testing.assert_array_equal(np.asarray(c), [2, 4, 1])


def test_bucket_ranks_argsort_matches_kernel():
    """The registry's ref backend (stable-argsort slots) must agree with the
    Pallas histogram kernel — it backs the exchange in use_pallas='off'."""
    d = RNG.integers(0, 9, 4000).astype(np.int32)   # 8 buckets + invalid
    r1, c1 = hp_ref.bucket_ranks_argsort(jnp.asarray(d), 8)
    r2, c2 = hp_ops.bucket_ranks(jnp.asarray(d), 8)
    m = d < 8
    np.testing.assert_array_equal(np.asarray(r1)[m], np.asarray(r2)[m])
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


# -- fused segment kernels vs python-loop oracles ------------------------------


def _loop_segment_scan(x, b):
    out, run = np.zeros_like(x), x.dtype.type(0)
    for i, (v, f) in enumerate(zip(x, b)):
        run = v if f else run + v
        out[i] = run
    return out


@pytest.mark.parametrize("n", [1, 7, 100, 2048, 6000])
def test_segment_scan_vs_loop(n):
    rng = np.random.default_rng(n)
    x = rng.integers(-40, 40, n).astype(np.int32)
    b = (rng.random(n) < 0.1).astype(np.int32)
    b[0] = 1
    want = _loop_segment_scan(x, b)
    got = np.asarray(ss_ops.segment_scan(jnp.asarray(x), jnp.asarray(b)))
    ref = np.asarray(ss_ref.segment_scan_ref(jnp.asarray(x), jnp.asarray(b)))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(ref, want)


def _loop_segment_rank(seg_b, ord_b, kind):
    n = len(seg_b)
    out = np.zeros(n, np.int32)
    rn = dr = mx = 0
    for i in range(n):
        if seg_b[i]:
            rn = dr = mx = 0
        rn += 1
        if ord_b[i]:
            dr += 1
            mx = rn
        out[i] = {"row_number": rn, "dense_rank": dr, "rank": mx}[kind]
    return out


@pytest.mark.parametrize("kind", ["rank", "dense_rank", "row_number"])
@pytest.mark.parametrize("n", [1, 9, 333, 2048, 4100])
def test_segment_rank_vs_loop(kind, n):
    rng = np.random.default_rng(n * 7 + len(kind))
    seg = (rng.random(n) < 0.08).astype(np.int32)
    seg[0] = 1
    ordb = np.maximum(seg, (rng.random(n) < 0.35).astype(np.int32))
    want = _loop_segment_rank(seg, ordb, kind)
    got = np.asarray(rk_ops.segment_rank(jnp.asarray(seg), jnp.asarray(ordb),
                                         kind))
    ref = np.asarray(rk_ref.segment_rank_ref(jnp.asarray(seg),
                                             jnp.asarray(ordb), kind))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(ref, want)


@pytest.mark.parametrize("n,K,center", [(50, 3, 1), (500, 5, 4), (2048, 4, 0),
                                        (3000, 7, 3), (3000, 200, 100)])
def test_stencil1d_exact_vs_loop(n, K, center):
    rng = np.random.default_rng(n + K)
    w = rng.random(K).astype(np.float32) + 0.1
    ext = np.zeros(n + K - 1, np.float32)
    ext_m = np.zeros(n + K - 1, np.float32)
    ext[center:center + n] = rng.normal(size=n).astype(np.float32)
    ext_m[center:center + n] = 1.0
    want = np.zeros(n, np.float64)
    total = float(np.float32(np.sum([float(x) for x in w])))
    for i in range(n):
        acc = sum(float(w[j]) * float(ext[i + j]) for j in range(K))
        mass = sum(float(w[j]) * float(ext_m[i + j]) for j in range(K))
        want[i] = acc * total / mass if mass else 0.0
    wl = [float(x) for x in w]
    got = np.asarray(st_ops.stencil1d_exact(jnp.asarray(ext),
                                            jnp.asarray(ext_m), wl))
    ref = np.asarray(st_ref.stencil1d_exact_ref(jnp.asarray(ext),
                                                jnp.asarray(ext_m), wl))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(ref, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("exact", [False, True])
@pytest.mark.parametrize("n", [40, 700, 2500])
def test_segment_stencil_vs_loop(n, exact):
    K, center = 3, 1
    rng = np.random.default_rng(n + exact)
    w = [0.25, 0.5, 0.25]
    seg = (rng.random(n) < 0.1).astype(np.int32)
    seg[0] = 1
    sid = np.cumsum(seg) - 1
    x = rng.normal(size=n).astype(np.float32)
    ext = np.zeros(n + K - 1, np.float32)
    ext[center:center + n] = x
    ext_s = np.full(n + K - 1, -2, np.int32)
    ext_s[center:center + n] = sid
    want = np.zeros(n, np.float64)
    total = float(np.float32(sum(w)))
    for i in range(n):
        acc = mass = 0.0
        for j in range(K):
            p = i + j - center
            if 0 <= p < n and sid[p] == sid[i]:
                acc += w[j] * float(x[p])
                mass += w[j]
        want[i] = (acc * total / mass if mass else 0.0) if exact else acc
    got = np.asarray(st_ops.segment_stencil(jnp.asarray(ext),
                                            jnp.asarray(ext_s), w, center,
                                            exact))
    ref = np.asarray(st_ref.segment_stencil_ref(jnp.asarray(ext),
                                                jnp.asarray(ext_s), w, center,
                                                exact))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(ref, want, rtol=1e-4, atol=1e-4)
