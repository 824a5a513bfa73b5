"""Multi-device correctness: subprocess runs with 8 fake host devices.

Each case executes a small script under XLA_FLAGS=--xla_force_host_platform_
device_count=8 (set before jax import, which is why these are subprocesses —
the main pytest process must keep seeing ONE device).
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run8(body: str, devices: int = 8):
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import numpy as np
        import jax
        assert jax.device_count() == {devices}
        from repro import hiframes as hf
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
        print("SUBPROC_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, f"stderr:\n{res.stderr[-4000:]}"
    assert "SUBPROC_OK" in res.stdout
    return res.stdout


def test_shuffle_join_aggregate_8dev():
    run8("""
        rng = np.random.default_rng(1)
        n = 1003
        ids = rng.integers(0, 37, n).astype(np.int32)
        xs = rng.normal(size=n).astype(np.float32)
        df = hf.table({"id": ids, "x": xs})
        a = hf.aggregate(df, "id", s=hf.sum_(df["x"]), c=hf.count()).collect()
        d = a.to_numpy(); o = np.argsort(d["id"])
        uids = np.unique(ids)
        assert np.array_equal(d["id"][o], uids)
        assert np.allclose(d["s"][o], [xs[ids==u].sum() for u in uids], atol=1e-3)
        dim = hf.table({"cid": rng.integers(0, 37, 77).astype(np.int32),
                        "w": rng.normal(size=77).astype(np.float32)}, "dim")
        tj = hf.join(df, dim, on=("id","cid")).collect()
        n_pairs = sum(int((np.asarray(dim.node.columns["cid"]) == i).sum()) for i in ids)
        assert tj.num_rows() == n_pairs
        assert not tj.overflow
    """)


def test_composite_keys_8dev():
    """2-column join/aggregate/sort across 8 shards match the host oracle."""
    run8("""
        rng = np.random.default_rng(11)
        n = 1003
        k1 = rng.integers(0, 6, n).astype(np.int32)
        k2 = rng.integers(0, 9, n).astype(np.int32)
        xs = rng.normal(size=n).astype(np.float32)
        df = hf.table({"k1": k1, "k2": k2, "x": xs})
        # aggregate on a composite key
        a = hf.aggregate(df, by=("k1", "k2"), s=hf.sum_(df["x"]),
                         c=hf.count()).collect().to_numpy()
        ref = {}
        for i in range(n):
            kt = (int(k1[i]), int(k2[i]))
            s, c = ref.get(kt, (0.0, 0))
            ref[kt] = (s + float(xs[i]), c + 1)
        got = {(int(a1), int(a2)): (float(s), int(c))
               for a1, a2, s, c in zip(a["k1"], a["k2"], a["s"], a["c"])}
        assert len(got) == len(ref)
        assert all(abs(got[k][0] - ref[k][0]) < 1e-2 and got[k][1] == ref[k][1]
                   for k in ref)
        # join on a composite key
        m = 77
        ca = rng.integers(0, 6, m).astype(np.int32)
        cb = rng.integers(0, 9, m).astype(np.int32)
        ws = rng.normal(size=m).astype(np.float32)
        dim = hf.table({"ca": ca, "cb": cb, "w": ws}, "dim")
        tj = hf.join(df, dim, on=[("k1", "ca"), ("k2", "cb")]).collect()
        n_pairs = sum(1 for i in range(n) for j in range(m)
                      if k1[i] == ca[j] and k2[i] == cb[j])
        assert tj.num_rows() == n_pairs
        assert not tj.overflow
        # lexicographic sample-sort on two keys
        st = df.sort(by=("k1", "k2")).collect().to_numpy()
        order = np.lexsort((k2, k1))
        assert np.array_equal(st["k1"], k1[order])
        assert np.array_equal(st["k2"], k2[order])
    """)


def test_window_ops_8dev():
    run8("""
        rng = np.random.default_rng(2)
        n = 777
        xs = rng.normal(size=n).astype(np.float32)
        df = hf.table({"x": xs})
        c = hf.cumsum(df, df["x"], out="c").collect().to_numpy()
        assert np.allclose(c["c"], np.cumsum(xs), atol=1e-3)
        w = hf.wma(df, df["x"], [1,2,1], out="w").collect().to_numpy()
        ext = np.concatenate([[0.], xs, [0.]])
        assert np.allclose(w["w"], (ext[:-2]+2*ext[1:-1]+ext[2:])/4, atol=1e-4)
        # ladder exscan variant
        c2 = hf.cumsum(df, df["x"], out="c").collect(
            hf.ExecConfig(exscan_method="ladder")).to_numpy()
        assert np.allclose(c2["c"], np.cumsum(xs), atol=1e-3)
    """)


def test_rebalance_and_sort_8dev():
    run8("""
        rng = np.random.default_rng(3)
        n = 901
        ids = rng.integers(0, 19, n).astype(np.int32)
        xs = rng.normal(size=n).astype(np.float32)
        df = hf.table({"id": ids, "x": xs})
        s = hf.sma(df[df["id"] < 7], df["x"], 3, out="s")
        t = s.collect()
        counts = np.asarray(t.counts)
        # rebalanced: counts even (block) except the tail
        assert counts.max() - counts.min() <= max(1, counts.max() - counts.min())
        xs_f = xs[ids < 7]
        ext = np.concatenate([[0.], xs_f, [0.]])
        ref = (ext[:-2]+ext[1:-1]+ext[2:])/3
        assert np.allclose(t.to_numpy()["s"], ref, atol=1e-4)
        st = df.sort("x").collect().to_numpy()
        assert np.allclose(st["x"], np.sort(xs))
    """)


def test_kernel_path_8dev():
    run8("""
        rng = np.random.default_rng(4)
        n = 640
        ids = rng.integers(0, 23, n).astype(np.int32)
        xs = rng.normal(size=n).astype(np.float32)
        df = hf.table({"id": ids, "x": xs})
        cfg = hf.ExecConfig(use_kernels=True)
        a = hf.aggregate(df, "id", s=hf.sum_(df["x"])).collect(cfg).to_numpy()
        o = np.argsort(a["id"]); uids = np.unique(ids)
        assert np.allclose(a["s"][o], [xs[ids==u].sum() for u in uids], atol=1e-3)
    """)


def test_gradient_compression_8dev():
    run8("""
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.optim import compression
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        g_local = np.stack([np.full((64,), i, np.float32) for i in range(8)])
        def f(g, e):
            return compression.compressed_psum(g, e, ("data",))
        out, err = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=(P("data"), P("data")), check_vma=False))(
            jnp.asarray(g_local.reshape(-1)),
            jnp.zeros((8*64,), jnp.float32))
        got = np.asarray(out).reshape(8, 64)
        # mean over devices of values 0..7 = 3.5
        assert np.allclose(got, 3.5, atol=0.1), got[:, 0]
    """)


def test_elastic_checkpoint_reshard():
    """Save on 8 devices, restore on 4 — elastic reshard through checkpoint."""
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        run8(f"""
            import jax.numpy as jnp
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
            from repro.checkpoint import save
            mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
            sh = NamedSharding(mesh, P("data"))
            tree = {{"w": jax.device_put(jnp.arange(64, dtype=jnp.float32), sh)}}
            save("{d}", 5, tree)
        """)
        run8(f"""
            import jax.numpy as jnp
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
            from repro.checkpoint import restore
            mesh = Mesh(np.array(jax.devices()).reshape(4), ("data",))
            sh = NamedSharding(mesh, P("data"))
            template = {{"w": jnp.zeros(64, jnp.float32)}}
            tree, step, _ = restore("{d}", template, shardings={{"w": sh}})
            assert step == 5
            assert np.allclose(np.asarray(tree["w"]), np.arange(64))
            assert len(tree["w"].sharding.device_set) == 4
        """, devices=4)


def test_small_mesh_model_lowering():
    """pjit train step with model+data axes on 8 fake devices lowers & runs."""
    run8("""
        import jax.numpy as jnp
        from repro.configs import get_reduced, ShapeSpec
        from repro.launch import steps as S
        from repro.launch.mesh import make_local_mesh
        from repro.models import lm
        from repro.optim import OptConfig, adamw
        mesh = make_local_mesh(model_axis=2)
        cfg = get_reduced("qwen3-0.6b")
        shape = ShapeSpec("t", "train", 32, 8)
        ocfg = OptConfig()
        cell = S.cell_shardings(cfg, shape, mesh, ocfg)
        fn = S.make_train_step(cfg, ocfg, n_micro=2)
        params = jax.device_put(lm.init_params(cfg, jax.random.PRNGKey(0)),
                                cell["params"])
        opt = adamw.init_state(params, ocfg)
        state = {"params": params, "opt": opt}
        toks = jnp.zeros((8, 32), jnp.int32)
        batch = {"tokens": toks, "labels": toks}
        with mesh:
            st2, loss = jax.jit(fn)(state, batch)
        assert np.isfinite(float(loss))
    """)
