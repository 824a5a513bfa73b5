"""Compile rehearsals for a TPU v5e that is described, not attached.

The TPU compiler is installed with JAX, and compiles for a ``v5e:2x2``
topology that is only described: nothing runs, but what the chip's compiler
refuses (a kernel block it cannot tile, an in-kernel primitive Mosaic does
not lower, a plan larger than a chip's 16 GiB) fails here at no chip time.

  * every registered Pallas kernel compiles for one chip in "compiled" mode
    at the per-shard size ``chip_smoke.py`` gives it (``bucket_scatter`` at
    P=4, the stencils with a 3- and a 200-tap window) and lands in the
    program as a ``tpu_custom_call``;
  * the one-chip smoke's Q26 plan, at its size, fits a chip's memory;
  * the four-chip smoke's Q26 really exchanges (all-to-all in the compiled
    HLO) and fits each chip's memory;
  * ``use_pallas="interpret"`` plans but does not compile for a TPU, and a
    kernel that Mosaic refuses surfaces as a typed KernelBackendError.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro import hiframes as hf
from repro.configs.hiframes_tpcx import SF100, V5E_SMOKE_FRACTION
from repro.core import errors as err, ir
from repro.core.api import DataFrame
from repro.kernels import registry as kreg
from repro.launch.serve import build_mix

HBM_BYTES = 16 * 2**30
W3 = (0.25, 0.5, 0.25)
W200 = tuple(np.linspace(0.1, 1.0, 200).tolist())   # a wide rolling window
SMOKE_1 = SF100.scaled(V5E_SMOKE_FRACTION[1])
SMOKE_4 = SF100.scaled(V5E_SMOKE_FRACTION[4])


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler or library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_cases(name: str, n: int):
    """[(fn, argument shapes)] calling kernel ``name`` of the compiled set;
    the stencils with a narrow and a wide window."""
    ks = kreg.resolve("compiled")
    i32 = (n,), jnp.int32
    f32 = (n,), jnp.float32

    def ext(w, dt=jnp.float32):
        return (n + len(w) - 1,), dt

    cases = {
        "prefix_sum": [(ks.prefix_sum, [i32])],
        "segment_scan": [(ks.segment_scan, [f32, i32])],
        "segment_rank": [(functools.partial(ks.segment_rank, kind="rank"),
                          [i32, i32])],
        "segment_sums": [(functools.partial(ks.segment_sums, num_segments=n),
                          [f32, i32, ((n,), jnp.bool_)])],
        "bucket_scatter": [(functools.partial(ks.bucket_scatter, P=4),
                            [i32])],
        "stencil1d": [(functools.partial(ks.stencil1d, weights=w), [ext(w)])
                      for w in (W3, W200)],
        "stencil1d_exact": [(functools.partial(ks.stencil1d_exact, weights=w),
                             [ext(w), ext(w)]) for w in (W3, W200)],
        "segment_stencil": [(functools.partial(ks.segment_stencil, weights=w,
                                               center=len(w) // 2, exact=True),
                             [ext(w), ext(w, jnp.int32)])
                            for w in (W3, W200)],
    }
    return cases[name]


@pytest.mark.parametrize("name", kreg.names())
def test_kernel_compiles_for_v5e(name, one_chip):
    for fn, shapes in _kernel_cases(name, SMOKE_1.store_sales_rows):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()


def _store_sales(rows: int):
    cols = ("ss_item_sk", "ss_customer_sk", "ss_ticket_number")
    shapes = {c: jax.ShapeDtypeStruct((rows,), jnp.int32) for c in cols}
    shapes["ss_net_paid"] = jax.ShapeDtypeStruct((rows,), jnp.float32)
    return hf.table(shapes, "store_sales")


def _item(items: int):
    return hf.table({c: jax.ShapeDtypeStruct((items,), jnp.int32)
                     for c in ("i_item_sk", "i_class_id", "i_category_id")},
                    "item").replicate()


def _cfg(topo, chips: int, mode: str = "off"):
    mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
    return hf.ExecConfig(mesh=mesh, use_pallas=mode)


def test_one_chip_q26_fits_hbm(topo):
    """The smoke's Q26, with its Pallas kernels compiled, at the one-chip
    smoke size: arguments, temporaries and outputs fit 16 GiB."""
    mix = build_mix(_store_sales(SMOKE_1.store_sales_rows),
                    _item(SMOKE_1.items))
    low = mix["q26"]().lower(_cfg(topo, 1, "compiled"))
    compiled = low.compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
             + ma.output_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 2**30:.2f} GiB"
    assert "tpu_custom_call" in compiled.as_text()


def _registered_store_sales(mesh, rows: int):
    """``store_sales`` as ``Session.register(partition_by="ss_item_sk")``
    leaves it, abstractly: hash-partitioned device shards whose capacity,
    under safe_capacities, is every row of the table."""
    sharding = NamedSharding(mesh, PartitionSpec("data"))
    P = mesh.devices.size
    shapes = {c: jax.ShapeDtypeStruct((P * rows,), dt, sharding=sharding)
              for c, dt in (("ss_item_sk", jnp.int32),
                            ("ss_customer_sk", jnp.int32),
                            ("ss_ticket_number", jnp.int32),
                            ("ss_net_paid", jnp.float32))}
    layout = ir.ScanLayout(kind="hash", partitioned_by=("ss_item_sk",),
                           counts=np.full(P, rows // P, np.int32),
                           capacity=rows, nshards=P)
    return DataFrame(ir.Scan("store_sales", shapes, layout=layout))


def test_four_chip_q26_exchanges_within_hbm(topo):
    """The four-chip smoke's Q26 over the registered table: its exchange
    really runs (all-to-all in the compiled HLO) and its worst-case buffers
    fit each chip's memory.  At five times this size they did not (a chip
    run asked for 16.45G of 15.75G)."""
    cfg = _cfg(topo, 4)
    mix = build_mix(_registered_store_sales(cfg.mesh,
                                            SMOKE_4.store_sales_rows),
                    _item(SMOKE_4.items))
    compiled = mix["q26"]().lower(cfg).compile()
    assert "all-to-all" in compiled.as_text()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
             + ma.output_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 2**30:.2f} GiB"


def test_interpret_mode_plans_but_does_not_compile_for_tpu(topo):
    df = _store_sales(4096)
    low = df[df["ss_net_paid"] > 1.0].lower(_cfg(topo, 1, "interpret"))
    assert low.kernels.mode_of("prefix_sum") == "interpret"
    with pytest.raises(ValueError, match="interpret"):
        low.compile()


def _refused_prefix_sum(x, interpret=False):
    """A prefix sum in the shape Mosaic refuses: a 1-D block that is not the
    whole array, a scalar VMEM carry and an in-kernel cumsum."""
    def kernel(x_ref, o_ref, carry):
        @pl.when(pl.program_id(0) == 0)
        def _init():
            carry[0] = jnp.zeros((), x_ref.dtype)

        c = jnp.cumsum(x_ref[...])
        o_ref[...] = c + carry[0]
        carry[0] = carry[0] + c[-1]

    block = 1000
    nb = -(-x.shape[0] // block)
    xp = jnp.pad(x, (0, nb * block - x.shape[0]))
    return pl.pallas_call(
        kernel, grid=(nb,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct(xp.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((1,), x.dtype)],
        interpret=interpret)(xp)[:x.shape[0]]


def test_mosaic_refusal_is_a_typed_kernel_error(topo, monkeypatch):
    spec = kreg.get("prefix_sum")
    monkeypatch.setitem(kreg._REGISTRY, "prefix_sum",
                        kreg.KernelSpec("prefix_sum", spec.ref,
                                        _refused_prefix_sum))
    df = _store_sales(4096)
    low = df[df["ss_net_paid"] > 1.0].lower(_cfg(topo, 1, "compiled"))
    with pytest.raises(err.KernelBackendError) as info:
        low.compile()
    assert (info.value.kernel, info.value.backend) == ("prefix_sum",
                                                        "compiled")
