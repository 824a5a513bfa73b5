"""Typed kernel registry: every execution hot-path primitive, two backends.

Each primitive is registered once with a ``ref`` implementation (the pure
lax/jnp composition that used to live inline in ``core/physical.py``) and a
``pallas`` implementation (a fused Pallas kernel from a sibling subpackage).
``core.lower.Lowered`` resolves the whole table to a :class:`KernelSet` from
the single ``ExecConfig.use_pallas`` lever:

  "off"       -> every primitive is its ref composition (bit-for-bit the
                 pre-registry numerics)
  "interpret" -> Pallas kernels under the interpreter (CPU CI / debugging)
  "compiled"  -> Pallas kernels compiled for the accelerator (TPU)

The backends are numerics-only swaps: the physical planner never sees the
mode, so plans, exchanges and collective counts are identical across all
three (asserted by the census gate in ``tests/test_kernel_registry.py``).

Registered primitives and their contracts:

  prefix_sum(x)                         dtype-preserving inclusive scan
  segment_scan(x, boundary)             segmented inclusive scan; boundary
                                        != 0 starts a segment
  segment_rank(seg_b, ord_b, kind)      1-based in-segment ranks (int32);
                                        kind static
  segment_sums(values, seg_id, valid, num_segments)
                                        per-segment sums of the valid prefix
  bucket_scatter(dest, P)               (slot, send_counts): stable
                                        within-bucket slot of every row at
                                        its ORIGINAL position; dest == P
                                        marks invalid rows (slot garbage,
                                        masked by callers)
  stencil1d(ext, weights)               weighted window over an extended
                                        (halo-carrying) array
  stencil1d_exact(ext, ext_m, weights)  stencil + mass renormalize, fused
  segment_stencil(ext, ext_s, weights, center, exact)
                                        partition-masked stencil (+ fused
                                        renormalize when exact)

To add a primitive: ship a ``ref.py`` oracle and a Pallas kernel whose jit'd
wrapper takes a trailing ``interpret`` keyword, then ``register()`` the pair
below.  ``tests/test_kernel_registry.py`` sweeps every registered name, so a
new primitive gets ref-vs-pallas parity coverage for free.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable

MODES = ("off", "interpret", "compiled")


@dataclass(frozen=True)
class KernelSpec:
    """One named primitive with its two backends."""
    name: str
    ref: Callable
    pallas: Callable


_REGISTRY: dict[str, KernelSpec] = {}


def register(name: str, *, ref: Callable, pallas: Callable) -> None:
    if name in _REGISTRY:
        raise ValueError(f"kernel {name!r} already registered")
    _REGISTRY[name] = KernelSpec(name, ref, pallas)


def names() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get(name: str) -> KernelSpec:
    return _REGISTRY[name]


class KernelSet:
    """The registry resolved for one backend mode.

    Primitives are attributes: ``kernels.prefix_sum(x)``.  In "off" mode the
    attribute IS the ref callable; otherwise it is the pallas callable with
    ``interpret`` pre-bound, so call sites are mode-oblivious.

    ``overrides`` maps kernel name -> mode, stepping INDIVIDUAL kernels off
    the global mode — the carrier of the per-kernel degradation ladder
    (:func:`downgrade`) the retry policy drives on
    :class:`~repro.core.errors.KernelBackendError`.  ``wrap`` is an optional
    ``wrap(name, mode, fn) -> fn`` hook applied to every resolved callable
    (error typing + fault injection, core/lower.py).
    """

    def __init__(self, mode: str, overrides: dict | None = None, wrap=None):
        if mode not in MODES:
            raise ValueError(
                f"use_pallas must be one of {MODES}, got {mode!r}")
        overrides = dict(overrides or {})
        bad = {m for m in overrides.values() if m not in MODES}
        if bad:
            raise ValueError(f"kernel fallback modes must be in {MODES}, "
                             f"got {sorted(bad)}")
        fns = {}
        modes = {}
        for name, spec in _REGISTRY.items():
            m = overrides.get(name, mode)
            if m == "off":
                fn = spec.ref
            else:
                fn = functools.partial(
                    spec.pallas, interpret=(m == "interpret"))
            if wrap is not None:
                fn = wrap(name, m, fn)
            fns[name] = fn
            modes[name] = m
        self.mode = mode
        self.kernel_modes = modes
        self._fns = fns

    def mode_of(self, name: str) -> str:
        """The backend mode ``name`` actually resolves to (after overrides)."""
        return self.kernel_modes[name]

    def __getattr__(self, name):
        try:
            return self.__dict__["_fns"][name]
        except KeyError:
            raise AttributeError(
                f"no kernel {name!r} registered (have: {names()})") from None

    def __repr__(self):
        return f"KernelSet(mode={self.mode!r}, kernels={names()})"


@functools.lru_cache(maxsize=None)
def resolve(mode: str) -> KernelSet:
    """KernelSet for a ``use_pallas`` mode; cached, one instance per mode."""
    return KernelSet(mode)


def resolve_with(mode: str, overrides: dict | None = None,
                 wrap=None) -> KernelSet:
    """KernelSet with per-kernel mode ``overrides`` and an optional ``wrap``
    hook.  Falls back to the cached plain set when neither is given."""
    if not overrides and wrap is None:
        return resolve(mode)
    return KernelSet(mode, overrides, wrap)


def downgrade(mode: str, platform: str) -> str | None:
    """The degradation ladder: the next-softer backend for a kernel that
    failed under ``mode`` on a ``platform`` device (None = exhausted).  On a
    TPU the interpreter is no rung — it would emulate the kernel on the host
    at a fraction of the speed — so ``compiled`` falls straight to ``off``;
    elsewhere ``compiled`` (which only a TPU can run) steps to
    ``interpret``."""
    if mode == "compiled":
        return "off" if platform == "tpu" else "interpret"
    if mode == "interpret":
        return "off"
    return None


# -- registrations -------------------------------------------------------------

from .hash_partition import ops as _hp_ops, ref as _hp_ref    # noqa: E402
from .segment_rank import ops as _rk_ops, ref as _rk_ref      # noqa: E402
from .segment_reduce import ops as _sr_ops, ref as _sr_ref    # noqa: E402
from .segment_scan import ops as _ss_ops, ref as _ss_ref      # noqa: E402
from .stencil1d import ops as _st_ops, ref as _st_ref         # noqa: E402
from .stream_compact import ops as _sc_ops, ref as _sc_ref    # noqa: E402

register("prefix_sum",
         ref=_sc_ref.prefix_sum_ref, pallas=_sc_ops.prefix_sum)
register("segment_scan",
         ref=_ss_ref.segment_scan_ref, pallas=_ss_ops.segment_scan)
register("segment_rank",
         ref=_rk_ref.segment_rank_ref, pallas=_rk_ops.segment_rank)
register("segment_sums",
         ref=_sr_ref.segment_sums_exact, pallas=_sr_ops.segment_sums)
register("bucket_scatter",
         ref=_hp_ref.bucket_ranks_argsort, pallas=_hp_ops.bucket_ranks)
register("stencil1d",
         ref=_st_ref.stencil1d_ref, pallas=_st_ops.stencil1d)
register("stencil1d_exact",
         ref=_st_ref.stencil1d_exact_ref, pallas=_st_ops.stencil1d_exact)
register("segment_stencil",
         ref=_st_ref.segment_stencil_ref, pallas=_st_ops.segment_stencil)

# The default backend: pure lax compositions (the "off" lever position).
REF = resolve("off")
