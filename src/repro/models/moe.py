"""Mixture-of-Experts with capacity-based, sort-free dispatch.

The dispatch problem — route a data-dependent number of tokens to each expert
shard under a static-shape compiler — is EXACTLY the paper's 1D_VAR problem,
and the solution is the same static-capacity + validity-count scheme as
core.physical.exchange (DESIGN.md §3): tokens are ranked within their target
expert (the hash_partition pattern), clamped to a per-expert capacity, and
scattered into an (E, C, d) buffer that is expert-sharded over the "model"
mesh axis (EP).  Overflowed tokens are dropped (standard capacity-factor MoE
semantics) and their probability mass is renormalized away.

Shared experts (DeepSeek-MoE / Kimi lineage) are plain always-on SwiGLU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .layers import swiglu

# Expert-parallel mesh registry: set by the launcher (steps/dryrun) so the
# optimized EP dispatch path can shard_map over the "model" axis.  None ->
# the GSPMD-auto path (the recorded baseline; see EXPERIMENTS.md §Perf).
_EP_MESH = None


def set_ep_mesh(mesh):
    global _EP_MESH
    _EP_MESH = mesh


def get_ep_mesh():
    return _EP_MESH


def expert_capacity(cfg: ModelConfig, n_tokens: int) -> int:
    cap = int(np.ceil(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    return max(8, -(-cap // 8) * 8)                    # round to sublane


def moe_block(p: dict, x, cfg: ModelConfig):
    """Dispatch to the EP shard_map path when a mesh is registered and the
    config asks for it; otherwise the GSPMD-auto baseline."""
    mesh = _EP_MESH
    if (getattr(cfg, "moe_impl", "gspmd") == "ep" and mesh is not None
            and "model" in mesh.axis_names
            and cfg.n_experts % mesh.shape["model"] == 0):
        return _moe_block_ep(p, x, cfg, mesh)
    return _moe_block_gspmd(p, x, cfg)


def _moe_block_gspmd(p: dict, x, cfg: ModelConfig):
    """x: (B, S, d) -> (out, aux_loss).

    p: router (d, E); experts {w_gate,w_up,w_down: (E, d, ff)/(E, ff, d)};
    optional shared {w_gate,w_up,w_down}.
    """
    b, s, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = b * s
    dt = x.dtype
    xt = x.reshape(T, d)

    logits = (xt @ p["router"].astype(dt)).astype(jnp.float32)   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = lax.top_k(probs, k)                   # (T, k)
    topw = topw / jnp.maximum(jnp.sum(topw, axis=-1, keepdims=True), 1e-9)

    # aux load-balancing loss (Switch-style)
    me = jnp.mean(probs, axis=0)                       # (E,)
    ce = jnp.mean(jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)

    # --- dispatch: 1D_VAR-style capacity + rank (no argsort) ---------------
    C = expert_capacity(cfg, T)
    flat_e = topi.reshape(T * k)                       # (Tk,)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    flat_w = topw.reshape(T * k)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)          # (Tk, E)
    ranks = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    keep = ranks < C
    slot = jnp.where(keep, ranks, C)

    buf = jnp.zeros((E, C + 1, d), dt)
    buf = buf.at[flat_e, slot].set(xt[flat_t], mode="drop")
    buf = buf[:, :C]                                   # (E, C, d)

    # --- expert computation (EP over the "model" axis) ---------------------
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["experts"]["w_gate"].astype(dt)))
    u = jnp.einsum("ecd,edf->ecf", buf, p["experts"]["w_up"].astype(dt))
    eo = jnp.einsum("ecf,efd->ecd", g * u, p["experts"]["w_down"].astype(dt))

    # --- combine ------------------------------------------------------------
    contrib = eo[flat_e, jnp.minimum(slot, C - 1)]     # (Tk, d)
    contrib = contrib * (flat_w * keep.astype(jnp.float32)).astype(dt)[:, None]
    y = jnp.zeros((T, d), dt).at[flat_t].add(contrib)

    if "shared" in p:
        y = y + swiglu(p["shared"], xt, dt)
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# Optimized EP dispatch (§Perf iteration 1 — see EXPERIMENTS.md)
# ---------------------------------------------------------------------------


def _moe_block_ep(p: dict, x, cfg: ModelConfig, mesh):
    """Expert-parallel dispatch via shard_map — the HiFrames 1D_VAR scheme.

    The GSPMD-auto baseline replicates the data-dependent scatter dispatch
    across the model axis (TBs of all-gather — the measured baseline).  Here
    the block-input activations are ALREADY replicated over "model" (standard
    TP), so each model shard simply SELECTS the token copies routed to its
    local experts — static capacity + within-expert rank, exactly the
    hash_partition/compact pattern of core.physical — computes its expert
    matmuls, and contributes partial outputs through ONE psum.  Per-layer
    collective volume drops from O(E·C·d) all-gathers to one (T_loc, d)
    all-reduce.  Capacity is per (expert, data-shard) rather than global —
    standard per-device-capacity MoE semantics (noted in DESIGN.md).
    """
    b, s, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    M = mesh.shape["model"]
    E_loc = E // M
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dt = x.dtype

    def fn(x_loc, router, experts):
        bl = x_loc.shape[0]
        T = bl * s
        xt = x_loc.reshape(T, d)
        logits = (xt @ router.astype(dt)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        topw, topi = lax.top_k(probs, k)
        topw = topw / jnp.maximum(jnp.sum(topw, axis=-1, keepdims=True), 1e-9)

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32), axis=0)
        if dp:   # product of GLOBAL means (matches the baseline exactly)
            me = lax.pmean(me, dp)
            ce = lax.pmean(ce, dp)
        aux = E * jnp.sum(me * ce)

        m_idx = lax.axis_index("model")
        flat_e = topi.reshape(T * k)
        flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
        flat_w = topw.reshape(T * k)
        le = flat_e - m_idx * E_loc
        mine = (le >= 0) & (le < E_loc)
        le_c = jnp.where(mine, le, E_loc)
        onehot = jax.nn.one_hot(le_c, E_loc, dtype=jnp.int32)   # row E_loc -> 0
        ranks = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
        C = expert_capacity(cfg, T)
        keep = mine & (ranks < C)
        slot = jnp.where(keep, ranks, C)

        buf = jnp.zeros((E_loc + 1, C + 1, d), dt)
        buf = buf.at[le_c, slot].set(xt[flat_t], mode="drop")
        buf = buf[:E_loc, :C]

        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf,
                                   experts["w_gate"].astype(dt)))
        u = jnp.einsum("ecd,edf->ecf", buf, experts["w_up"].astype(dt))
        eo = jnp.einsum("ecf,efd->ecd", g * u,
                        experts["w_down"].astype(dt))

        contrib = eo[jnp.minimum(le_c, E_loc - 1), jnp.minimum(slot, C - 1)]
        contrib = contrib * (flat_w * keep.astype(jnp.float32)).astype(dt)[:, None]
        y = jnp.zeros((T, d), dt).at[flat_t].add(contrib)
        y = lax.psum(y, "model")
        return y.reshape(bl, s, d), aux

    x_spec = P(dp if dp else None, None, None)
    e_spec = jax.tree.map(lambda _: P("model", None, None), p["experts"])
    y, aux = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(x_spec, P(), e_spec),
        out_specs=(x_spec, P()), check_vma=False,
    )(x, p["router"], p["experts"])

    if "shared" in p:
        y = y + swiglu(p["shared"], x.reshape(b * s, d), dt).reshape(b, s, d)
    return y, aux


def moe_param_shapes(cfg: ModelConfig) -> dict:
    d, E, ff = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    shapes = {
        "router": (d, E),
        "experts": {"w_gate": (E, d, ff), "w_up": (E, d, ff),
                    "w_down": (E, ff, d)},
    }
    if cfg.n_shared_experts:
        sf = ff * cfg.n_shared_experts
        shapes["shared"] = {"w_gate": (d, sf), "w_up": (d, sf),
                            "w_down": (sf, d)}
    return shapes
