"""Feed-forward blocks: dense (SwiGLU / GEGLU / GELU) and Mixture-of-Experts.

MoE uses **replicated-activation expert parallelism** inside ``shard_map``:
activations are sharded over the data axes and replicated over `model`, while
experts are sharded over `model`.  Dispatch is therefore a *local* gather
(each device selects, from its replicated token shard, the tokens routed to
its resident experts, up to capacity) and combine is a single `psum` over
`model` — the same collective a dense row-parallel MLP needs.  No all-to-all,
no (T, E, C) dispatch tensors.  This is the ESOP philosophy at the routing
level: tokens that a device's experts don't own are never fetched/computed.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .common import ShardCtx, apply_norm, dense_init, init_norm, norm_axes

# ---------------------------------------------------------------------------
# Dense MLP
# ---------------------------------------------------------------------------


def init_mlp(key, cfg, block) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    dt = cfg.param_dtype
    p = {"norm": init_norm(cfg), "w_down": dense_init(ks[2], (f, d), f, dt)}
    if block.mlp in ("swiglu", "geglu"):
        p["w_gate"] = dense_init(ks[0], (d, f), d, dt)
        p["w_up"] = dense_init(ks[1], (d, f), d, dt)
    else:  # gelu
        p["w_up"] = dense_init(ks[1], (d, f), d, dt)
    return p


def mlp_axes(cfg, block) -> dict:
    a = {"norm": norm_axes(cfg), "w_down": ("mlp", "embed"),
         "w_up": ("embed", "mlp")}
    if block.mlp in ("swiglu", "geglu"):
        a["w_gate"] = ("embed", "mlp")
    return a


def apply_mlp(p, x, cfg, block, ctx: ShardCtx) -> jnp.ndarray:
    h = apply_norm(p["norm"], x, cfg.norm)
    if block.mlp == "swiglu":
        a = jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])
    elif block.mlp == "geglu":
        a = jax.nn.gelu(h @ p["w_gate"]) * (h @ p["w_up"])
    else:
        a = jax.nn.gelu(h @ p["w_up"])
    a = ctx.shard(a, "batch", None, "mlp_act")
    from .common import row_parallel_matmul
    y = row_parallel_matmul(a, p["w_down"], ctx, "mlp_act")
    return ctx.shard(y, "batch", "seq_act", None)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------


def init_moe(key, cfg, block) -> dict:
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    ks = jax.random.split(key, 7)
    dt = cfg.param_dtype
    p = {
        "norm": init_norm(cfg),
        "w_router": dense_init(ks[0], (d, e), d, jnp.float32),
        "w_gate": dense_init(ks[1], (e, d, f), d, dt),
        "w_up": dense_init(ks[2], (e, d, f), d, dt),
        "w_down": dense_init(ks[3], (e, f, d), f, dt),
    }
    if cfg.n_shared_experts:
        fs = cfg.moe_d_ff * cfg.n_shared_experts
        p["ws_gate"] = dense_init(ks[4], (d, fs), d, dt)
        p["ws_up"] = dense_init(ks[5], (d, fs), d, dt)
        p["ws_down"] = dense_init(ks[6], (fs, d), fs, dt)
    return p


def moe_axes(cfg, block) -> dict:
    a = {
        "norm": norm_axes(cfg),
        "w_router": ("embed", None),
        # expert_mlp is deliberately distinct from the dense "mlp" logical
        # axis: experts are already TP'd on the expert axis.
        "w_gate": ("expert", "embed", "expert_mlp"),
        "w_up": ("expert", "embed", "expert_mlp"),
        "w_down": ("expert", "expert_mlp", "embed"),
    }
    if cfg.n_shared_experts:
        a.update(ws_gate=("embed", "mlp"), ws_up=("embed", "mlp"),
                 ws_down=("mlp", "embed"))
    return a


def _capacity(t_local: int, cfg) -> int:
    c = math.ceil(t_local * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(1, min(t_local, max(c, min(t_local, 16))))


def _moe_local(t, vals, idx, w_gate, w_up, w_down, first_e: jnp.ndarray,
               capacity: int, cfg):
    """Dispatch/compute/combine for the experts resident on this device.

    t: (T, D) tokens; vals/idx: (T, K) top-k gates & expert ids;
    w_*: (E_l, ...) local expert weights; first_e: global id of expert 0.
    Returns the partial output (T, D) — caller psums over the expert axis.
    """
    e_l = w_gate.shape[0]
    tcount, _ = t.shape

    def one_expert(we_gate, we_up, we_down, e_off):
        e_id = first_e + e_off
        match = idx == e_id  # (T, K)
        m = jnp.any(match, axis=1)  # (T,)
        gate = jnp.sum(jnp.where(match, vals, 0.0), axis=1)  # (T,)
        # Stable priority order: routed tokens first, then position.
        order = jnp.argsort(jnp.where(m, 0, 1) * tcount + jnp.arange(tcount))
        take = order[:capacity]  # (C,) token ids (padded w/ unrouted)
        took = m[take]
        xe = t[take] * took[:, None].astype(t.dtype)  # (C, D)
        h = jax.nn.silu(xe @ we_gate) * (xe @ we_up)
        ye = (h @ we_down) * (gate[take] * took)[:, None].astype(t.dtype)
        return take, ye

    take, ye = jax.vmap(one_expert)(
        w_gate, w_up, w_down, jnp.arange(e_l))
    out = jnp.zeros_like(t)
    out = out.at[take.reshape(-1)].add(ye.reshape(-1, t.shape[1]))
    return out


def apply_moe(p, x, cfg, block, ctx: ShardCtx) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y, aux_loss)."""
    b, s, d = x.shape
    h = apply_norm(p["norm"], x, cfg.norm)
    t_global = h.reshape(-1, d)

    # Router (tiny): computed in the auto-sharded region, fp32.
    logits = t_global.astype(jnp.float32) @ p["w_router"]
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, cfg.top_k)
    vals = (vals / jnp.sum(vals, -1, keepdims=True)).astype(x.dtype)

    # Load-balancing aux loss (Switch-style), fp32.
    e = cfg.n_experts
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # (T, K, E)
    frac_tokens = jnp.mean(jnp.sum(onehot, axis=1), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = cfg.router_aux_coef * e * jnp.sum(frac_tokens * frac_probs)

    expert_axis = ctx.rules.get("expert") if ctx.rules else None
    if ctx.mesh is None or expert_axis is None:
        part = _moe_local(t_global, vals, idx, p["w_gate"], p["w_up"],
                          p["w_down"], jnp.int32(0),
                          _capacity(t_global.shape[0], cfg), cfg)
        y = part.reshape(b, s, d)
    else:
        mesh = ctx.mesh
        batch_axis = ctx.rules.get("batch")
        tspec = P(batch_axis, None)
        ep = _axis_prod(mesh, expert_axis)
        ep_names = (expert_axis if isinstance(expert_axis, tuple)
                    else (expert_axis,))
        t_local_n = t_global.shape[0] // _axis_prod(mesh, batch_axis)
        capacity = _capacity(t_local_n, cfg)

        def inner(t_l, vals_l, idx_l, wg, wu, wd):
            idx0 = jnp.zeros((), jnp.int32)
            for name in ep_names:  # row-major index over the EP axes
                idx0 = idx0 * mesh.shape[name] + jax.lax.axis_index(name)
            first_e = idx0 * (cfg.n_experts // ep)
            part = _moe_local(t_l, vals_l, idx_l, wg, wu, wd, first_e,
                              capacity, cfg)
            return jax.lax.psum(part, ep_names)

        y = shard_map(
            inner, mesh=mesh,
            in_specs=(tspec, tspec, tspec,
                      P(expert_axis, None, None), P(expert_axis, None, None),
                      P(expert_axis, None, None)),
            out_specs=tspec,
            check_vma=False,
        )(t_global, vals, idx, p["w_gate"], p["w_up"], p["w_down"])
        y = y.reshape(b, s, d)

    if cfg.n_shared_experts:
        a = jax.nn.silu(h @ p["ws_gate"]) * (h @ p["ws_up"])
        y = y + a @ p["ws_down"]
    return ctx.shard(y, "batch", "seq_act", None), aux


def _axis_prod(mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        out = 1
        for a in axis:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axis]
