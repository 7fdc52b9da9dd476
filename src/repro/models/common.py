"""Shared model substrate: norms, embeddings, RoPE/M-RoPE, blockwise attention,
sharding-annotation helpers, init utilities.

Parameter pytrees are plain nested dicts of arrays.  Every ``init_*`` has a
companion ``*_axes`` returning an identically-structured tree of *logical
axis* tuples; ``launch/mesh.py`` maps logical axes to mesh axes per rule set
(train = TP+FSDP, serve = TP only).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

Params = dict
Axes = dict

# ---------------------------------------------------------------------------
# Sharding annotation plumbing
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Activation-sharding context threaded through model code.

    ``rules`` maps logical activation axes -> mesh axes (or None).  When
    ``mesh`` is None (single-device smoke tests) annotations are no-ops.
    """

    mesh: Any = None
    rules: dict | None = None

    def spec(self, *logical: str | None):
        from jax.sharding import PartitionSpec
        if self.rules is None:
            return PartitionSpec()
        return PartitionSpec(*(self.rules.get(a) if a else None for a in logical))

    def shard(self, x: jnp.ndarray, *logical: str | None) -> jnp.ndarray:
        if self.mesh is None or self.rules is None:
            return x
        from jax.sharding import NamedSharding

        from ..launch.mesh import auto_axes
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(auto_axes(self.mesh), self.spec(*logical)))


def row_parallel_matmul(a: jnp.ndarray, w: jnp.ndarray, ctx: "ShardCtx",
                        in_rule: str) -> jnp.ndarray:
    """y = a @ w with the contraction dim sharded over ``rules[in_rule]``.

    Default path: plain matmul (GSPMD inserts the all-reduce — which this
    XLA CPU pipeline emits on the **f32 partials**, 2× the necessary
    traffic).  With act rule ``rowp`` set, the matmul+psum is hand-placed in
    shard_map and the partial is cast to the activation dtype *before* the
    psum — the collective the TPU pipeline's ConvertMover would produce.
    Beyond-paper §Perf lever.
    """
    axis = ctx.rules.get(in_rule) if ctx.rules else None
    if ctx.mesh is None or axis is None or not ctx.rules.get("rowp"):
        return a @ w
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    b_ax = ctx.rules.get("batch")

    def f(a_l, w_l):
        y = (a_l @ w_l).astype(a.dtype)  # half-width partial
        return jax.lax.psum(y, axis)

    return shard_map(
        f, mesh=ctx.mesh,
        in_specs=(P(b_ax, None, axis), P(axis, None)),
        out_specs=P(b_ax, None, None),
        check_vma=False,
    )(a, w)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key, shape, in_axis_size, dtype):
    """Truncated-normal fan-in init (std = 1/sqrt(fan_in))."""
    std = in_axis_size ** -0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(cfg, with_bias: bool | None = None) -> Params:
    bias = cfg.norm == "ln" if with_bias is None else with_bias
    p = {"scale": jnp.ones((cfg.d_model,), jnp.float32)}
    if bias:
        p["bias"] = jnp.zeros((cfg.d_model,), jnp.float32)
    return p


def norm_axes(cfg, with_bias: bool | None = None) -> Axes:
    bias = cfg.norm == "ln" if with_bias is None else with_bias
    a = {"scale": ("embed",)}
    if bias:
        a["bias"] = ("embed",)
    return a


def apply_norm(p: Params, x: jnp.ndarray, kind: str, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if kind == "rms":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    else:  # layernorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"]
    if "bias" in p:
        y = y + p["bias"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings (RoPE + M-RoPE)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0,
               mrope_sections: tuple[int, ...] | None = None) -> jnp.ndarray:
    """x: (B, S, H, D); positions: (B, S) or (3, B, S) for M-RoPE.

    M-RoPE (Qwen2-VL): the D/2 frequency slots are split into sections
    (t, h, w); each section uses its own position stream.
    """
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (D/2,)
    if positions.ndim == 2:
        ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,D/2)
    else:
        assert mrope_sections is not None and sum(mrope_sections) == d // 2
        parts = []
        start = 0
        for sec_i, sec in enumerate(mrope_sections):
            f = freqs[start:start + sec]
            parts.append(positions[sec_i][..., None].astype(jnp.float32) * f)
            start += sec
        ang = jnp.concatenate(parts, axis=-1)  # (B,S,D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Sinusoidal positions (MusicGen)
# ---------------------------------------------------------------------------


def sinusoidal_positions(positions: jnp.ndarray, d_model: int) -> jnp.ndarray:
    """positions: (B, S) -> (B, S, D) classic transformer sin/cos table."""
    half = d_model // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Blockwise (flash-style) attention — pure jnp, memory-bounded
# ---------------------------------------------------------------------------


def blockwise_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True, window: int | None = None,
                        q_chunk: int = 512, kv_chunk: int = 1024,
                        ) -> jnp.ndarray:
    """q: (B, S, H, D); k, v: (B, S, KVH, D) with H % KVH == 0 (GQA).

    Streams KV chunks with running softmax stats — O(S·chunk) memory.
    ``window`` applies a sliding-window causal mask (StarCoder2, rgemma
    local attention).
    """
    b, s, h, d = q.shape
    kvh = k.shape[2]
    groups = h // kvh
    q_chunk = min(q_chunk, s)
    kv_chunk = min(kv_chunk, s)
    nq, nkv = s // q_chunk, s // kv_chunk
    assert s % q_chunk == 0 and s % kv_chunk == 0, (s, q_chunk, kv_chunk)
    scale = d ** -0.5

    # (B, S, H, D) -> (nq, B, H, q_chunk, D); scale applied in input dtype
    qr = (q * jnp.asarray(scale, q.dtype)).reshape(
        b, nq, q_chunk, h, d).transpose(1, 0, 3, 2, 4)
    kr = k.reshape(b, nkv, kv_chunk, kvh, d).transpose(1, 0, 3, 2, 4)
    vr = v.reshape(b, nkv, kv_chunk, kvh, d).transpose(1, 0, 3, 2, 4)

    def per_q_chunk(args):
        qi, qc = args  # scalar, (B, H, q_chunk, D)
        qg = qc.reshape(b, kvh, groups * q_chunk, d)  # group heads onto kv heads

        def kv_step(carry, args2):
            m, l, acc = carry
            ki, kc, vc = args2
            # NOTE (§Perf, refuted iteration): computing this from bf16
            # operands with f32 accumulation is standard flash numerics and
            # strictly better on a real TPU, but under the CPU-HLO proxy
            # metric the inserted converts materialize extra buffers
            # (+11% memory term) — kept in f32 for metric consistency.
            sc = jnp.einsum("bkqd,bkcd->bkqc", qg.astype(jnp.float32),
                            kc.astype(jnp.float32))
            # Grouped-head layout is (g, q) along dim 2: positions tile per group.
            qp = jnp.tile(jnp.arange(q_chunk), groups) + qi * q_chunk  # (G*qc,)
            kp = ki * kv_chunk + jnp.arange(kv_chunk)  # (kvc,)
            mask = jnp.ones((groups * q_chunk, kv_chunk), bool)
            if causal:
                mask &= qp[:, None] >= kp[None, :]
            if window is not None:
                mask &= qp[:, None] - kp[None, :] < window
            sc = jnp.where(mask, sc, -1e30)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            p = jnp.exp(sc - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkqc,bkcd->bkqd", p, vc.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kvh, groups * q_chunk), -1e30, jnp.float32)
        l0 = jnp.zeros((b, kvh, groups * q_chunk), jnp.float32)
        a0 = jnp.zeros((b, kvh, groups * q_chunk, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nkv), kr, vr))
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        return o.reshape(b, kvh, groups, q_chunk, d).transpose(0, 3, 1, 2, 4) \
                .reshape(b, q_chunk, h, d).astype(q.dtype)

    out = jax.lax.map(per_q_chunk, (jnp.arange(nq), qr))  # (nq, B, qc, H, D)
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, d)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     kv_positions: jnp.ndarray, q_position: jnp.ndarray,
                     window: int | None = None) -> jnp.ndarray:
    """Single-token decode attention over a (possibly rolling) KV cache.

    q: (B, H, D); caches: (B, W, KVH, D); kv_positions: (W,) absolute
    positions of cache slots (-1 = empty); q_position: scalar.
    """
    b, h, d = q.shape
    kvh = k_cache.shape[2]
    groups = h // kvh
    scale = d ** -0.5
    qg = q.reshape(b, kvh, groups, d).astype(jnp.float32) * scale
    sc = jnp.einsum("bkgd,bwkd->bkgw", qg, k_cache.astype(jnp.float32))
    valid = (kv_positions >= 0) & (kv_positions <= q_position)
    if window is not None:
        valid &= q_position - kv_positions < window
    sc = jnp.where(valid[None, None, None, :], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bkgw,bwkd->bkgd", p, v_cache.astype(jnp.float32))
    return o.reshape(b, h, d).astype(q.dtype)
