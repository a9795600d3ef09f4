"""Shared model primitives: norms, activations, RoPE, initializers.

All models are pure-functional: params are pytrees of jnp arrays, layer stacks
are stored with a leading ``L`` dim and consumed by ``jax.lax.scan``.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

Params = Any  # pytree


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


# ---------------------------------------------------------------- norms / act


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(dt)


def activation(name: str, x: jax.Array) -> jax.Array:
    if name == "gelu":
        return jax.nn.gelu(x)
    if name == "silu":
        return jax.nn.silu(x)
    if name == "relu_sq":
        return jnp.square(jax.nn.relu(x))
    raise ValueError(name)


def mlp_apply(p: Params, x: jax.Array, act: str) -> jax.Array:
    """SwiGLU (w_gate/w_up/w_down) or plain 2-layer MLP (w_in/w_out)."""
    dt = x.dtype
    if "w_gate" in p:
        g = jnp.einsum("...d,df->...f", x, p["w_gate"].astype(dt))
        u = jnp.einsum("...d,df->...f", x, p["w_up"].astype(dt))
        h = jax.nn.silu(g) * u
        return jnp.einsum("...f,fd->...d", h, p["w_down"].astype(dt))
    h = jnp.einsum("...d,df->...f", x, p["w_in"].astype(dt))
    h = activation(act, h)
    return jnp.einsum("...f,fd->...d", h, p["w_out"].astype(dt))


def mlp_init(rng, d_model: int, d_ff: int, act: str, dtype) -> Params:
    k1, k2, k3 = jax.random.split(rng, 3)
    s_in = 1.0 / np.sqrt(d_model)
    s_out = 1.0 / np.sqrt(d_ff)
    if act == "swiglu":
        return {
            "w_gate": (jax.random.normal(k1, (d_model, d_ff)) * s_in).astype(dtype),
            "w_up": (jax.random.normal(k2, (d_model, d_ff)) * s_in).astype(dtype),
            "w_down": (jax.random.normal(k3, (d_ff, d_model)) * s_out).astype(dtype),
        }
    return {
        "w_in": (jax.random.normal(k1, (d_model, d_ff)) * s_in).astype(dtype),
        "w_out": (jax.random.normal(k2, (d_ff, d_model)) * s_out).astype(dtype),
    }


def mlp_param_count(d_model: int, d_ff: int, act: str) -> int:
    return d_model * d_ff * (3 if act == "swiglu" else 2)


# ----------------------------------------------------------------------- RoPE


def rope_freqs(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    if not theta:
        return x
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta))          # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(ang)[..., None, :]                    # (..., S, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq_len: int, d_model: int) -> np.ndarray:
    pos = np.arange(seq_len, dtype=np.float32)[:, None]
    dim = np.arange(0, d_model, 2, dtype=np.float32)[None, :]
    ang = pos / np.power(10000.0, dim / d_model)
    out = np.zeros((seq_len, d_model), dtype=np.float32)
    out[:, 0::2] = np.sin(ang)
    out[:, 1::2] = np.cos(ang)
    return out


# ----------------------------------------------------------------- attention proj


def attn_proj_init(rng, cfg: ModelConfig, dtype, *, cross: bool = False) -> Params:
    kq, kk, kv, ko = jax.random.split(rng, 4)
    d, h, kvh, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    s = 1.0 / np.sqrt(d)
    so = 1.0 / np.sqrt(h * hd)
    return {
        "wq": (jax.random.normal(kq, (d, h * hd)) * s).astype(dtype),
        "wk": (jax.random.normal(kk, (d, kvh * hd)) * s).astype(dtype),
        "wv": (jax.random.normal(kv, (d, kvh * hd)) * s).astype(dtype),
        "wo": (jax.random.normal(ko, (h * hd, d)) * so).astype(dtype),
    }


def attn_param_count(cfg: ModelConfig) -> int:
    d, h, kvh, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return d * h * hd * 2 + d * kvh * hd * 2


def qkv_split(p: Params, x: jax.Array, cfg: ModelConfig):
    """x: (B, S, D) -> q (B,S,H,hd), k/v (B,S,KV,hd)."""
    dt = x.dtype
    b, s, _ = x.shape
    q = jnp.einsum("bsd,de->bse", x, p["wq"].astype(dt)).reshape(
        b, s, cfg.num_heads, cfg.head_dim
    )
    k = jnp.einsum("bsd,de->bse", x, p["wk"].astype(dt)).reshape(
        b, s, cfg.num_kv_heads, cfg.head_dim
    )
    v = jnp.einsum("bsd,de->bse", x, p["wv"].astype(dt)).reshape(
        b, s, cfg.num_kv_heads, cfg.head_dim
    )
    return q, k, v


def out_proj(p: Params, attn_out: jax.Array) -> jax.Array:
    """attn_out: (B, S, H, hd) -> (B, S, D)."""
    b, s, h, hd = attn_out.shape
    return jnp.einsum("bse,ed->bsd", attn_out.reshape(b, s, h * hd), p["wo"].astype(attn_out.dtype))


# ------------------------------------------------------------ stacked layers


def stack_layer_init(rng, n_layers: int, init_one):
    """Initialize ``n_layers`` copies of a layer and stack each leaf on axis 0."""
    rngs = jax.random.split(rng, n_layers)
    layers = [init_one(r) for r in rngs]
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *layers)


def remat_body(body, remat: str):
    """A layer scan's ``body`` under the remat policy ``remat``: "none" keeps
    every activation for the backward pass, "full" recomputes the whole
    body there, "dots" saves the matmul outputs and recomputes the rest."""
    if remat == "none":
        return body
    if remat == "full":
        return jax.checkpoint(body, prevent_cse=False)
    if remat == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots, prevent_cse=False
        )
    raise ValueError(f"unknown remat policy {remat!r}: expected none, full or dots")


def embed_init(rng, vocab: int, d_model: int, dtype) -> jax.Array:
    return (jax.random.normal(rng, (vocab, d_model)) * 0.02).astype(dtype)


def softmax_xent(logits: jax.Array, targets: jax.Array, mask: jax.Array | None = None):
    """Mean token cross-entropy. logits (B,S,V) f32-upcast; targets (B,S) int."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        return jnp.sum(nll * mask) / denom
    return jnp.mean(nll)


def replace(cfg, **kw):
    return dataclasses.replace(cfg, **kw)
