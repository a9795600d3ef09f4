"""Unified model API: every family exposes the same five functions.

    api = build_model(cfg)
    params = api.init_params(rng)
    loss, metrics = api.loss_fn(params, batch)            # train shapes
    logits, cache = api.prefill_fn(params, batch)         # inference-prefill
    logits, cache = api.decode_fn(params, cache, tok, pos)  # one decode step

The loss head uses chunked cross-entropy (scan over sequence chunks with
rematerialized logits) so (B, S, V) never materializes in f32 — required for
49k-256k vocabs at 32k context.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import encdec, layers, moe, rglru, rwkv, transformer

Params = Any
XENT_CHUNK = 512


def count_params_analytic(cfg: ModelConfig, active_only: bool = False) -> int:
    d, v, l = cfg.d_model, cfg.vocab_size, cfg.num_layers
    embed = v * d * (1 if cfg.tie_embeddings else 2)
    if cfg.family in ("dense", "vlm"):
        per = layers.attn_param_count(cfg) + layers.mlp_param_count(d, cfg.d_ff, cfg.act)
        n = l * per + embed
        if cfg.family == "vlm":
            n += cfg.vision.patch_dim * d
        return n
    if cfg.family == "moe":
        per = layers.attn_param_count(cfg) + moe.moe_param_count(cfg, active_only)
        return l * per + embed
    if cfg.family == "rwkv":
        return l * rwkv.rwkv_block_param_count(cfg) + embed
    if cfg.family == "hybrid":
        ng, nt = transformer._hybrid_layout(cfg)
        mlp = layers.mlp_param_count(d, cfg.d_ff, "swiglu")
        rec = rglru.rec_block_param_count(cfg) + mlp
        att = layers.attn_param_count(cfg) + mlp
        return ng * (2 * rec + att) + nt * rec + embed
    if cfg.family == "encdec":
        return encdec.encdec_param_count(cfg)
    raise ValueError(cfg.family)


def chunked_xent(hidden: jax.Array, head: jax.Array, targets: jax.Array,
                 chunk: int = XENT_CHUNK):
    """Token-mean cross-entropy without materializing full-seq f32 logits.

    hidden (B,S,D); head (D,V); targets (B,S) int32, -1 = masked out.
    """
    b, s, d = hidden.shape
    chunk = min(chunk, s)
    nc = -(-s // chunk)
    pad = nc * chunk - s
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)), constant_values=-1)
    hc = hidden.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    tc = targets.reshape(b, nc, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def body(carry, inp):
        tot, cnt = carry
        h, t = inp
        logits = jnp.einsum("bcd,dv->bcv", h, head.astype(h.dtype)).astype(jnp.float32)
        mask = (t >= 0).astype(jnp.float32)
        tt = jnp.maximum(t, 0)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tt[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * mask
        return (tot + jnp.sum(nll), cnt + jnp.sum(mask)), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())), (hc, tc))
    return tot / jnp.maximum(cnt, 1.0)


def _final_hidden(params, cfg, x):
    return layers.rms_norm(x, params["final_ln"], cfg.norm_eps)


def _head_matrix(params, cfg):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


@dataclass
class ModelApi:
    cfg: ModelConfig
    init_params: Callable
    loss_fn: Callable              # (params, batch) -> (loss, metrics)
    forward_fn: Callable           # (params, batch) -> hidden (B,S,D)
    prefill_fn: Callable           # (params, batch) -> (last_logits, cache)
    decode_fn: Callable            # (params, cache, token, pos) -> (logits, cache)
    init_cache: Callable           # (batch, seq) -> cache pytree (zeros)


def build_model(cfg: ModelConfig, remat: str = "none") -> ModelApi:
    dt = layers.dtype_of(cfg.param_dtype)
    fam = cfg.family

    if fam == "encdec":
        def init_params(rng):
            return encdec.encdec_init(rng, cfg)

        def forward_fn(params, batch):
            x, _, _ = encdec.encdec_forward(params, cfg, batch, remat=remat)
            return _final_hidden_encdec(params, cfg, x)

        def loss_fn(params, batch):
            x, aux, _ = encdec.encdec_forward(params, cfg, batch, remat=remat)
            x = layers.rms_norm(x, params["final_ln"], cfg.norm_eps)
            loss = chunked_xent(x, params["lm_head"], batch["targets"])
            return loss, {"xent": loss}

        def prefill_fn(params, batch):
            x, _, cache = encdec.encdec_forward(
                params, cfg, batch, want_cache=True, remat=remat
            )
            logits = encdec.encdec_logits(params, cfg, x[:, -1:])[:, 0]
            return logits, cache

        def decode_fn(params, cache, token, pos):
            return encdec.encdec_decode_step(params, cfg, cache, token, pos)

        def init_cache(batch, seq):
            return encdec.encdec_init_cache(cfg, batch, seq, dt)

        return ModelApi(cfg, init_params, loss_fn, forward_fn, prefill_fn, decode_fn, init_cache)

    fwd = {
        "dense": transformer.dense_forward,
        "moe": transformer.dense_forward,
        "vlm": transformer.dense_forward,
        "rwkv": transformer.rwkv_forward,
        "hybrid": transformer.hybrid_forward,
    }[fam]
    dec = {
        "dense": transformer.dense_decode_step,
        "moe": transformer.dense_decode_step,
        "vlm": transformer.dense_decode_step,
        "rwkv": transformer.rwkv_decode_step,
        "hybrid": transformer.hybrid_decode_step,
    }[fam]
    cache_init = {
        "dense": transformer.dense_init_cache,
        "moe": transformer.dense_init_cache,
        "vlm": transformer.dense_init_cache,
        "rwkv": transformer.rwkv_init_cache,
        "hybrid": transformer.hybrid_init_cache,
    }[fam]

    def init_params(rng):
        return transformer.lm_init(rng, cfg)

    def forward_fn(params, batch):
        x, _, _ = fwd(params, cfg, batch, remat=remat)
        return _final_hidden(params, cfg, x)

    def loss_fn(params, batch):
        x, aux, _ = fwd(params, cfg, batch, remat=remat)
        with jax.named_scope("xent"):
            x = _final_hidden(params, cfg, x)
            loss = chunked_xent(x, _head_matrix(params, cfg), batch["targets"])
        metrics = {"xent": loss}
        if fam == "moe":
            loss = loss + aux["moe_aux"] + aux["moe_zloss"]
            metrics.update(aux)
        return loss, metrics

    def prefill_fn(params, batch):
        x, _, cache = fwd(params, cfg, batch, want_cache=True, remat=remat)
        logits = transformer.lm_logits(params, cfg, x[:, -1:])[:, 0]
        return logits, cache

    def decode_fn(params, cache, token, pos):
        return dec(params, cfg, cache, token, pos)

    def init_cache(batch, seq):
        return cache_init(cfg, batch, seq, dt)

    return ModelApi(cfg, init_params, loss_fn, forward_fn, prefill_fn, decode_fn, init_cache)


def _final_hidden_encdec(params, cfg, x):
    return layers.rms_norm(x, params["final_ln"], cfg.norm_eps)


# -------------------------------------------------------------- batch helpers


def batch_dims(cfg: ModelConfig, shape: ShapeConfig) -> dict[str, tuple]:
    """Shapes (no data) for every input of the (cfg, shape) cell."""
    b, s = shape.global_batch, shape.seq_len
    if shape.kind in ("train",):
        if cfg.family == "encdec":
            return {
                "frames": (b, s, cfg.encdec.frame_dim),
                "tokens": (b, s),
                "targets": (b, s),
            }
        if cfg.family == "vlm":
            np_ = cfg.vision.n_patches
            return {
                "patches": (b, np_, cfg.vision.patch_dim),
                "tokens": (b, s - np_),
                "targets": (b, s),
            }
        return {"tokens": (b, s), "targets": (b, s)}
    if shape.kind == "prefill":
        if cfg.family == "encdec":
            return {"frames": (b, s, cfg.encdec.frame_dim), "tokens": (b, s)}
        if cfg.family == "vlm":
            np_ = cfg.vision.n_patches
            return {"patches": (b, np_, cfg.vision.patch_dim), "tokens": (b, s - np_)}
        return {"tokens": (b, s)}
    # decode
    return {"token": (b,), "pos": (b,)}


def make_dummy_batch(cfg: ModelConfig, shape: ShapeConfig, rng) -> dict[str, jax.Array]:
    dims = batch_dims(cfg, shape)
    out = {}
    for name, shp in dims.items():
        rng, k = jax.random.split(rng)
        if name in ("tokens", "targets", "token"):
            out[name] = jax.random.randint(k, shp, 0, cfg.vocab_size, dtype=jnp.int32)
        elif name == "pos":
            out[name] = jnp.zeros(shp, jnp.int32)
        else:
            out[name] = (jax.random.normal(k, shp) * 0.02).astype(
                layers.dtype_of(cfg.compute_dtype)
            )
    if cfg.family == "vlm" and "targets" in out:
        np_ = cfg.vision.n_patches
        out["targets"] = out["targets"].at[:, :np_].set(-1)  # no loss on patches
    return out
