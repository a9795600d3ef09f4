"""LM stack assembly for all decoder families (dense / moe / vlm / rwkv / hybrid).

Layers are stacked on a leading L dim and consumed with ``jax.lax.scan`` so the
HLO stays compact at 88 layers (granite-34b) and compile times stay sane on the
512-device dry-run. Sharding is expressed through ``repro.sharding.shard``
constraints; with no mesh active everything runs single-device (smoke tests).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import layers, moe, rglru, rwkv
from repro.sharding import get_ctx, shard
from repro.sharding.ctx import maybe_gather_params

Params = Any


# ------------------------------------------------------------------ dense block


def dense_block_init(rng, cfg: ModelConfig, dtype) -> Params:
    k1, k2, k3 = jax.random.split(rng, 3)
    p = {
        "ln1": jnp.zeros((cfg.d_model,), jnp.float32),
        "attn": layers.attn_proj_init(k1, cfg, dtype),
        "ln2": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    if cfg.family == "moe":
        p["moe"] = moe.moe_init(k2, cfg, dtype)
    else:
        p["mlp"] = layers.mlp_init(k3, cfg.d_model, cfg.d_ff, _mlp_act(cfg), dtype)
    return p


def _mlp_act(cfg: ModelConfig) -> str:
    return "swiglu" if cfg.act == "swiglu" else cfg.act


def _attn_head_spec(cfg: ModelConfig):
    """Shard attention head dims over tp only when divisible."""
    from repro.sharding import mesh_axis_size

    tp = mesh_axis_size("tp")
    return "tp" if (tp > 1 and cfg.num_heads % tp == 0) else None


def dense_block_apply(p, x: jax.Array, cfg: ModelConfig, *, positions, want_kv: bool):
    """Train/prefill path. x (B,S,D). Returns (x, aux_metrics, (k,v)|None).

    The two halves run under the named scopes ``attention`` and ``mlp``; JAX
    carries them into the backward pass and the remat recompute, so a
    profiler trace can be split by layer (``op_name`` in the compiled HLO)."""
    hspec = _attn_head_spec(cfg)
    with jax.named_scope("attention"):
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        h = shard(h, "dp", None, None)
        q, k, v = layers.qkv_split(p["attn"], h, cfg)
        q = apply_positions(q, positions, cfg)
        k = apply_positions(k, positions, cfg)
        q = shard(q, "dp", None, hspec, None)
        o = attn.causal_attention(
            q, k, v,
            window=cfg.attn_window,
            q_block=cfg.attn_q_block,
            kv_block=cfg.attn_kv_block,
            softcap=cfg.attn_logit_softcap,
        )
        x = x + shard(layers.out_proj(p["attn"], o), "dp", "sp", None)
    with jax.named_scope("mlp"):
        h2 = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
        h2 = shard(h2, "dp", None, None)
        aux = {}
        if cfg.family == "moe":
            ff, aux = moe.moe_apply(p["moe"], h2, cfg)
        else:
            ff = layers.mlp_apply(p["mlp"], h2, _mlp_act(cfg))
        x = x + shard(ff, "dp", "sp", None)
    kv = None
    if want_kv:
        kv = (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))  # (B,KV,S,hd)
    return x, aux, kv


def apply_positions(x: jax.Array, positions, cfg: ModelConfig) -> jax.Array:
    if not cfg.rope_theta:
        return x
    return layers.apply_rope(x, positions, cfg.rope_theta)


def dense_block_decode(p, x: jax.Array, cfg: ModelConfig, kc, vc, pos,
                       ks=None, vs=None):
    """Decode path. x (B,D); kc/vc (B,KV,S,hd) (int8 when quantized, with
    ks/vs scales (B,KV,S,1)); pos (B,). Returns (x, kc, vc, ks, vs)."""
    ctx = get_ctx()
    quant = ks is not None
    h = layers.rms_norm(x[:, None], p["ln1"], cfg.norm_eps)  # (B,1,D)
    q, k, v = layers.qkv_split(p["attn"], h, cfg)
    q = apply_positions(q, pos[:, None], cfg)
    k = apply_positions(k, pos[:, None], cfg)
    q1, k1, v1 = q[:, 0], k[:, 0], v[:, 0]                   # (B,H,hd)/(B,KV,hd)
    if quant:
        k1q, k1s = attn.quantize_kv(k1)
        v1q, v1s = attn.quantize_kv(v1)
        kc = attn.cache_scatter_update(kc, k1q, pos)
        vc = attn.cache_scatter_update(vc, v1q, pos)
        ks = attn.cache_scatter_update(ks, k1s, pos)
        vs = attn.cache_scatter_update(vs, v1s, pos)
        kc_a = attn.dequantize_kv(kc, ks, k1.dtype)
        vc_a = attn.dequantize_kv(vc, vs, v1.dtype)
    else:
        kc = attn.cache_scatter_update(kc, k1, pos)
        vc = attn.cache_scatter_update(vc, v1, pos)
        kc_a, vc_a = kc, vc
    s = kc.shape[2]
    tp = ctx.mesh.shape[ctx.tp_axis] if (ctx.mesh and ctx.tp_axis) else 1
    if ctx.mesh is not None and tp > 1 and s % tp == 0:
        o = attn.flash_decode_attention(
            ctx.mesh, q1, kc_a, vc_a, pos,
            seq_axis=ctx.tp_axis,
            batch_axes=(ctx.dp_axes if ctx.shard_batch else ()),
            window=cfg.attn_window, softcap=cfg.attn_logit_softcap,
        )
    else:
        o = attn.plain_decode_attention(
            q1, kc_a, vc_a, pos, window=cfg.attn_window,
            softcap=cfg.attn_logit_softcap,
        )
    x = x + layers.out_proj(p["attn"], o[:, None])[:, 0]
    h2 = layers.rms_norm(x[:, None], p["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        ff, _ = moe.moe_apply(p["moe"], h2, cfg, no_drop=True)
    else:
        ff = layers.mlp_apply(p["mlp"], h2, _mlp_act(cfg))
    return x + ff[:, 0], kc, vc, ks, vs


# ----------------------------------------------------------------- LM skeleton


def lm_init(rng, cfg: ModelConfig) -> Params:
    dtype = layers.dtype_of(cfg.param_dtype)
    ke, kb, kh, kv_ = jax.random.split(rng, 4)
    p: dict[str, Any] = {
        "embed": layers.embed_init(ke, cfg.vocab_size, cfg.d_model, dtype),
        "final_ln": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = (
            jax.random.normal(kh, (cfg.d_model, cfg.vocab_size)) / np.sqrt(cfg.d_model)
        ).astype(dtype)
    if cfg.family in ("dense", "moe", "vlm"):
        p["blocks"] = layers.stack_layer_init(
            kb, cfg.num_layers, lambda r: dense_block_init(r, cfg, dtype)
        )
    elif cfg.family == "rwkv":
        p["blocks"] = layers.stack_layer_init(
            kb, cfg.num_layers, lambda r: rwkv.rwkv_block_init(r, cfg, dtype)
        )
    elif cfg.family == "hybrid":
        p.update(_hybrid_init(kb, cfg, dtype))
    else:
        raise ValueError(cfg.family)
    if cfg.family == "vlm":
        p["patch_proj"] = (
            jax.random.normal(kv_, (cfg.vision.patch_dim, cfg.d_model))
            / np.sqrt(cfg.vision.patch_dim)
        ).astype(dtype)
    return p


def embed_tokens(params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
        if cfg.family == "hybrid":  # gemma-style embedding scale
            x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
        return shard(x, "dp", "sp", None)


def lm_logits(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = layers.rms_norm(x, params["final_ln"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("...d,dv->...v", x, head.astype(x.dtype))
    return shard(logits, "dp", None, "tp") if logits.ndim == 3 else logits


# ------------------------------------------------------- dense/moe/vlm forward


def _scan_blocks(params, cfg, x, positions, *, want_kv, remat: str = "none"):
    """The layer scan of dense, MoE and VLM blocks. Under the paper's FSDP
    gathers, the train path of dense blocks with the batch split over dp
    runs each layer, its gather included, in a region manual over dp
    (``ParamGather.dp_manual``). Prefill (``want_kv``), MoE blocks (their
    aux losses and expert axes) and a batch left whole gather through
    ``maybe_gather_params``."""
    ctx = get_ctx()
    region = None
    if (ctx.gather_params is not None and not want_kv and cfg.family != "moe"
            and ctx.shard_batch):
        def apply(bp, h, positions):
            return dense_block_apply(bp, h, cfg, positions=positions, want_kv=False)[0]

        region = ctx.gather_params.dp_manual(apply, params["blocks"])

    def body(carry, bp):
        h, aux_acc = carry
        if region is not None:
            return (region(bp, h, positions), aux_acc), None
        bp = maybe_gather_params(bp)  # FSDP gather (paper schedule) if active
        h, aux, kv = dense_block_apply(bp, h, cfg, positions=positions, want_kv=want_kv)
        aux_acc = {k: aux_acc.get(k, 0.0) + v for k, v in aux.items()} if aux else aux_acc
        return (h, aux_acc), kv

    aux0 = (
        {"moe_aux": 0.0, "moe_zloss": 0.0, "moe_drop_frac": 0.0}
        if cfg.family == "moe"
        else {}
    )
    (x, aux), kvs = jax.lax.scan(layers.remat_body(body, remat), (x, aux0), params["blocks"])
    if cfg.family == "moe":
        aux = {k: v / cfg.num_layers for k, v in aux.items()}
    return x, aux, kvs


def dense_forward(params, cfg: ModelConfig, batch, *, want_cache=False, remat="none"):
    """batch: tokens (B,S) [+ patches (B,Np,pd) for vlm]. Returns (logits, aux, cache)."""
    tokens = batch["tokens"]
    x = embed_tokens(params, cfg, tokens)
    if cfg.family == "vlm":
        patches = jnp.einsum(
            "bpe,ed->bpd", batch["patches"].astype(x.dtype), params["patch_proj"]
        )
        x = jnp.concatenate([patches, x], axis=1)
        x = shard(x, "dp", "sp", None)
    s = x.shape[1]
    positions = jnp.arange(s)[None, :]
    x, aux, kvs = _scan_blocks(params, cfg, x, positions, want_kv=want_cache, remat=remat)
    cache = None
    if want_cache:
        if cfg.kv_cache_dtype == "int8":
            kq, ks_ = attn.quantize_kv(kvs[0])
            vq, vs_ = attn.quantize_kv(kvs[1])
            cache = {"k": kq, "v": vq, "ks": ks_, "vs": vs_}
        else:
            cache = {"k": kvs[0], "v": kvs[1]}  # (L,B,KV,S,hd)
    return x, aux, cache


def dense_decode_step(params, cfg: ModelConfig, cache, token, pos):
    """token (B,), pos (B,). Returns (logits (B,V), new cache)."""
    x = embed_tokens(params, cfg, token[:, None])[:, 0]     # (B,D)
    quant = "ks" in cache

    if quant:
        def body(h, xs):
            bp, kc, vc, ks, vs = xs
            h, kc, vc, ks, vs = dense_block_decode(bp, h, cfg, kc, vc, pos, ks, vs)
            return h, (kc, vc, ks, vs)

        x, (kcs, vcs, kss, vss) = jax.lax.scan(
            body, x,
            (params["blocks"], cache["k"], cache["v"], cache["ks"], cache["vs"]),
        )
        new_cache = {"k": kcs, "v": vcs, "ks": kss, "vs": vss}
    else:
        def body(h, xs):
            bp, kc, vc = xs
            h, kc, vc, _, _ = dense_block_decode(bp, h, cfg, kc, vc, pos)
            return h, (kc, vc)

        x, (kcs, vcs) = jax.lax.scan(
            body, x, (params["blocks"], cache["k"], cache["v"])
        )
        new_cache = {"k": kcs, "v": vcs}
    logits = lm_logits(params, cfg, x[:, None])[:, 0]
    return logits, new_cache


def dense_init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype):
    shp = (cfg.num_layers, batch, cfg.num_kv_heads, seq_len, cfg.head_dim)
    if cfg.kv_cache_dtype == "int8":
        sshp = shp[:-1] + (1,)
        return {
            "k": jnp.zeros(shp, jnp.int8), "v": jnp.zeros(shp, jnp.int8),
            "ks": jnp.zeros(sshp, jnp.float32), "vs": jnp.zeros(sshp, jnp.float32),
        }
    return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)}


# --------------------------------------------------------------- rwkv forward


def rwkv_forward(params, cfg: ModelConfig, batch, *, want_cache=False, remat="none"):
    x = embed_tokens(params, cfg, batch["tokens"])

    def body(h, bp):
        bp = maybe_gather_params(bp)
        h, st = rwkv.rwkv_block_apply(bp, h, cfg, state=None, chunked=True)
        return h, (st if want_cache else None)

    x, sts = jax.lax.scan(layers.remat_body(body, remat), x, params["blocks"])
    return x, {}, (sts if want_cache else None)


def rwkv_decode_step(params, cfg: ModelConfig, cache, token, pos):
    x = embed_tokens(params, cfg, token[:, None])[:, 0]

    def body(h, xs):
        bp, st = xs
        h2, st2 = rwkv.rwkv_block_apply(bp, h[:, None], cfg, state=st, chunked=False)
        return h2[:, 0], st2

    x, sts = jax.lax.scan(body, x, (params["blocks"], cache))
    logits = lm_logits(params, cfg, x[:, None])[:, 0]
    return logits, sts


def rwkv_init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype):
    h, hd, d = cfg.num_heads, cfg.head_dim, cfg.d_model
    return {
        "wkv": jnp.zeros((cfg.num_layers, batch, h, hd, hd), jnp.float32),
        "tm_x": jnp.zeros((cfg.num_layers, batch, d), dtype),
        "cm_x": jnp.zeros((cfg.num_layers, batch, d), dtype),
    }


# -------------------------------------------------------------- hybrid forward


def _hybrid_layout(cfg: ModelConfig) -> tuple[int, int]:
    """(n_groups of the repeating pattern, n_trailing_rec)."""
    plen = len(cfg.rglru.pattern)
    return cfg.num_layers // plen, cfg.num_layers % plen


def _hybrid_attn_layer_init(rng, cfg, dtype):
    k1, k2 = jax.random.split(rng)
    return {
        "ln1": jnp.zeros((cfg.d_model,), jnp.float32),
        "attn": layers.attn_proj_init(k1, cfg, dtype),
        "ln2": jnp.zeros((cfg.d_model,), jnp.float32),
        "mlp": layers.mlp_init(k2, cfg.d_model, cfg.d_ff, "swiglu", dtype),
    }


def _hybrid_rec_layer_init(rng, cfg, dtype):
    k1, k2 = jax.random.split(rng)
    return {
        "rec": rglru.rec_block_init(k1, cfg, dtype),
        "ln2": jnp.zeros((cfg.d_model,), jnp.float32),
        "mlp": layers.mlp_init(k2, cfg.d_model, cfg.d_ff, "swiglu", dtype),
    }


def _hybrid_init(rng, cfg: ModelConfig, dtype):
    ng, nt = _hybrid_layout(cfg)
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "grp_rec_a": layers.stack_layer_init(
            k1, ng, lambda r: _hybrid_rec_layer_init(r, cfg, dtype)
        ),
        "grp_rec_b": layers.stack_layer_init(
            jax.random.fold_in(k1, 1), ng, lambda r: _hybrid_rec_layer_init(r, cfg, dtype)
        ),
        "grp_attn": layers.stack_layer_init(
            k2, ng, lambda r: _hybrid_attn_layer_init(r, cfg, dtype)
        ),
        "tail_rec": layers.stack_layer_init(
            k3, max(nt, 1), lambda r: _hybrid_rec_layer_init(r, cfg, dtype)
        ),
    }


def _hybrid_rec_apply(p, x, cfg, state):
    x, st = rglru.rec_block_apply(p["rec"], x, cfg, state=state)
    h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
    return x + shard(layers.mlp_apply(p["mlp"], h, "swiglu"), "dp", "sp", None), st


def _hybrid_attn_apply(p, x, cfg, positions, want_kv):
    h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = layers.qkv_split(p["attn"], h, cfg)
    q = apply_positions(q, positions, cfg)
    k = apply_positions(k, positions, cfg)
    o = attn.blockwise_attention(
        q, k, v, causal=True, window=cfg.rglru.window,
        q_block=cfg.attn_q_block, kv_block=cfg.attn_kv_block,
    )
    x = x + shard(layers.out_proj(p["attn"], o), "dp", "sp", None)
    h2 = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
    x = x + shard(layers.mlp_apply(p["mlp"], h2, "swiglu"), "dp", "sp", None)
    kv = (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)) if want_kv else None
    return x, kv


def hybrid_forward(params, cfg: ModelConfig, batch, *, want_cache=False, remat="none"):
    x = embed_tokens(params, cfg, batch["tokens"])
    positions = jnp.arange(x.shape[1])[None, :]
    ng, nt = _hybrid_layout(cfg)

    def body(h, gp):
        gp = maybe_gather_params(gp)
        h, st_a = _hybrid_rec_apply(gp["grp_rec_a"], h, cfg, None)
        h, st_b = _hybrid_rec_apply(gp["grp_rec_b"], h, cfg, None)
        h, kv = _hybrid_attn_apply(gp["grp_attn"], h, cfg, positions, want_cache)
        ys = (st_a, st_b, kv) if want_cache else None
        return h, ys

    xs = {k: params[k] for k in ("grp_rec_a", "grp_rec_b", "grp_attn")}
    x, ys = jax.lax.scan(layers.remat_body(body, remat), x, xs)

    def tail(h, tp_):
        tp_ = maybe_gather_params(tp_)
        h, st = _hybrid_rec_apply(tp_, h, cfg, None)
        return h, (st if want_cache else None)

    if nt:
        x, tail_sts = jax.lax.scan(layers.remat_body(tail, remat), x, params["tail_rec"])
    else:
        tail_sts = None
    cache = None
    if want_cache:
        st_a, st_b, kv = ys
        cache = {
            "rec_a": st_a, "rec_b": st_b,
            "attn_k": _window_clip(kv[0], cfg), "attn_v": _window_clip(kv[1], cfg),
            "tail": tail_sts,
        }
    return x, {}, cache


def _window_clip(kv, cfg: ModelConfig):
    """Keep only the trailing window of prefill KV (hybrid decode needs <= W)."""
    w = cfg.rglru.window
    s = kv.shape[3]
    return kv[:, :, :, max(0, s - w):] if s > w else kv


def hybrid_decode_step(params, cfg: ModelConfig, cache, token, pos):
    x = embed_tokens(params, cfg, token[:, None])[:, 0]
    ng, nt = _hybrid_layout(cfg)

    def rec_step(h, p, st):
        h2, st2 = _hybrid_rec_apply(p, h[:, None], cfg, st)
        return h2[:, 0], st2

    def attn_step(h, p, kc, vc):
        hh = layers.rms_norm(h[:, None], p["ln1"], cfg.norm_eps)
        q, k, v = layers.qkv_split(p["attn"], hh, cfg)
        q = apply_positions(q, pos[:, None], cfg)
        k = apply_positions(k, pos[:, None], cfg)
        w = kc.shape[2]
        slot = pos % w
        kc = attn.cache_scatter_update(kc, k[:, 0], slot)
        vc = attn.cache_scatter_update(vc, v[:, 0], slot)
        # ring-buffer positions: absolute position stored at slot s is the
        # largest p' <= pos with p' % w == s
        idx = jnp.arange(w)
        abs_pos = pos[:, None] - ((pos[:, None] - idx[None, :]) % w)
        o = attn.ring_decode_attention(q[:, 0], kc, vc, abs_pos, pos, cfg.rglru.window)
        h = h + layers.out_proj(p["attn"], o[:, None])[:, 0]
        h2 = layers.rms_norm(h[:, None], p["ln2"], cfg.norm_eps)
        return h + layers.mlp_apply(p["mlp"], h2, "swiglu")[:, 0], kc, vc

    def body(h, xs):
        gp, st_a, st_b, kc, vc = xs
        h, st_a = rec_step(h, gp["grp_rec_a"], st_a)
        h, st_b = rec_step(h, gp["grp_rec_b"], st_b)
        h, kc, vc = attn_step(h, gp["grp_attn"], kc, vc)
        return h, (st_a, st_b, kc, vc)

    xs = (
        {k: params[k] for k in ("grp_rec_a", "grp_rec_b", "grp_attn")},
        cache["rec_a"], cache["rec_b"], cache["attn_k"], cache["attn_v"],
    )
    x, (st_a, st_b, kcs, vcs) = jax.lax.scan(body, x, xs)

    def tail_body(h, xs):
        tp_, st = xs
        h, st = rec_step(h, tp_, st)
        return h, st

    if nt:
        x, tail_sts = jax.lax.scan(tail_body, x, (params["tail_rec"], cache["tail"]))
    else:
        tail_sts = cache["tail"]
    logits = lm_logits(params, cfg, x[:, None])[:, 0]
    return logits, {
        "rec_a": st_a, "rec_b": st_b, "attn_k": kcs, "attn_v": vcs, "tail": tail_sts,
    }


def hybrid_init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype):
    ng, nt = _hybrid_layout(cfg)
    w = min(cfg.rglru.window, seq_len)
    lru = cfg.rglru.lru_width
    kcw = cfg.rglru.conv_width - 1

    def rec_state(n):
        return {
            "h": jnp.zeros((n, batch, lru), jnp.float32),
            "conv": jnp.zeros((n, batch, kcw, lru), dtype),
        }

    return {
        "rec_a": rec_state(ng),
        "rec_b": rec_state(ng),
        "attn_k": jnp.zeros((ng, batch, cfg.num_kv_heads, w, cfg.head_dim), dtype),
        "attn_v": jnp.zeros((ng, batch, cfg.num_kv_heads, w, cfg.head_dim), dtype),
        "tail": rec_state(max(nt, 1)),
    }
