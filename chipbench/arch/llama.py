"""A Llama-style dense decoder (GQA, rotary positions, SwiGLU), as the
program's ``dense`` family lays it out.

The plain forward writes the model out in ``jax.numpy``: RMS norm with the
scale stored as ``1 + w`` (the program's parameterisation of the published
norm), rotary positions on the two halves of each head, grouped-query
causal softmax attention, SwiGLU MLP, tied or untied head, token-mean
cross-entropy. It imports nothing of the program.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

LAYER_LEAVES = frozenset(("ln1", "wq", "wk", "wv", "wo", "ln2", "w_gate", "w_up", "w_down"))
# where each per-layer leaf sits in the program's "blocks" subtree
_PROGRAM_PATH = {
    "ln1": ("ln1",), "ln2": ("ln2",),
    "wq": ("attn", "wq"), "wk": ("attn", "wk"), "wv": ("attn", "wv"), "wo": ("attn", "wo"),
    "w_gate": ("mlp", "w_gate"), "w_up": ("mlp", "w_up"), "w_down": ("mlp", "w_down"),
}
# norm weights w enter as x * (1 + w); drawn non-zero so that the scale is tested
NORM_STD = 0.1
EMBED_STD = 0.02
Q_BLOCK = 512


@dataclass(frozen=True)
class Dims:
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    eps: float
    rope_theta: float
    param_dtype: str

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        return cls(
            d=c["hidden_size"], layers=c["num_hidden_layers"],
            heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
            head_dim=c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
            d_ff=c["intermediate_size"], vocab=c["vocab_size"],
            tied=bool(c["tie_word_embeddings"]), eps=float(c["rms_norm_eps"]),
            rope_theta=float(c["rope_theta"]), param_dtype=c["param_dtype"],
        )


def program_fields(c: dict) -> dict:
    """The program's ModelConfig fields that the configuration file sets."""
    m = Dims.from_config(c)
    return dict(d_model=m.d, num_layers=m.layers, num_heads=m.heads,
                num_kv_heads=m.kv_heads, head_dim=m.head_dim, d_ff=m.d_ff,
                vocab_size=m.vocab, norm_eps=m.eps, rope_theta=m.rope_theta,
                tie_embeddings=m.tied, param_dtype=c["param_dtype"],
                compute_dtype=c["compute_dtype"])


def leaf_specs(m: Dims) -> dict[str, tuple[tuple[int, ...], str, float]]:
    """name -> (shape, dtype, std). Per-layer leaves carry a leading L axis."""
    pd = m.param_dtype
    qd, kvd = m.heads * m.head_dim, m.kv_heads * m.head_dim
    L = m.layers
    out = {
        "embed": ((m.vocab, m.d), pd, EMBED_STD),
        "final_ln": ((m.d,), "float32", NORM_STD),
        "ln1": ((L, m.d), "float32", NORM_STD),
        "wq": ((L, m.d, qd), pd, 1 / math.sqrt(m.d)),
        "wk": ((L, m.d, kvd), pd, 1 / math.sqrt(m.d)),
        "wv": ((L, m.d, kvd), pd, 1 / math.sqrt(m.d)),
        "wo": ((L, qd, m.d), pd, 1 / math.sqrt(qd)),
        "ln2": ((L, m.d), "float32", NORM_STD),
        "w_gate": ((L, m.d, m.d_ff), pd, 1 / math.sqrt(m.d)),
        "w_up": ((L, m.d, m.d_ff), pd, 1 / math.sqrt(m.d)),
        "w_down": ((L, m.d_ff, m.d), pd, 1 / math.sqrt(m.d_ff)),
    }
    if not m.tied:
        out["lm_head"] = ((m.d, m.vocab), pd, 1 / math.sqrt(m.d))
    return out


def to_program(p: dict) -> dict:
    """The reference layout nested as the program's parameter tree."""
    tree = {"embed": p["embed"], "final_ln": p["final_ln"], "blocks": {}}
    if "lm_head" in p:
        tree["lm_head"] = p["lm_head"]
    for name in sorted(LAYER_LEAVES):
        node = tree["blocks"]
        *outer, last = _PROGRAM_PATH[name]
        for k in outer:
            node = node.setdefault(k, {})
        node[last] = p[name]
    return tree


def named(tree: dict) -> dict:
    """A tree shaped like the program's parameters, flattened back to the
    reference's names."""
    out = {k: tree[k] for k in ("embed", "final_ln", "lm_head") if k in tree}
    for name in LAYER_LEAVES:
        node = tree["blocks"]
        for k in _PROGRAM_PATH[name]:
            node = node[k]
        out[name] = node
    return out


# ------------------------------------------------------------ plain forward


def _mm(spec: str, a, b, precision: str):
    """A float32 product at HIGHEST precision; ``fp8`` first rounds both
    operands to float8 e4m3 (the control)."""
    if precision == "fp8":
        a = a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        b = b.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    elif precision != "f32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, theta):
    """x (B, S, H, hd): rotate the pairs (i, i + hd/2) by position * theta^(-2i/hd)."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _attention(q, k, v, precision):
    """Causal softmax attention; q (B,S,H,hd), k/v (B,S,KV,hd); head h reads
    key/value head h // (H/KV). Queries go in blocks, each against the keys
    up to its own end."""
    s, h, hd = q.shape[1:]
    g = h // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    outs = []
    for lo in range(0, s, Q_BLOCK):
        hi = min(lo + Q_BLOCK, s)
        sc = _mm("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi], precision) / math.sqrt(hd)
        allowed = np.arange(lo, hi)[:, None] >= np.arange(hi)[None, :]
        sc = jnp.where(jnp.asarray(allowed)[None, None], sc, -jnp.inf)
        outs.append(_mm("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v[:, :hi], precision))
    return jnp.concatenate(outs, axis=1)


def loss_sum(p, tokens, targets, m: Dims, precision: str):
    """(sum of token losses, number of tokens) of one block of rows; ``p``
    holds float32 leaves in the reference's layout."""
    x = p["embed"][tokens]
    b, s = tokens.shape

    def layer(x, lp):
        h = _rms_norm(x, lp["ln1"], m.eps)
        q = _mm("bsd,de->bse", h, lp["wq"], precision).reshape(b, s, m.heads, m.head_dim)
        k = _mm("bsd,de->bse", h, lp["wk"], precision).reshape(b, s, m.kv_heads, m.head_dim)
        v = _mm("bsd,de->bse", h, lp["wv"], precision).reshape(b, s, m.kv_heads, m.head_dim)
        o = _attention(_rope(q, m.rope_theta), _rope(k, m.rope_theta), v, precision)
        x = x + _mm("bse,ed->bsd", o.reshape(b, s, -1), lp["wo"], precision)
        h = _rms_norm(x, lp["ln2"], m.eps)
        gate = _mm("bsd,df->bsf", h, lp["w_gate"], precision)
        up = _mm("bsd,df->bsf", h, lp["w_up"], precision)
        x = x + _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, lp["w_down"], precision)
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, {k: p[k] for k in LAYER_LEAVES})
    x = _rms_norm(x, p["final_ln"], m.eps)
    head = p["embed"].T if m.tied else p["lm_head"]
    logits = _mm("bsd,dv->bsv", x, head, precision)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = jax.nn.logsumexp(logits, axis=-1) - gold
    return jnp.sum(nll), jnp.asarray(nll.size, jnp.float32)


# ------------------------------------------------------------------- FLOPs
# Copied from the program's launch/analytic_costs.py (dense branch): a
# multiply-add counts 2, causal attention counts its lower triangle only,
# and a training step is three forwards (no recomputation counted).


def _layer_linear_flops_per_tok(m: Dims) -> float:
    attn = 2 * m.d * (m.heads * m.head_dim) * 2 + 2 * m.d * (m.kv_heads * m.head_dim) * 2
    return attn + 6 * m.d * m.d_ff


def forward_flops_per_token(m: Dims, seq: int, *, causal_frac: float = 0.5) -> float:
    """Forward FLOPs per token; ``causal_frac=1`` counts the full attention
    rectangle that the program's blockwise attention executes."""
    attn = 4.0 * seq * m.heads * m.head_dim * causal_frac
    return m.layers * (_layer_linear_flops_per_tok(m) + attn) + 2.0 * m.d * m.vocab


def useful_flops_per_token(m: Dims, seq: int) -> float:
    """Useful training FLOPs per token: forward and backward, 3 forwards."""
    return 3.0 * forward_flops_per_token(m, seq)
