"""Attention: causal attention for train/prefill, as one fused Pallas kernel
on the TPU or as chunked online-softmax scans, and sequence-parallel
flash-decoding for the serve path.

Train/prefill: ``causal_attention`` is what the dense block calls. On a TPU,
where the shapes, mask and mesh fit (``fused_attention_blocks``), it runs
``fused_causal_attention``: the Splash kernel of
``jax.experimental.pallas.ops.tpu.splash_attention``, which keeps each score
tile in VMEM, saves only the per-row log-sum-exp for its backward, recomputes
the scores there tile by tile, and skips the tiles wholly above the
diagonal. Everywhere else it runs ``blockwise_attention``: a scan over Q
blocks around an online-softmax scan over KV blocks carrying (m, l, acc), so
the working set is O(q_block * kv_block) scores; it takes windows, soft caps
and non-causal masks, computes every block pair (fully masked ones too), and
autodiff keeps each block's probabilities for the backward.

Decode: KV caches are laid out (B, KV, S, hd) with the sequence dim sharded
over the ``model`` mesh axis. ``flash_decode`` computes per-shard partial
attention with a log-sum-exp combine over the axis (the TPU analogue of
flash-decoding), so a 32k-context cache never needs gathering.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash
from jax.sharding import PartitionSpec as P

from repro.sharding import get_ctx, mesh_axis_size

NEG_INF = -1e30


def _block_mask(q_pos, k_pos, *, causal: bool, window: Optional[int]):
    """q_pos (qb,), k_pos (kb,) -> bool (qb, kb); True = attend."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        m &= q_pos[:, None] - k_pos[None, :] < window
    return m


def chunked_attention(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Skv, KV, hd)
    v: jax.Array,  # (B, Skv, KV, hd)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    kv_block: int = 1024,
    q_offset: int = 0,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Online-softmax attention, scanning over KV blocks. Returns (B, Sq, H, hd)."""
    b, sq, h, hd = q.shape
    _, skv, kvh, _ = k.shape
    assert h % kvh == 0
    qpkv = h // kvh
    kv_block = min(kv_block, skv)
    # pad kv to a block multiple
    nkb = -(-skv // kv_block)
    pad = nkb * kv_block - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, nkb, kv_block, kvh, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nkb, kv_block, kvh, hd).transpose(1, 0, 2, 3, 4)

    qq = q.reshape(b, sq, kvh, qpkv, hd).astype(jnp.float32)
    scale = hd ** -0.5
    q_pos = q_offset + jnp.arange(sq)

    def step(carry, inp):
        m, l, acc = carry
        kblk, vblk, kidx = inp  # (B, kb, KV, hd) x2, scalar block idx
        k_pos = kidx * kv_block + jnp.arange(kv_block)
        s = jnp.einsum(
            "bqkgh,bckh->bkgqc", qq, kblk.astype(jnp.float32)
        ) * scale  # (B, KV, G, Sq, kb)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        mask = _block_mask(q_pos, k_pos, causal=causal, window=window)
        valid = k_pos < skv
        mask &= valid[None, :]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqc,bckh->bkgqh", p, vblk.astype(jnp.float32))
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kvh, qpkv, sq), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, kvh, qpkv, sq), dtype=jnp.float32)
    a0 = jnp.zeros((b, kvh, qpkv, sq, hd), dtype=jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kb, vb, jnp.arange(nkb)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]          # (B, KV, G, Sq, hd)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd)
    return out.astype(q.dtype)


def blockwise_attention(
    q: jax.Array,  # (B, S, H, hd)
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_block: int = 512,
    kv_block: int = 1024,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Outer scan over Q blocks, inner online-softmax scan over KV blocks.

    Working set per step is O(q_block * kv_block) scores. For windowed
    attention each Q block slices a fixed-size KV window (no full-length scan).
    """
    b, s, h, hd = q.shape
    if s <= q_block:
        return chunked_attention(
            q, k, v, causal=causal, window=window, kv_block=kv_block, softcap=softcap
        )
    q_block = min(q_block, s)
    nqb = -(-s // q_block)
    pad = nqb * q_block - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qb = q.reshape(b, nqb, q_block, h, hd).transpose(1, 0, 2, 3, 4)

    if window is not None:
        # fixed-size KV slice per q block: [end - window - q_block, end)
        span = window + q_block
        span = min(-(-span // kv_block) * kv_block, k.shape[1])

        def step_w(_, inp):
            qblk, i = inp
            q_off = i * q_block
            start = jnp.clip(q_off + q_block - span, 0, k.shape[1] - span)
            ks = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
            # positions inside the slice are start..start+span-1; causal+window
            # masks are computed from absolute positions via q_offset handling:
            out = _attend_block(
                qblk, ks, vs, q_off, start, causal=causal, window=window,
                kv_block=kv_block, softcap=softcap, skv_valid=k.shape[1],
            )
            return None, out

        _, outs = jax.lax.scan(step_w, None, (qb, jnp.arange(nqb)))
    else:

        def step(_, inp):
            qblk, i = inp
            out = _attend_block(
                qblk, k, v, i * q_block, 0, causal=causal, window=None,
                kv_block=kv_block, softcap=softcap, skv_valid=k.shape[1],
            )
            return None, out

        _, outs = jax.lax.scan(step, None, (qb, jnp.arange(nqb)))

    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, nqb * q_block, h, hd)
    return out[:, :s]


def _attend_block(
    qblk, k, v, q_off, kv_off, *, causal, window, kv_block, softcap, skv_valid
):
    """One q block against a KV range starting at absolute position kv_off."""
    b, sq, h, hd = qblk.shape
    skv = k.shape[1]
    kvh = k.shape[2]
    qpkv = h // kvh
    kv_block = min(kv_block, skv)
    nkb = -(-skv // kv_block)
    padk = nkb * kv_block - skv
    if padk:
        k = jnp.pad(k, ((0, 0), (0, padk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, padk), (0, 0), (0, 0)))
    kb = k.reshape(b, nkb, kv_block, kvh, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nkb, kv_block, kvh, hd).transpose(1, 0, 2, 3, 4)
    qq = qblk.reshape(b, sq, kvh, qpkv, hd).astype(jnp.float32)
    scale = hd ** -0.5
    q_pos = q_off + jnp.arange(sq)

    def inner(carry, inp):
        m, l, acc = carry
        kblk, vblk, j = inp
        k_pos = kv_off + j * kv_block + jnp.arange(kv_block)
        s = jnp.einsum("bqkgh,bckh->bkgqc", qq, kblk.astype(jnp.float32)) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        mask = _block_mask(q_pos, k_pos, causal=causal, window=window)
        mask &= (k_pos < skv_valid)[None, :]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqc,bckh->bkgqh", p, vblk.astype(jnp.float32))
        return (m_new, l_new, pv + acc * corr[..., None]), None

    m0 = jnp.full((b, kvh, qpkv, sq), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, kvh, qpkv, sq), dtype=jnp.float32)
    a0 = jnp.zeros((b, kvh, qpkv, sq, hd), dtype=jnp.float32)
    (m, l, acc), _ = jax.lax.scan(inner, (m0, l0, a0), (kb, vb, jnp.arange(nkb)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd).astype(qblk.dtype)


# ------------------------------------------------------------ fused (TPU)

# the kernel's tiles are whole multiples of the vector lanes
_LANES = 128


def fused_attention_blocks(
    q_shape: tuple[int, ...],  # (B, S, H, hd)
    k_shape: tuple[int, ...],  # (B, S, KV, hd)
    *,
    window: Optional[int],
    softcap: Optional[float],
    q_block: int,
    kv_block: int,
    batch_shards: int,
    head_shards: int,
) -> Optional[tuple[int, int]]:
    """The (q, kv) tile sizes of ``fused_causal_attention`` where, on a TPU,
    it computes what causal ``blockwise_attention`` computes, else None.

    It does for plain causal self-attention (no window or soft cap) where
    the blocks clipped to S are multiples of 128 that divide S, the head
    size is one the kernel tiles (at most 128, or a multiple of 128), and
    the mesh splits the batch and the query and KV heads evenly
    (``*_shards`` are the mesh sizes that split each dimension; 1 without a
    mesh).
    """
    b, s, h, hd = q_shape
    kvh = k_shape[2]
    bq, bkv = min(q_block, s), min(kv_block, s)
    fits = (
        window is None and softcap is None
        and bq % _LANES == 0 and bkv % _LANES == 0 and s % bq == 0 and s % bkv == 0
        and (hd <= 128 or hd % 128 == 0)
        and b % batch_shards == 0 and h % kvh == 0
        and h % head_shards == 0 and kvh % head_shards == 0
    )
    return (bq, bkv) if fits else None


@functools.lru_cache(maxsize=None)
def _splash_kernel(heads: int, seq: int, q_block: int, kv_block: int, interpret: bool):
    """One causal Splash kernel per shape, built once: its block masks are
    concrete arrays, made outside any trace so later traces can share them."""
    mask = splash.MultiHeadMask([splash.CausalMask((seq, seq))] * heads)
    blocks = splash.BlockSizes(
        block_q=q_block, block_kv=kv_block, block_kv_compute=kv_block,
        block_q_dkv=q_block, block_kv_dkv=kv_block, block_kv_dkv_compute=kv_block,
        block_q_dq=q_block, block_kv_dq=kv_block,
    )
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha(mask, block_sizes=blocks, head_shards=1,
                                      q_seq_shards=1, interpret=interpret)


def fused_causal_attention(
    q: jax.Array,  # (B, S, H, hd)
    k: jax.Array,  # (B, S, KV, hd)
    v: jax.Array,  # (B, S, KV, hd)
    *,
    q_block: int,
    kv_block: int,
    interpret: bool = False,
) -> jax.Array:
    """Causal softmax attention through the Splash kernel, vmapped over the
    batch; its softmax statistics and accumulators are float32, its
    matmuls take q, k and v in their own dtype, and the result has q's.

    The kernel takes no scale, so hd ** -0.5 is folded into q first, in
    q's dtype: exact where hd is a power of four; elsewhere (yi-9b's 128)
    the scale and the scaled q are rounded to q's dtype, roundings the
    scans, which scale float32 scores, do not make. Under an active mesh
    it runs inside a ``shard_map`` over the batch axes and, where ``tp``
    splits the heads, the heads: a Pallas call has no partitioning rule of
    its own.
    """
    def attend(q, k, v):
        _, s, h, hd = q.shape
        kernel = _splash_kernel(h, s, q_block, kv_block, interpret)
        qs = (q * jnp.asarray(hd ** -0.5, q.dtype)).transpose(0, 2, 1, 3)
        o = jax.vmap(kernel)(qs, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
        return o.transpose(0, 2, 1, 3)

    ctx = get_ctx()
    if ctx.mesh is None:
        return attend(q, k, v)
    heads = ctx.tp_axis if mesh_axis_size("tp") > 1 else None
    spec = P(ctx.dp_axes if ctx.shard_batch else None, None, heads, None)
    # manual over the axes not manual yet: inside a dp-manual region, tp alone
    return jax.shard_map(attend, mesh=ctx.mesh, in_specs=(spec, spec, spec), out_specs=spec,
                         axis_names=set(ctx.mesh.axis_names) - set(ctx.mesh.manual_axes),
                         check_vma=False)(q, k, v)


def causal_attention(
    q: jax.Array,  # (B, S, H, hd)
    k: jax.Array,  # (B, S, KV, hd)
    v: jax.Array,
    *,
    window: Optional[int] = None,
    q_block: int = 512,
    kv_block: int = 1024,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Causal self-attention of the train/prefill path: the fused kernel where
    ``fused_attention_blocks`` admits it for a TPU, ``blockwise_attention``
    everywhere else. The platform is the one the step is lowered for
    (``platform_dependent``), so a compile for a described TPU on another
    host takes the kernel too; only that platform's branch is lowered. q, k
    and v hold the whole sequence: the dense block gathers the residual
    stream's ``sp`` split before its QKV projection.
    """
    blockwise = functools.partial(
        blockwise_attention, causal=True, window=window, q_block=q_block,
        kv_block=kv_block, softcap=softcap,
    )
    ctx = get_ctx()
    blocks = fused_attention_blocks(
        q.shape, k.shape, window=window, softcap=softcap, q_block=q_block,
        kv_block=kv_block, batch_shards=mesh_axis_size("dp") if ctx.shard_batch else 1,
        head_shards=mesh_axis_size("tp"),
    )
    if blocks is None:
        return blockwise(q, k, v)
    fused = functools.partial(fused_causal_attention, q_block=blocks[0], kv_block=blocks[1])
    return jax.lax.platform_dependent(q, k, v, tpu=fused, default=blockwise)


# --------------------------------------------------------------------- decode


def plain_decode_attention(
    q: jax.Array,       # (B, H, hd) — single new token per sequence
    k_cache: jax.Array,  # (B, KV, S, hd)
    v_cache: jax.Array,  # (B, KV, S, hd)
    pos: jax.Array,      # (B,) int32 — current positions (cache[0..pos] valid)
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Reference single-token decode over the full cache (no seq sharding)."""
    b, h, hd = q.shape
    _, kvh, s, _ = k_cache.shape
    qpkv = h // kvh
    qq = q.reshape(b, kvh, qpkv, hd).astype(jnp.float32)
    scores = jnp.einsum("bkgh,bksh->bkgs", qq, k_cache.astype(jnp.float32)) * hd ** -0.5
    if softcap:
        scores = jnp.tanh(scores / softcap) * softcap
    idx = jnp.arange(s)
    mask = idx[None, :] <= pos[:, None]
    if window is not None:
        mask &= idx[None, :] > pos[:, None] - window
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bksh->bkgh", p, v_cache.astype(jnp.float32))
    return out.reshape(b, h, hd).astype(q.dtype)


def flash_decode_attention(
    mesh: jax.sharding.Mesh,
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos: jax.Array,
    *,
    seq_axis: str = "model",
    batch_axes=("data",),
    window: Optional[int] = None,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Sequence-parallel decode: cache seq dim sharded over ``seq_axis``;
    per-shard partial softmax states combined with an LSE merge (pmax/psum).
    """
    n_shards = mesh.shape[seq_axis]
    s = k_cache.shape[2]
    assert s % n_shards == 0, (s, n_shards)
    s_local = s // n_shards

    def shard_fn(q_l, k_l, v_l, pos_l):
        # q_l (Bl, H, hd); k_l/v_l (Bl, KV, S_local, hd); pos_l (Bl,)
        bl, h, hd = q_l.shape
        kvh = k_l.shape[1]
        qpkv = h // kvh
        shard_id = jax.lax.axis_index(seq_axis)
        offset = shard_id * s_local
        qq = q_l.reshape(bl, kvh, qpkv, hd).astype(jnp.float32)
        scores = jnp.einsum("bkgh,bksh->bkgs", qq, k_l.astype(jnp.float32)) * hd ** -0.5
        if softcap:
            scores = jnp.tanh(scores / softcap) * softcap
        idx = offset + jnp.arange(s_local)
        mask = idx[None, :] <= pos_l[:, None]
        if window is not None:
            mask &= idx[None, :] > pos_l[:, None] - window
        scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
        m_loc = jnp.max(scores, axis=-1)
        m_glob = jax.lax.pmax(m_loc, seq_axis)
        p = jnp.exp(scores - m_glob[..., None])
        l_loc = jnp.sum(p, axis=-1)
        acc = jnp.einsum("bkgs,bksh->bkgh", p, v_l.astype(jnp.float32))
        l_glob = jax.lax.psum(l_loc, seq_axis)
        acc_glob = jax.lax.psum(acc, seq_axis)
        out = acc_glob / jnp.maximum(l_glob, 1e-30)[..., None]
        return out.reshape(bl, h, hd).astype(q_l.dtype)

    dp = P(batch_axes)
    return jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            P(batch_axes, None, None),
            P(batch_axes, None, seq_axis, None),
            P(batch_axes, None, seq_axis, None),
            dp,
        ),
        out_specs=P(batch_axes, None, None),
        check_vma=False,
    )(q, k_cache, v_cache, pos)


def ring_decode_attention(
    q: jax.Array,        # (B, H, hd)
    k_cache: jax.Array,  # (B, KV, W, hd) — ring buffer (slot = pos % W)
    v_cache: jax.Array,
    abs_pos: jax.Array,  # (B, W) absolute position stored at each slot
    pos: jax.Array,      # (B,) current position
    window: int,
) -> jax.Array:
    """Decode attention over a fixed-size ring-buffer window cache."""
    b, h, hd = q.shape
    kvh = k_cache.shape[1]
    qpkv = h // kvh
    qq = q.reshape(b, kvh, qpkv, hd).astype(jnp.float32)
    scores = jnp.einsum("bkgh,bksh->bkgs", qq, k_cache.astype(jnp.float32)) * hd ** -0.5
    mask = (
        (abs_pos <= pos[:, None])
        & (abs_pos > pos[:, None] - window)
        & (abs_pos >= 0)
    )
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bksh->bkgh", p, v_cache.astype(jnp.float32))
    return out.reshape(b, h, hd).astype(q.dtype)


def cache_scatter_update(
    cache: jax.Array,   # (B, KV, S, hd) — possibly seq-sharded at the XLA level
    new: jax.Array,     # (B, KV, hd)
    pos: jax.Array,     # (B,)
) -> jax.Array:
    """Write ``new`` at cache[b, :, pos[b], :] via a drop-mode scatter (in-place
    under donation; with a seq-sharded cache only the owning shard writes)."""
    b = cache.shape[0]
    return cache.at[jnp.arange(b), :, pos, :].set(new, mode="drop")


# ------------------------------------------------------- int8 KV quantization


def quantize_kv(x: jax.Array):
    """Symmetric per-vector int8: x (..., hd) -> (q int8, scale (..., 1) f32).

    Halves the decode-path HBM reads of the KV cache; dequantization happens
    on-chip (VMEM) so only int8 bytes cross the HBM interface on TPU.
    """
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def attention_flops(sq: int, skv: int, h: int, hd: int, *, causal: bool) -> int:
    """Analytic attention FLOPs (QK^T + PV), for the roofline MODEL_FLOPS term."""
    pair_frac = 0.5 if causal and sq == skv else 1.0
    return int(4 * sq * skv * h * hd * pair_frac)
