"""Grouped-query attention with sliding-window, softcap, qk-norm, (M-)RoPE.

Two entry points per block:
  * ``attn_forward``  — full-sequence (train / prefill), causal.
  * ``attn_decode``   — one new token against a KV cache.

The jnp path is the canonical implementation that pjit/GSPMD partitions for the
dry-run; ``kernels/flash`` provides the Pallas TPU kernel validated against the
same math (``attn_impl="pallas"`` routes through it, interpret=True on CPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import (apply_mrope, apply_rope, dense_init,
                                 rms_head_norm, softcap)
from repro.models.tp import shard_hint, tp_ctx

NEG_INF = -2.0 ** 30  # large-but-finite: keeps fully-masked rows NaN-free


def init_attn(key, cfg: ModelConfig, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": dense_init(k1, cfg.d_model, cfg.q_dim, dtype),
        "wk": dense_init(k2, cfg.d_model, cfg.kv_dim, dtype),
        "wv": dense_init(k3, cfg.d_model, cfg.kv_dim, dtype),
        "wo": dense_init(k4, cfg.q_dim, cfg.d_model, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.head_dim,), dtype)
        p["k_norm"] = jnp.ones((cfg.head_dim,), dtype)
    return p


def _project_qkv(p, cfg: ModelConfig, x, positions):
    B, S, _ = x.shape
    # TP hint: column-parallel wq/wk/wv leave the HEAD axis sharded —
    # attention then runs head-local per device (Megatron cut #1)
    q = shard_hint((x @ p["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim), 2)
    k = shard_hint((x @ p["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim), 2)
    v = shard_hint((x @ p["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim), 2)
    if cfg.qk_norm:
        q = rms_head_norm(p["q_norm"], q)
        k = rms_head_norm(p["k_norm"], k)
    if cfg.mrope_sections:
        if positions.ndim == x.ndim - 1:          # (B,S) -> identical streams
            positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    elif cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(cfg: ModelConfig, q, k, v, mask):
    """q:(B,S,H,hd) k,v:(B,T,KV,hd) mask:(B,1,S,T) or (1,1,S,T) bool."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    q = q.reshape(B, S, KV, G, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32)
    scores = scores * (hd ** -0.5)
    scores = softcap(scores, cfg.attn_softcap)
    scores = jnp.where(mask[:, :, None] if mask.ndim == 4 else mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, S, H, hd)


def _sdpa_blocked(cfg: ModelConfig, q, k, v, *, causal: bool, window: int,
                  block: int = 1024):
    """Flash-style blocked attention in pure jnp: lax.scan over key blocks
    with online-softmax running (m, l, acc).  Never materializes the (S,T)
    score matrix — the §Perf fix for long-prefill memory (e.g. minicpm's
    36-head full-MHA at 32k).  Same math as _sdpa to fp32 accuracy."""
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    bk = min(block, T)
    assert T % bk == 0, (T, bk)
    nb = T // bk
    scale = hd ** -0.5
    qr = q.reshape(B, S, KV, G, hd).astype(jnp.float32)
    kb = jnp.moveaxis(k.reshape(B, nb, bk, KV, hd), 1, 0).astype(jnp.float32)
    vb = jnp.moveaxis(v.reshape(B, nb, bk, KV, hd), 1, 0).astype(jnp.float32)
    q_idx = jnp.arange(S)

    def body(carry, xs):
        m, l, acc = carry
        j, kblk, vblk = xs
        s = jnp.einsum("bskgd,btkd->bkgst", qr, kblk) * scale
        s = softcap(s, cfg.attn_softcap)
        k_idx = j * bk + jnp.arange(bk)
        mask = jnp.ones((S, bk), bool)
        if causal:
            mask &= k_idx[None, :] <= q_idx[:, None]
        if window > 0:
            mask &= (q_idx[:, None] - k_idx[None, :]) < window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bkgst,btkd->bkgsd", p, vblk)
        return (m_new, l, acc), None

    m0 = jnp.full((B, KV, G, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, S), jnp.float32)
    a0 = jnp.zeros((B, KV, G, S, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (jnp.arange(nb), kb, vb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.moveaxis(out, 3, 1)                 # (B,S,KV,G,hd)
    return out.reshape(B, S, H, hd).astype(q.dtype)


def _causal_mask(S: int, window: int):
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    m = j <= i
    if window > 0:
        m &= (i - j) < window
    return m[None]  # (1,S,T)


def attn_forward(p, cfg: ModelConfig, x, positions, *, local: bool = False,
                 causal: bool = True):
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x, positions)
    window = cfg.sliding_window if local else 0
    if cfg.attn_impl == "pallas" and not cfg.mrope_sections and causal:
        from repro.kernels.flash import ops as flash_ops

        def _flash(q, k, v):
            return flash_ops.flash_attention(
                q, k, v, causal=True, window=window,
                softcap=cfg.attn_softcap)

        c = tp_ctx()
        if (c is not None
                and cfg.n_heads % c[0].shape[c[1]] == 0
                and cfg.n_kv_heads % c[0].shape[c[1]] == 0):
            # head-sharded TP: run the Pallas kernel per device on its
            # LOCAL head shard — shard_map keeps the kernel call out of
            # GSPMD's hands (a custom call has no partitioning rule), so
            # the sharded attention path is served by the same kernel
            mesh, axis = c
            hs = P(None, None, axis, None)
            # check_vma off: the Pallas interpreter cannot evaluate a
            # kernel body whose refs vary over a mesh axis
            out = jax.shard_map(_flash, mesh=mesh, in_specs=(hs, hs, hs),
                                out_specs=hs, check_vma=False)(q, k, v)
        else:
            out = _flash(q, k, v)
    elif cfg.attn_impl == "blocked":
        out = _sdpa_blocked(cfg, q, k, v, causal=causal, window=window)
    else:
        if causal:
            mask = _causal_mask(S, window)[:, None]      # (1,1,S,T)
        else:
            mask = jnp.ones((1, 1, S, S), bool)
        out = _sdpa(cfg, q, k, v, mask)
    # TP hint: head-sharded context feeds the row-parallel wo — the
    # contraction's all-reduce is the layer's single output collective
    out = shard_hint(out, 2)
    return out.reshape(B, S, cfg.q_dim) @ p["wo"]


def init_cross_attn(key, cfg: ModelConfig, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, cfg.d_model, cfg.q_dim, dtype),
        "wk": dense_init(k2, cfg.d_model, cfg.kv_dim, dtype),
        "wv": dense_init(k3, cfg.d_model, cfg.kv_dim, dtype),
        "wo": dense_init(k4, cfg.q_dim, cfg.d_model, dtype),
    }


def cross_kv(p, cfg: ModelConfig, enc_out):
    B, T, _ = enc_out.shape
    k = (enc_out @ p["wk"]).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    v = (enc_out @ p["wv"]).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    return k, v


def cross_attn_forward(p, cfg: ModelConfig, x, k, v):
    """x: (B,S,d); k,v: (B,T,KV,hd) from the encoder. No positional encoding."""
    B, S, _ = x.shape
    q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
    mask = jnp.ones((1, 1, S, k.shape[1]), bool)
    out = _sdpa(cfg, q, k, v, mask)
    return out.reshape(B, S, cfg.q_dim) @ p["wo"]


def init_attn_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    shape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def attn_decode(p, cfg: ModelConfig, cache, x, pos, *, local: bool = False):
    """x: (B,1,d); pos: scalar int32 current position. Returns (out, cache)."""
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, dtype=jnp.int32)
    q, k_new, v_new = _project_qkv(p, cfg, x, positions)
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new.astype(cache["k"].dtype), pos, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new.astype(cache["v"].dtype), pos, axis=1)
    T = k.shape[1]
    j = jnp.arange(T)[None, :]
    m = j <= pos
    if local and cfg.sliding_window > 0:
        m &= (pos - j) < cfg.sliding_window
    mask = m[None, None]                              # (1,1,1,T)
    out = _sdpa(cfg, q, k, v, mask)
    out = out.reshape(B, 1, cfg.q_dim) @ p["wo"]
    return out, {"k": k, "v": v}
