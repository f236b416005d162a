"""Flash attention kernel: shape/dtype sweep + masking semantics vs ref."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash import ops as fops
from repro.kernels.flash import ref as fref


def _run(key, B, S, H, KV, hd, dtype, **kw):
    q = jax.random.normal(key, (B, S, H, hd)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, hd)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, hd)).astype(dtype)
    out = fops.flash_attention(q, k, v, **kw)
    G = H // KV
    kk = jnp.repeat(k, G, 2).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vv = jnp.repeat(v, G, 2).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    qq = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    ref = fref.attention_bh(qq, kk, vv, **{k_: v_ for k_, v_ in kw.items()
                                           if k_ in ("causal", "window", "softcap")})
    ref = ref.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    return np.asarray(out, np.float32), np.asarray(ref, np.float32)


@pytest.mark.parametrize("B,S,H,KV,hd", [
    (1, 64, 2, 2, 64), (2, 128, 4, 2, 64), (1, 256, 4, 1, 128),
    (1, 128, 2, 2, 256),
])
def test_causal_sweep(key, B, S, H, KV, hd):
    out, ref = _run(key, B, S, H, KV, hd, jnp.float32, causal=True,
                    block_q=64, block_k=64)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)


def test_sliding_window(key):
    out, ref = _run(key, 1, 256, 2, 2, 64, jnp.float32, causal=True,
                    window=32, block_q=64, block_k=64)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)


def test_softcap(key):
    out, ref = _run(key, 1, 128, 2, 2, 64, jnp.float32, causal=True,
                    softcap=30.0, block_q=64, block_k=64)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)


def test_non_causal(key):
    out, ref = _run(key, 2, 128, 2, 2, 64, jnp.float32, causal=False,
                    block_q=64, block_k=64)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)


def test_bf16(key):
    out, ref = _run(key, 1, 128, 2, 2, 64, jnp.bfloat16, causal=True,
                    block_q=64, block_k=64)
    np.testing.assert_allclose(out, ref, atol=3e-2, rtol=3e-2)


def test_block_size_invariance(key):
    q = jax.random.normal(key, (1, 256, 2, 64))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 256, 2, 64))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 256, 2, 64))
    a = fops.flash_attention(q, k, v, block_q=64, block_k=64)
    b = fops.flash_attention(q, k, v, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_window_equals_full_when_larger_than_seq(key):
    out, ref = _run(key, 1, 128, 2, 2, 64, jnp.float32, causal=True,
                    window=4096, block_q=64, block_k=64)
    full, _ = _run(key, 1, 128, 2, 2, 64, jnp.float32, causal=True,
                   block_q=64, block_k=64)
    np.testing.assert_allclose(out, full, atol=2e-5)


@pytest.mark.parametrize("H,KV", [(4, 2), (4, 1), (8, 2)])
def test_gqa_inkernel_map_bitwise_vs_repeat(key, H, KV):
    """The grid→KV-row index map over compact (B·KV,…) K/V must be
    BIT-identical to feeding the kernel G×-repeated K/V with an identity
    map: same blocks, same accumulation order — only the memory footprint
    changed."""
    from repro.kernels.flash.kernel import flash_attention_bh
    B, S, hd = 2, 128, 64
    q = jax.random.normal(key, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, hd))
    G = H // KV
    qq = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kc = k.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    vc = v.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    kr = jnp.repeat(k, G, 2).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vr = jnp.repeat(v, G, 2).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    compact = flash_attention_bh(qq, kc, vc, causal=True, block_q=64,
                                 block_k=64, heads=H)
    repeat = flash_attention_bh(qq, kr, vr, causal=True, block_q=64,
                                block_k=64)
    assert (np.asarray(compact) == np.asarray(repeat)).all()


def test_flash_attention_grad_matches_ref(key):
    """custom_vjp backward (jnp-reference recompute) vs autodiff through
    the pure-jnp oracle — what makes attn_impl='pallas' trainable."""
    B, S, H, KV, hd = 1, 64, 4, 2, 32
    q = jax.random.normal(key, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, hd))

    def f_kernel(q, k, v):
        return jnp.sum(fops.flash_attention(q, k, v, causal=True,
                                            block_q=64, block_k=64) ** 2)

    def f_ref(q, k, v):
        G = H // KV
        qq = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
        kk = jnp.repeat(k, G, 2).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
        vv = jnp.repeat(v, G, 2).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
        o = fref.attention_bh(qq, kk, vv, causal=True)
        return jnp.sum(o.reshape(B, H, S, hd).transpose(0, 2, 1, 3) ** 2)

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4)


def test_model_attn_impl_pallas_matches_jnp(key):
    """cfg.attn_impl='pallas' routes forward through the kernel — outputs
    must match the jnp path."""
    from repro.configs import get_config
    from repro.models import transformer
    cfg = get_config("qwen3-8b", smoke=True).replace(vocab_size=256)
    params = transformer.init_params(cfg, key)
    toks = jax.random.randint(key, (2, 64), 0, cfg.vocab_size)
    lj, _ = transformer.forward(cfg, params, toks)
    lp, _ = transformer.forward(cfg.replace(attn_impl="pallas"), params, toks)
    np.testing.assert_allclose(np.asarray(lj), np.asarray(lp), atol=2e-4,
                               rtol=1e-3)


def test_model_attn_impl_pallas_head_sharded_matches_jnp(key):
    """Under a TP context the kernel runs per device on its head shard
    through ``jax.shard_map``; on a 1x1 mesh that path must match the jnp
    attention."""
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer
    from repro.models.tp import tp_shard_ctx
    cfg = get_config("qwen3-8b", smoke=True).replace(vocab_size=256)
    params = transformer.init_params(cfg, key)
    toks = jax.random.randint(key, (2, 64), 0, cfg.vocab_size)
    mesh = make_host_mesh(1, 1)

    @jax.jit
    def sharded(params, toks):
        with tp_shard_ctx(mesh, "model"):
            return transformer.forward(cfg.replace(attn_impl="pallas"),
                                       params, toks)[0]

    lj, _ = transformer.forward(cfg, params, toks)
    np.testing.assert_allclose(np.asarray(lj),
                               np.asarray(sharded(params, toks)),
                               atol=2e-4, rtol=1e-3)
