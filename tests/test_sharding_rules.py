"""PartitionSpec rule unit tests against an AbstractMesh(16,16) — no devices
needed; validates divisibility fallbacks and mode switches."""
import jax
import numpy as np
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.launch import sharding, specs

def _abstract_mesh(*axes):
    return AbstractMesh(tuple(s for _, s in axes), tuple(n for n, _ in axes))


MESH = _abstract_mesh(("data", 16), ("model", 16))
MESH3 = _abstract_mesh(("pod", 2), ("data", 16), ("model", 16))


def _specs_for(arch, **over):
    cfg = get_config(arch)
    if over:
        cfg = cfg.replace(**over)
    p_shape = specs.params_shape(cfg)
    return cfg, p_shape, sharding.param_specs(cfg, p_shape, MESH)


def test_tp_rules_olmo():
    cfg, p_shape, sp = _specs_for("olmo-1b")
    assert sp["embed"] == P("model", None)                    # vocab-sharded
    blk = sp["blocks"]["p0"]
    assert blk["mixer"]["wq"] == P(None, None, "model")       # (sb, d, q_dim)
    assert blk["mixer"]["wo"] == P(None, "model", None)
    assert blk["ffn"]["w_down"] == P(None, "model", None)


def test_small_dims_fall_back_to_replication():
    cfg, p_shape, sp = _specs_for("xlstm-350m")
    blk = sp["blocks"]["p0"]["mixer"]
    # w_if: (sb, di, 2H) with 2H=8 < 16 → replicated
    assert blk["w_if"] == P(None, None, None)
    assert blk["wq"] == P(None, None, "model")


def test_moe_tp_vs_ep():
    _, _, sp_tp = _specs_for("qwen3-moe-235b-a22b", moe_shard="tp")
    _, _, sp_ep = _specs_for("qwen3-moe-235b-a22b", moe_shard="ep")
    tp = sp_tp["blocks"]["p0"]["ffn"]
    ep = sp_ep["blocks"]["p0"]["ffn"]
    # (sb, E, d, f): TP shards f, EP shards E
    assert tp["w_gate"] == P(None, None, None, "model")
    assert ep["w_gate"] == P(None, "model", None, None)
    assert ep["w_down"] == P(None, "model", None, None)
    # granite: 32 experts also divide 16
    _, _, g = _specs_for("granite-moe-1b-a400m", moe_shard="ep")
    assert g["blocks"]["p0"]["ffn"]["w_up"] == P(None, "model", None, None)


def test_fsdp_mode_shards_largest_dim_over_both_axes():
    cfg, p_shape, sp = _specs_for("olmo-1b", shard_mode="fsdp")
    # embed (V_pad=50304? -> 50304 % 256 == 0) largest dim over (data, model)
    v = cfg.padded_vocab
    assert v % 256 == 0
    assert sp["embed"] == P(("data", "model"), None)
    blk = sp["blocks"]["p0"]
    # wq: (sb=16, 2048, 2048): largest divisible dim gets both axes
    assert ("data", "model") in tuple(blk["mixer"]["wq"])


def test_batch_specs_modes():
    cfg = get_config("olmo-1b")
    batch = specs.train_inputs(cfg, specs.INPUT_SHAPES["train_4k"])
    sp = sharding.batch_specs(cfg, batch, MESH)
    assert sp["tokens"] == P(("data",), None)
    sp3 = sharding.batch_specs(cfg, batch, MESH3)
    assert sp3["tokens"] == P(("pod", "data"), None)
    # fsdp: batch over all axes (256 % 256 == 0)
    spf = sharding.batch_specs(cfg.replace(shard_mode="fsdp"), batch, MESH)
    assert spf["tokens"] == P(("data", "model"), None)


def test_batch_indivisible_replicates():
    cfg = get_config("olmo-1b")
    import jax.numpy as jnp
    b = {"x": jax.ShapeDtypeStruct((3, 8), jnp.int32)}
    sp = sharding.batch_specs(cfg, b, MESH)
    assert sp["x"] == P(None, None)


def test_cache_shard_modes():
    cfg = get_config("gemma2-9b")
    _, _, cache = specs.decode_inputs(cfg, specs.INPUT_SHAPES["decode_32k"])
    # production default is "seq" (§Perf H2)
    seq = sharding.cache_specs(cfg, cache, MESH, shard_seq=False)
    assert seq["p0"]["k"] == P(None, ("data",), "model", None, None)
    hd = sharding.cache_specs(cfg.replace(cache_shard="hd"), cache, MESH,
                              shard_seq=False)
    k = hd["p0"]["k"]                       # (sb, B, S, KV, hd)
    assert k == P(None, ("data",), None, None, "model")
    bat = sharding.cache_specs(cfg.replace(cache_shard="batch"), cache, MESH,
                               shard_seq=False)
    assert bat["p0"]["k"] == P(None, ("data",), None, None, None)


def test_long_context_shard_seq():
    cfg = get_config("gemma2-9b")
    _, _, cache = specs.decode_inputs(cfg, specs.INPUT_SHAPES["long_500k"])
    sp = sharding.cache_specs(cfg.replace(cache_shard="hd"), cache, MESH,
                              shard_seq=True)
    k = sp["p0"]["k"]
    assert k[2] in ("data", ("data",))      # sequence axis sharded
    sp2 = sharding.cache_specs(cfg, cache, MESH, shard_seq=True)
    assert sp2["p0"]["k"][2] == ("data", "model")   # default "seq" 


def test_shard_seq_fallback_divisibility():
    """shard_seq fallback chain: (data, model) when S divides the full
    product, data-only when S divides only dp_size, REPLICATED otherwise —
    the dp fallback used to be unconditional, emitting invalid specs for
    sequence lengths not divisible by the data axis."""
    import jax.numpy as jnp
    cfg = get_config("gemma2-9b")          # cache_shard="seq" default
    seq_total = 16 * 16                    # data * model on MESH

    def k_spec(S):
        cache = {"p0": {"k": jax.ShapeDtypeStruct((2, 1, S, 2, 8),
                                                  jnp.bfloat16)}}
        sp = sharding.cache_specs(cfg, cache, MESH, shard_seq=True)
        return sp["p0"]["k"]

    assert k_spec(seq_total)[2] == ("data", "model")   # full split
    # PartitionSpec normalises a 1-tuple entry to its axis name, so each
    # entry is compared as a normalised one-entry spec
    assert P(k_spec(16 * 17)[2]) == P(("data",))       # dp-only fallback
    assert k_spec(274)[2] is None                      # 274 % 16 != 0
    # hd-mode: seq_total is dp_size only; same chain without `model`
    def k_spec_hd(S):
        cache = {"p0": {"k": jax.ShapeDtypeStruct((2, 1, S, 2, 32),
                                                  jnp.bfloat16)}}
        sp = sharding.cache_specs(cfg.replace(cache_shard="hd"), cache,
                                  MESH, shard_seq=True)
        return sp["p0"]["k"]

    assert P(k_spec_hd(32)[2]) == P(("data",))
    assert k_spec_hd(34)[2] is None


def test_prefill_out_spec_guards_compose():
    """The prefill logit out-spec's batch and vocab guards act on their own
    axes: a non-divisible global_batch drops ONLY the batch split and must
    not resurrect a vocab split the vocab guard rejected."""
    from repro.configs.base import InputShape
    from repro.launch.dryrun import prefill_out_spec
    cfg = get_config("olmo-1b")
    dp = ("data",)
    assert cfg.padded_vocab % 16 == 0
    ok = InputShape("p", 128, 32, "prefill")          # 32 % 16 == 0
    odd = InputShape("p", 128, 3, "prefill")          # 3 % 16 != 0
    assert prefill_out_spec(cfg, ok, MESH, dp) == P(dp, "model")
    assert prefill_out_spec(cfg, odd, MESH, dp) == P(None, "model")
    # a model axis the (256-padded) vocab does NOT divide: vocab never
    # sharded, whatever the batch does (this is the composition the
    # unconditional override used to break)
    mesh5 = _abstract_mesh(("data", 16), ("model", 5))
    assert cfg.padded_vocab % 5 != 0
    assert prefill_out_spec(cfg, ok, mesh5, dp) == P(dp, None)
    assert prefill_out_spec(cfg, odd, mesh5, dp) == P(None, None)


def test_applicability_rules():
    ok, _ = specs.applicable(get_config("xlstm-350m"), "long_500k")
    assert ok
    ok, _ = specs.applicable(get_config("jamba-v0.1-52b"), "long_500k")
    assert ok
    ok, _ = specs.applicable(get_config("gemma2-9b"), "long_500k")
    assert ok                               # sliding-window dense
    ok, why = specs.applicable(get_config("qwen3-8b"), "long_500k")
    assert not ok and "full-attention" in why
    for shape in ("train_4k", "prefill_32k", "decode_32k"):
        for arch in ("qwen3-8b", "seamless-m4t-medium"):
            ok, _ = specs.applicable(get_config(arch), shape)
            assert ok
