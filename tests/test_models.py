"""Per-arch smoke tests (deliverable f) + prefill/decode consistency."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.common.sharding import ShardingRules
from repro.models import init_params, loss_fn, transformer

RULES = ShardingRules(batch=None, fsdp=None, tensor=None, expert=None)
KEY = jax.random.PRNGKey(0)


def _batch_for(cfg, B=2, S=32):
    if cfg.family == "cnn":
        return {"images": jax.random.normal(KEY, (B, 28, 28, 1)),
                "labels": jnp.zeros((B,), jnp.int32)}
    if cfg.frontend == "frames":
        return {"frames": jax.random.normal(KEY, (B, S, cfg.d_model)),
                "labels": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)}
    b = {"tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)}
    if cfg.frontend == "patches":
        b["patches"] = jax.random.normal(KEY, (B, cfg.n_frontend_tokens, cfg.d_model))
    return b


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    """Reduced same-family config: one forward + one SGD step on CPU;
    output shapes correct, loss finite, no NaNs after the update."""
    cfg = configs.get_smoke(arch)
    params, _ = init_params(cfg, KEY)
    batch = _batch_for(cfg)
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: loss_fn(p, batch, cfg, RULES), has_aux=True)(params)
    assert np.isfinite(float(loss)), arch
    new_params = jax.tree.map(
        lambda p, g: (p.astype(jnp.float32) - 0.01 * g.astype(jnp.float32)
                      ).astype(p.dtype), params, grads)
    (loss2, _), = (loss_fn(new_params, batch, cfg, RULES),)
    loss2 = loss2[0] if isinstance(loss2, tuple) else loss2
    assert np.isfinite(float(loss2)), arch
    for leaf in jax.tree.leaves(new_params):
        assert not bool(jnp.any(jnp.isnan(leaf))), arch


@pytest.mark.parametrize("arch", [a for a in configs.ARCH_IDS if a != "femnist_cnn"])
def test_logit_shapes(arch):
    cfg = configs.get_smoke(arch)
    params, _ = init_params(cfg, KEY)
    batch = _batch_for(cfg, B=2, S=32)
    x, labels, _ = transformer.forward(params, batch, cfg, RULES)
    assert x.shape == (2, 32, cfg.d_model)
    logits = transformer.unembed(params, x, cfg, RULES)
    assert logits.shape == (2, 32, cfg.vocab_size)


@pytest.mark.parametrize("arch", ["qwen2_0_5b", "recurrentgemma_9b", "rwkv6_3b",
                                  "llama3_2_vision_90b", "qwen3_moe_30b_a3b",
                                  "musicgen_large"])
def test_prefill_decode_consistency(arch):
    """logits from [prefill(S) then decode(token S)] == full forward at S.

    This pins the KV-cache/ring-buffer/recurrent-state plumbing across every
    layer family to the training-path math.
    """
    cfg = configs.get_smoke(arch)
    # MoE capacity drops depend on group size; use einsum oracle + big cf to
    # make prefill(S) and forward numerically identical
    if cfg.n_experts:
        cfg = dataclasses.replace(cfg, moe_impl="einsum", capacity_factor=8.0)
    params, _ = init_params(cfg, KEY)
    B, S = 2, 16
    full = _batch_for(cfg, B, S + 1)
    x, _, _ = transformer.forward(params, full, cfg, RULES)
    want = transformer.unembed(params, x, cfg, RULES)[:, -1]  # logits at pos S

    prompt = jax.tree.map(lambda t: t[:, :S] if t.ndim >= 2 and t.shape[1] == S + 1 else t, full)
    if cfg.frontend == "patches":
        prompt["patches"] = full["patches"]
    logits_p, cache = transformer.prefill(params, prompt, cfg, RULES, cache_len=S + 1)

    if cfg.frontend == "frames":
        step = {"frames": full["frames"][:, S:S + 1],
                "pos": jnp.full((B, 1), S, jnp.int32)}
    else:
        step = {"tokens": full["tokens"][:, S:S + 1],
                "pos": jnp.full((B, 1), S, jnp.int32)}
        if cfg.frontend == "patches":
            step["media"] = full["patches"]
    got, _ = transformer.decode_step(params, step, cache, cfg, RULES)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=0.08, atol=0.08)


def test_head_padding_exactness():
    """Pad heads are masked out of the function: corrupting their weights
    (wq AND the matching wo rows' inputs) cannot change the output, and
    gradients into pad-head weights are exactly zero."""
    cfg = configs.get_smoke("deepseek_coder_33b")
    cfg_pad = dataclasses.replace(cfg, n_heads=6, n_kv_heads=2, head_dim=16)
    params, _ = init_params(cfg_pad, KEY, tp=4)    # pads 6 -> 8 query heads
    batch = _batch_for(cfg_pad, 2, 16)
    x1, _, _ = transformer.forward(params, batch, cfg_pad, RULES)
    p2 = jax.tree.map(lambda x: x, params)
    wq = p2["unit"]["0_attn"]["attn"]["wq"]
    p2["unit"]["0_attn"]["attn"]["wq"] = wq.at[:, :, 6:, :].set(99.0)
    x2, _, _ = transformer.forward(p2, batch, cfg_pad, RULES)
    np.testing.assert_allclose(np.asarray(x1, np.float32),
                               np.asarray(x2, np.float32), rtol=1e-5, atol=1e-5)
    # zero gradient into pad-head wq columns
    grads = jax.grad(lambda p: loss_fn(p, batch, cfg_pad, RULES)[0])(params)
    gq = np.asarray(grads["unit"]["0_attn"]["attn"]["wq"], np.float32)
    assert np.abs(gq[:, :, 6:, :]).max() == 0.0


def _dense_causal_attention(q, k, v, window):
    """Plain masked-softmax attention, every query against every key."""
    B, S, H, hd = q.shape
    kv = np.arange(H) // (H // k.shape[2])        # query head -> its KV head
    logits = np.einsum("bqhd,bshd->bhqs", q, k[:, :, kv]) / np.sqrt(hd)
    pos = np.arange(S)
    mask = pos[None, :] <= pos[:, None]
    if window:
        mask &= pos[None, :] > pos[:, None] - window
    logits = np.where(mask, logits, -np.inf)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    return np.einsum("bhqs,bshd->bqhd", probs, v[:, :, kv])


@pytest.mark.parametrize("window", [0, 6])
def test_causal_attention_matches_dense_reference(window):
    """The query-block scan (4 blocks of q_chunk=16, GQA 4:2) against plain
    masked-softmax attention; window 6 < q_chunk slides across block
    edges."""
    from repro.models.config import ModelConfig
    from repro.models.layers import causal_attention
    cfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64,
                      head_dim=16, q_chunk=16)
    ks = jax.random.split(KEY, 3)
    B, S = 2, 64
    q = jax.random.normal(ks[0], (B, S, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, 2, 16), jnp.float32)
    got = causal_attention(q, k, v, cfg, RULES, window=window)
    want = _dense_causal_attention(*(np.asarray(t, np.float64)
                                     for t in (q, k, v)), window)
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               rtol=1e-5, atol=1e-5)


def test_param_counts_match_analytic():
    for arch in ("olmo_1b", "qwen2_0_5b"):
        cfg = configs.get(arch)
        params, _ = init_params(cfg, abstract=True)
        n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
        # analytic count ignores norm scales/biases and head padding —
        # within 5%
        assert abs(n - cfg.param_count) / cfg.param_count < 0.05, arch


def test_full_configs_match_assignment():
    """The exact numbers from the assignment table."""
    c = configs.get("arctic-480b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size, c.n_experts, c.top_k, c.dense_residual) == \
        (35, 7168, 56, 8, 4864, 32000, 128, 2, True)
    c = configs.get("qwen3-moe-30b-a3b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size, c.n_experts, c.top_k) == \
        (48, 2048, 32, 4, 768, 151936, 128, 8)
    c = configs.get("qwen1.5-110b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size, c.qkv_bias) == (80, 8192, 64, 8, 49152, 152064, True)
    c = configs.get("recurrentgemma-9b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size) == (38, 4096, 16, 1, 12288, 256000)
    assert c.block_pattern == ("rglru", "rglru", "attn")
    assert c.is_subquadratic
    c = configs.get("rwkv6-3b")
    assert (c.n_layers, c.d_model, c.d_ff, c.vocab_size) == (32, 2560, 8960, 65536)
    assert c.is_subquadratic
    c = configs.get("llama-3.2-vision-90b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size) == (100, 8192, 64, 8, 28672, 128256)
    assert not c.is_subquadratic  # long_500k skipped, documented
    c = configs.get("musicgen-large")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size) == (48, 2048, 32, 32, 8192, 2048)
    c = configs.get("deepseek-coder-33b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size) == (62, 7168, 56, 8, 19200, 32256)
    c = configs.get("olmo-1b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size, c.norm) == (16, 2048, 16, 16, 8192, 50304, "nonparam")
    c = configs.get("qwen2-0.5b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size, c.qkv_bias) == (24, 896, 14, 2, 4864, 151936, True)
