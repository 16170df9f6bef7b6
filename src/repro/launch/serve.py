"""Batched serving driver: prefill + token-by-token decode.

CPU-runnable with --smoke; ``launch.specs.input_specs`` lowers the same
step functions for prefill and decode shapes on any mesh.

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b --smoke \
      --batch 4 --prompt-len 64 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.common.sharding import ShardingRules
from repro.launch.mesh import make_test_mesh
from repro.models import transformer


def decode_frames(key, step: int, batch: int, d_model: int):
    """Per-decode-step synthetic frame input: one fresh key per step.

    Folding the step index into the data key is what makes consecutive
    decode steps see *different* frames — reusing ``key`` directly would
    replay the identical array every step (the REPRO203 bug class; pinned
    by tests/test_lint.py::test_serve_decode_frames_differ_per_step).
    """
    return jax.random.normal(jax.random.fold_in(key, step),
                             (batch, 1, d_model), jnp.bfloat16)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    mesh = make_test_mesh((len(jax.devices()), 1), ("data", "model"))
    rules = ShardingRules(batch=("data",), fsdp=None, tensor=None, expert=None)
    # one root key, split once: init / prompt data / decode frames / token
    # sampling each own an independent stream (a key is consumed at most
    # once — REPRO203)
    k_init, k_data, k_decode, k_sample = jax.random.split(
        jax.random.PRNGKey(args.seed), 4)
    cache_len = args.prompt_len + args.gen

    with jax.set_mesh(mesh):
        params, _ = transformer.init_params(cfg, k_init)
        B, P = args.batch, args.prompt_len
        if cfg.frontend == "frames":
            batch = {"frames": jax.random.normal(
                         k_data, (B, P, cfg.d_model), jnp.bfloat16),
                     "labels": jnp.zeros((B, P), jnp.int32)}
        else:
            batch = {"tokens": jax.random.randint(k_data, (B, P), 0,
                                                  cfg.vocab_size)}
        media = None
        if cfg.frontend == "patches":
            media = jax.random.normal(
                jax.random.fold_in(k_data, 1),
                (B, cfg.n_frontend_tokens, cfg.d_model), jnp.bfloat16)
            batch["patches"] = media

        prefill = jax.jit(lambda p, b: transformer.prefill(p, b, cfg, rules, cache_len))
        decode = jax.jit(lambda p, b, c: transformer.decode_step(p, b, c, cfg, rules))

        t0 = time.time()
        logits, cache = prefill(params, batch)
        logits.block_until_ready()
        t_prefill = time.time() - t0
        print(f"prefill {B}x{P}: {t_prefill:.2f}s "
              f"({B*P/t_prefill:.0f} tok/s)")

        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        out = [tok]
        t0 = time.time()
        for i in range(args.gen):
            step_batch = {"tokens": tok,
                          "pos": jnp.full((B, 1), P + i, jnp.int32)}
            if cfg.frontend == "frames":
                # decode_frames folds the step index into the key, so the
                # repeated k_decode use is a derivation, not a reuse
                step_batch = {"frames": decode_frames(k_decode, i, B,  # repro: noqa(REPRO203)
                                                      cfg.d_model),
                              "pos": jnp.full((B, 1), P + i, jnp.int32)}
            if media is not None:
                step_batch["media"] = media
            logits, cache = decode(params, step_batch, cache)
            if args.temperature > 0:
                k_sample, sk = jax.random.split(k_sample)
                tok = jax.random.categorical(sk, logits / args.temperature)[:, None]
            else:
                tok = jnp.argmax(logits, -1)[:, None]
            tok = tok.astype(jnp.int32)
            out.append(tok)
        jax.block_until_ready(tok)
        dt = time.time() - t0
        print(f"decode {args.gen} steps: {dt:.2f}s "
              f"({B*args.gen/dt:.1f} tok/s, {dt/args.gen*1e3:.1f} ms/step)")
        toks = jnp.concatenate(out, axis=1)
        print("sample token ids[0]:", np.asarray(toks[0])[:16].tolist())


if __name__ == "__main__":
    main()
