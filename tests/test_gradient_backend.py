"""The gradient regime's inputs: weights and tokens from the caller, the
clients' own streams in row order, its spans and counters; and the model
conventions it runs OLMo with (no embedding scale, norm eps 1e-5, weights
from the caller's key alone)."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, fl
from repro.common.sharding import ShardingRules
from repro.data import lm
from repro.launch.mesh import make_test_mesh
from repro.launch.train import build_rules
from repro.models import layers as L
from repro.models import transformer
from repro.obs import context as obs_context
from repro.obs import Obs

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
RULES = ShardingRules(batch=None, fsdp=None, tensor=None, expert=None)
BATCH, SEQ, SEED = 4, 16, 11


def _digest(env_seed: str) -> str:
    code = textwrap.dedent("""
        import hashlib, jax, numpy as np
        from repro import configs
        from repro.models import transformer
        p, _ = transformer.init_params(configs.get_smoke("olmo_1b"),
                                       jax.random.PRNGKey(3))
        h = hashlib.sha256()
        for x in jax.tree.leaves(p):
            h.update(np.asarray(x, np.float32).tobytes())
        print(h.hexdigest())
    """)
    env = dict(os.environ, PYTHONHASHSEED=env_seed, JAX_PLATFORMS="cpu",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True)
    return out.stdout.split()[-1]


def test_init_params_is_the_same_in_every_process():
    """Two processes with differently salted ``hash()`` make the same
    weights from the same key (the stacked layers once came from the
    model's name's hash)."""
    assert _digest("1") == _digest("2")


def test_init_params_stacked_layers_follow_the_key():
    cfg = configs.get_smoke("olmo_1b")
    a, _ = transformer.init_params(cfg, jax.random.PRNGKey(3))
    b, _ = transformer.init_params(cfg, jax.random.PRNGKey(4))
    wa = np.asarray(a["unit"]["0_attn"]["attn"]["wq"], np.float32)
    wb = np.asarray(b["unit"]["0_attn"]["attn"]["wq"], np.float32)
    assert not np.array_equal(wa, wb)
    assert not np.array_equal(wa[0], wa[1])     # distinct per layer


def test_only_recurrentgemma_scales_its_embedding():
    assert [a for a in configs.ARCH_IDS
            if configs.get(a).embed_scale] == ["recurrentgemma_9b"]
    cfg = configs.get_smoke("olmo_1b")
    params, _ = transformer.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.arange(2 * 8).reshape(2, 8) % cfg.vocab_size}
    plain = transformer.embed_inputs(params, batch, cfg, RULES)[0]
    table = np.asarray(params["embed"], np.float32)
    np.testing.assert_array_equal(np.asarray(plain, np.float32),
                                  table[np.asarray(batch["tokens"])])
    scaled_cfg = dataclasses.replace(cfg, embed_scale=True)
    scaled = transformer.embed_inputs(params, batch, scaled_cfg, RULES)[0]
    # sqrt(64) = 8: exact in bfloat16
    np.testing.assert_array_equal(np.asarray(scaled, np.float32),
                                  np.asarray(plain, np.float32) * 8.0)


def test_olmo_norm_uses_eps_1e5():
    """OLMo's LayerNorm has eps 1e-5 (the model default is 1e-6), and the
    model's norms read it: on a row whose variance is 1e-5 the two give
    outputs a factor sqrt(2) apart."""
    assert configs.get("olmo_1b").norm_eps == 1e-5
    assert configs.get("olmo_100m").norm_eps == 1e-5
    assert configs.get("qwen2_0_5b").norm_eps == 1e-6
    cfg = configs.get_smoke("olmo_1b", dtype="float32", tie_embeddings=False)
    d = cfg.d_model
    x = jnp.asarray(np.tile([1.0, -1.0], d // 2) * np.sqrt(1e-5),
                    jnp.float32)[None, None]
    params = {"final_norm": {}, "lm_head": jnp.eye(d, cfg.vocab_size)}
    got = np.asarray(transformer.unembed(params, x, cfg, RULES))[0, 0, :d]
    want = np.asarray(x)[0, 0] / np.sqrt(1e-5 + 1e-5)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(L.norm(x, {}, "nonparam"))[0, 0],
        np.asarray(x)[0, 0] / np.sqrt(1e-5 + 1e-6), rtol=1e-5)


def _backend(**kw):
    cfg = configs.get_smoke("olmo_1b")
    exp = fl.ExperimentConfig(strategy="sfl_two_step", seed=SEED)
    strategy = exp.make_strategy()
    mesh = make_test_mesh((1, 1), ("data", "model"))
    backend = fl.GradientBackend(
        cfg, strategy, mesh, build_rules(mesh, strategy.transport),
        batch=BATCH, seq=SEQ, seed=SEED,
        sample_counts=np.arange(100, 110, dtype=np.float32),
        onu_ids=np.arange(10) // 5, **kw)
    seen = []
    step = backend.train_step

    def recording(params, opt_state, batch):
        seen.append(jax.tree.map(np.asarray, batch))
        return step(params, opt_state, batch)
    backend.train_step = recording
    return backend, seen


@pytest.mark.parametrize("selected,mask,rows,weights", [
    ([5, 2, 7, 1], [1, 0, 1, 1], [5, 2, 7, 1], [105, 0, 107, 101]),
    # over-selected: the involved clients first, in selection order
    ([5, 2, 7, 1, 9, 3], [1, 0, 1, 0, 1, 1], [5, 7, 9, 3],
     [105, 107, 109, 103]),
    # fewer than the rows: copies of the first at weight 0
    ([6, 4, 8], [1, 1, 0], [6, 4, 8, 6], [106, 104, 0, 0]),
], ids=["one_per_row", "overselected", "padded"])
def test_rows_are_the_selected_clients_own_streams(selected, mask, rows,
                                                    weights):
    """Row r holds client ``rows[r]``'s own stream (``data.lm``), the
    sequence drawn from the round's generator, and its weight k * mask."""
    backend, seen = _backend()
    rng = np.random.default_rng(99)
    backend.run_round(0, np.asarray(selected), np.asarray(mask, np.float32),
                      {}, rng)
    (batch,) = seen
    want = lm.client_rows(SEED, SEQ, backend.cfg.vocab_size)(
        np.random.default_rng(99), np.asarray(rows))
    np.testing.assert_array_equal(batch["tokens"], want)
    np.testing.assert_array_equal(batch["client_weight"], weights)
    # the round's generator moved on by exactly those draws
    ref = np.random.default_rng(99)
    ref.integers(0, lm.CLIENT_ROWS, size=BATCH)
    assert rng.random() == ref.random()
    for r, c in enumerate(rows):
        stream = lm.client_lm_batches(SEED, c, lm.CLIENT_ROWS, 1, SEQ,
                                      backend.cfg.vocab_size)["tokens"][:, 0]
        assert any(np.array_equal(batch["tokens"][r], s) for s in stream)


def test_replay_consumes_the_rounds_draws():
    backend, seen = _backend()
    sel, mask = np.array([3, 1, 4, 0]), np.ones(4, np.float32)
    a, b = np.random.default_rng(5), np.random.default_rng(5)
    backend.run_round(0, sel, mask, {}, a)
    backend.replay_round(0, sel, mask, {}, b)
    assert a.random() == b.random() and len(seen) == 1


def test_caller_weights_and_tokens_are_used():
    """Weights handed in are the ones trained (placed, not redrawn); the
    caller's ``batch_fn`` makes the rows."""
    cfg = configs.get_smoke("olmo_1b")
    mine, _ = transformer.init_params(cfg, jax.random.PRNGKey(42), tp=1)
    rows = np.arange(BATCH * SEQ, dtype=np.int32).reshape(BATCH, SEQ) % 7
    calls = []

    def batch_fn(rng, clients):
        calls.append(np.asarray(clients))
        return rows
    backend, seen = _backend(params=mine, batch_fn=batch_fn)
    for a, b in zip(jax.tree.leaves(backend.params), jax.tree.leaves(mine)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    backend.run_round(0, np.array([2, 0, 1, 3]), np.ones(4, np.float32), {},
                      np.random.default_rng(0))
    np.testing.assert_array_equal(calls[0], [2, 0, 1, 3])
    np.testing.assert_array_equal(seen[0]["tokens"], rows)


def test_tokens_counter_counts_weighted_rows():
    """``lm.tokens`` adds the tokens of the rows whose weight is > 0."""
    obs = Obs()
    with obs_context.use(obs):
        backend, _ = _backend()
        backend.run_round(0, np.array([0, 1, 2, 3]),
                          np.array([1, 0, 1, 1], np.float32), {},
                          np.random.default_rng(0))
        backend.run_round(1, np.array([4, 5, 6, 7]),
                          np.array([1, 1, 1, 1], np.float32), {},
                          np.random.default_rng(1))
    assert obs.metrics.counter("lm.tokens").total == (3 + 4) * SEQ
    assert obs.metrics.histogram("backend.train_step_s").count == 2
