"""Faithful FL engine: round mechanics, equivalences, learning progress."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, fl, obs
from repro.core import factored, fedavg, selection
from repro.core.fedavg import FLConfig
from repro.data import femnist
from repro.models import femnist_cnn
from repro.pon import PonConfig, round_times


def _loss(params, batch):
    return femnist_cnn.loss_fn(params, batch)


def test_local_sgd_reduces_loss():
    cfg = configs.get("femnist_cnn").reduced()
    params, _ = femnist_cnn.init_params(cfg, jax.random.PRNGKey(0))
    clients, _ = femnist.generate(femnist.FemnistConfig(n_clients=2, seed=3))
    rng = np.random.default_rng(0)
    batches = jax.tree.map(jnp.asarray,
                           femnist.client_minibatches(rng, clients[0], 20, 10))
    l0 = float(_loss(params, jax.tree.map(lambda x: x[0], batches))[0])
    p2, _ = fedavg.local_sgd(params, batches, _loss, lr=0.05, steps=20)
    l1 = float(_loss(p2, jax.tree.map(lambda x: x[0], batches))[0])
    assert l1 < l0


def test_sfl_and_classical_updates_identical():
    """Same mask ⇒ SFL and classical produce the SAME global model (the
    paper's difference is transport, not math)."""
    cfg = configs.get("femnist_cnn").reduced()
    params, _ = femnist_cnn.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    C = 12
    deltas = jax.tree.map(
        lambda p: jnp.asarray(rng.normal(size=(C,) + p.shape).astype(np.float32)),
        params)
    weights = jnp.asarray(rng.uniform(10, 100, C).astype(np.float32))
    mask = jnp.asarray((rng.random(C) > 0.3).astype(np.float32))
    onu = jnp.asarray(rng.integers(0, 4, C))
    p_sfl, s1 = fedavg.apply_round(params, deltas, weights, mask, onu, 4, "sfl")
    p_cls, s2 = fedavg.apply_round(params, deltas, weights, mask, onu, 4, "classical")
    for a, b in zip(jax.tree.leaves(p_sfl), jax.tree.leaves(p_cls)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
    # ... but the uplink accounting differs: ≤4 θ vs every involved client
    assert float(s1["uplink_models"]) <= 4 < float(s2["uplink_models"])


def test_fl_round_end_to_end_accuracy_improves():
    """A few SFL rounds on synthetic FEMNIST beat the initial model."""
    cfg = configs.get("femnist_cnn").reduced()
    params, _ = femnist_cnn.init_params(cfg, jax.random.PRNGKey(0))
    fl = FLConfig(n_onus=4, clients_per_onu=5, n_selected=10,
                  local_steps=8, local_batch=10, local_lr=0.08)
    data_cfg = femnist.FemnistConfig(n_clients=fl.n_clients, seed=5)
    clients, eval_set = femnist.generate(data_cfg)
    eval_batch = jax.tree.map(jnp.asarray, eval_set)
    counts = femnist.sample_counts(clients)
    onu = fedavg.onu_of_client(fl)
    pon = PonConfig(n_onus=fl.n_onus, clients_per_onu=fl.clients_per_onu)
    rng = np.random.default_rng(0)

    acc0 = float(_loss(params, eval_batch)[1]["acc"])
    for rnd in range(6):
        sel = selection.select_clients(rng, fl.n_clients, fl.n_selected)
        rt = round_times(pon, rng, sel, onu, counts, "sfl")
        cb = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[femnist.client_minibatches(rng, clients[c], fl.local_steps,
                                         fl.local_batch) for c in sel])
        deltas, _ = fedavg.train_selected_clients(params, cb, _loss, fl)
        params, stats = fedavg.apply_round(
            params, deltas, jnp.asarray(counts[sel]),
            jnp.asarray(rt["involved"]), jnp.asarray(onu[sel]), fl.n_onus, "sfl")
    acc1 = float(_loss(params, eval_batch)[1]["acc"])
    assert acc1 > acc0 + 0.05, (acc0, acc1)


def test_overselection_backup():
    rng = np.random.default_rng(0)
    sel = selection.select_clients(rng, 100, 20, overselect=0.3)
    assert len(sel) == 26
    assert len(np.unique(sel)) == 26


FC1 = 3136 * 2048       # the full CNN's dense weight, 6,422,528 params


def _client_batches(n_clients, steps, batch, seed=3):
    clients, _ = femnist.generate(
        femnist.FemnistConfig(n_clients=n_clients, seed=seed))
    rng = np.random.default_rng(0)
    return jax.tree.map(lambda *xs: jnp.stack(xs), *[
        femnist.client_minibatches(rng, c, steps, batch) for c in clients])


def _dense_sgd(params, batches, lr, loss=_loss):
    """Plain H-step SGD, every weight a dense per-client copy."""
    def step(p, b):
        (l, _), g = jax.value_and_grad(loss, has_aux=True)(p, b)
        return jax.tree.map(
            lambda w, gw: (w.astype(jnp.float32) - lr * gw).astype(w.dtype),
            p, g), l
    p, losses = jax.lax.scan(step, params, batches)
    return jax.tree.map(lambda a, b: a.astype(jnp.float32) - b, p, params), \
        jnp.mean(losses)


def test_factored_local_update_matches_dense_sgd():
    """Full-width CNN, H=8, B=10: the update that carries ``fc1_w`` as the
    global weight plus the step history follows plain dense SGD, and a
    padding client (its minibatch mask all zero) moves nothing."""
    params, _ = femnist_cnn.init_params(configs.get("femnist_cnn"),
                                        jax.random.PRNGKey(0))
    H, B, lr = 8, 10, 0.01
    cb = _client_batches(3, H, B)
    cb["mask"] = jnp.ones(cb["labels"].shape, jnp.float32).at[2].set(0.0)
    flc = FLConfig(local_steps=H, local_batch=B, local_lr=lr, client_chunk=4)
    with jax.default_matmul_precision("highest"):
        program = fedavg.chunk_program(_loss, flc)
        deltas, losses = program(params, cb)
        ref, ref_losses = jax.vmap(_dense_sgd, in_axes=(None, 0, None))(
            params, cb, lr)
        p, _ = fedavg.local_sgd(params, jax.tree.map(lambda x: x[0], cb),
                                _loss, lr, H)
    assert program.factored_params == FC1
    np.testing.assert_allclose(np.asarray(losses), np.asarray(ref_losses),
                               rtol=1e-6, atol=1e-6)
    for name in ref:
        got, want = np.asarray(deltas[name]), np.asarray(ref[name])
        gap = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert gap < 1e-4, (name, gap)
        assert not np.any(got[2]), name
    np.testing.assert_allclose(np.asarray(p["fc1_w"] - params["fc1_w"]),
                               np.asarray(deltas["fc1_w"][0]), atol=1e-6)


def _toy_loss(params, batch):
    """A linear model that uses its weight as an array, not via dense."""
    err = batch["x"] @ params["w"] - batch["y"]
    return jnp.mean(err ** 2), {}


def _eligibility_case(case):
    """(loss, params, client batches, FLConfig, strategy) of one case."""
    cnn = configs.get("femnist_cnn")
    small = cnn.reduced()       # fc1_w 392 x 64: factored iff H*B < 55
    H, B = {"cnn": (8, 10), "small_hb50": (5, 10), "small_hb80": (8, 10),
            "small_bf16": (5, 10), "small_fedprox": (5, 10),
            "toy": (2, 2)}[case]
    flc = FLConfig(local_steps=H, local_batch=B, local_lr=0.01,
                   client_chunk=2)
    strategy = "fedprox" if case == "small_fedprox" else "sfl_two_step"
    if case == "toy":
        rng = np.random.default_rng(0)
        params = {"w": jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)}
        cb = {"x": jnp.asarray(rng.normal(size=(2, H, B, 64)), jnp.float32),
              "y": jnp.asarray(rng.normal(size=(2, H, B, 32)), jnp.float32)}
        return _toy_loss, params, cb, flc, strategy
    params, _ = femnist_cnn.init_params(cnn if case == "cnn" else small,
                                        jax.random.PRNGKey(0))
    cb = _client_batches(2, H, B)
    if case == "small_bf16":
        params = jax.tree.map(lambda w: w.astype(jnp.bfloat16), params)
        cb["images"] = cb["images"].astype(jnp.bfloat16)
    return _loss, params, cb, flc, strategy


@pytest.mark.parametrize("case,planned,n_params", [
    ("cnn", ["fc1_w"], FC1),
    ("small_hb50", ["fc1_w"], 392 * 64),
    ("small_hb80", [], 0),              # 80 * (392 + 64) > 392 * 64
    ("small_bf16", [], 0),
    ("small_fedprox", ["fc1_w"], 0),    # local_sgd_prox stays dense
    ("toy", [], 0),                     # the loss declares nothing
])
def test_factored_eligibility(case, planned, n_params):
    """Which weights ``factored.plan`` picks, observed from the loss and
    the shapes, and the ``train.chunk`` span's ``factored_params``: what
    the client update that ran carries factored. Where FedAvg's update
    picks nothing, its deltas are plain dense SGD's, bit for bit."""
    from repro.obs.context import Obs
    from repro.obs.tracer import Tracer

    loss, params, cb, flc, strategy = _eligibility_case(case)
    paths, treedef = jax.tree_util.tree_flatten_with_path(params)
    one = jax.tree.map(lambda x: x[0], cb)
    picked = factored.plan(loss, [w for _, w in paths], treedef, one)
    assert [paths[i][0][0].key for i in picked] == planned
    t = Tracer()
    with obs.use(Obs(tracer=t)):
        deltas, _ = fedavg.train_selected_clients(
            params, cb, loss, flc,
            local_update=fl.make_strategy(strategy).local_update)
    spans = [s.args for s in t.spans if s.name == "train.chunk"]
    assert [s["factored_params"] for s in spans] == [n_params]
    if not planned:
        ref, _ = jax.jit(jax.vmap(_dense_sgd, in_axes=(None, 0, None, None)),
                         static_argnums=3)(params, cb, flc.local_lr, loss)
        for name in ref:
            np.testing.assert_array_equal(np.asarray(deltas[name]),
                                          np.asarray(ref[name]), name)
