"""repro.fl: strategy registry, RoundLoop driver, and the bit-for-bit
regression pin against the pre-refactor bench_accuracy loop."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, fl
from repro.core import aggregation, fedavg, selection
from repro.core.fedavg import FLConfig
from repro.data import femnist
from repro.models import femnist_cnn
from repro.pon import PonConfig


def _loss(params, batch):
    return femnist_cnn.loss_fn(params, batch)


# ---------------------------------------------------------------- registry

def test_registry_ships_required_strategies():
    names = fl.strategy_names()
    for required in ("sfl_two_step", "classical", "fedprox", "fedopt"):
        assert required in names, names
    # legacy mode strings resolve through aliases
    assert fl.canonical_name("sfl") == "sfl_two_step"
    assert isinstance(fl.make_strategy("sfl"), fl.SflTwoStep)
    with pytest.raises(KeyError):
        fl.canonical_name("nope")


def test_every_registered_strategy_matches_numpy_oracle():
    """aggregate() of every strategy == the numpy weighted mean on a toy
    pytree — the paper's central identity holds across the registry."""
    rng = np.random.default_rng(3)
    C, n_onus = 14, 4
    tree = {"w": jnp.asarray(rng.normal(size=(C, 5, 2)).astype(np.float32)),
            "b": jnp.asarray(rng.normal(size=(C, 3)).astype(np.float32))}
    weights = jnp.asarray(rng.uniform(1, 80, C).astype(np.float32))
    mask = jnp.asarray((rng.random(C) > 0.4).astype(np.float32))
    onu = jnp.asarray(rng.integers(0, n_onus, C))
    for name in fl.strategy_names():
        strat = fl.make_strategy(name)
        agg, stats = strat.aggregate(tree, weights, mask, onu, n_onus)
        assert float(stats["involved"]) == float(jnp.sum(mask))
        for k in tree:
            want, K = aggregation.numpy_weighted_mean(
                np.asarray(tree[k]), np.asarray(weights), np.asarray(mask))
            np.testing.assert_allclose(np.asarray(agg[k]), want,
                                       rtol=1e-4, atol=1e-4, err_msg=name)
            assert np.isclose(float(stats["K"]), K), name


def _toy_client():
    cfg = configs.get("femnist_cnn").reduced()
    params, _ = femnist_cnn.init_params(cfg, jax.random.PRNGKey(0))
    clients, _ = femnist.generate(femnist.FemnistConfig(n_clients=1, seed=11))
    rng = np.random.default_rng(0)
    batches = jax.tree.map(
        jnp.asarray, femnist.client_minibatches(rng, clients[0], 4, 8))
    flc = FLConfig(local_steps=4, local_batch=8, local_lr=0.05)
    return params, batches, flc


def test_fedprox_mu_zero_reduces_to_fedavg():
    params, batches, flc = _toy_client()
    d_avg, _ = fl.make_strategy("sfl_two_step").local_update(
        params, batches, _loss, flc)
    d_prox0, _ = fl.make_strategy("fedprox", mu=0.0).local_update(
        params, batches, _loss, flc)
    for a, b in zip(jax.tree.leaves(d_avg), jax.tree.leaves(d_prox0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_fedprox_pulls_toward_global():
    """Larger mu ⇒ smaller local drift from the global model."""
    params, batches, flc = _toy_client()
    norm = {}
    for mu in (0.0, 10.0):
        d, _ = fl.make_strategy("fedprox", mu=mu).local_update(
            params, batches, _loss, flc)
        norm[mu] = float(sum(jnp.sum(jnp.square(x))
                             for x in jax.tree.leaves(d)))
    assert norm[10.0] < norm[0.0]


def test_fedopt_server_update_steps_with_optimizer_state():
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(4, 3)).astype(np.float32))}
    delta = {"w": jnp.asarray(rng.normal(size=(4, 3)).astype(np.float32))}
    for opt in ("adamw", "yogi"):
        strat = fl.make_strategy("fedopt", server_opt=opt, server_lr=0.1)
        state = strat.init_state(params)
        p1, state = strat.server_update(params, delta, state)
        p2, state = strat.server_update(p1, delta, state)
        assert int(state["t"]) == 2
        assert np.all(np.isfinite(np.asarray(p2["w"])))
        assert not np.allclose(np.asarray(p1["w"]), np.asarray(params["w"]))
        # adaptive step still moves in the delta's direction on average
        moved = np.sign(np.asarray(p1["w"]) - np.asarray(params["w"]))
        agree = np.mean(moved == np.sign(np.asarray(delta["w"])))
        assert agree > 0.9, (opt, agree)


# ---------------------------------------------------------------- RoundLoop

def _old_bench_accuracy_loop(n_rounds, n_selected, seed, modes, pon):
    """The pre-refactor bench_accuracy.run loop, verbatim — the regression
    oracle the RoundLoop must reproduce bit for bit."""
    cfg = configs.get("femnist_cnn").reduced()
    topo = {"n_onus": pon.n_onus, "clients_per_onu": pon.clients_per_onu}
    flc = FLConfig(n_selected=n_selected, local_steps=8, local_lr=0.06,
                   pon=pon, **topo)
    data_cfg = femnist.FemnistConfig(n_clients=flc.n_clients, seed=seed + 7)
    clients, eval_set = femnist.generate(data_cfg)
    eval_batch = jax.tree.map(jnp.asarray, eval_set)
    counts = femnist.sample_counts(clients)
    onu = fedavg.onu_of_client(flc)
    results = {}
    for mode in modes:
        rng = np.random.default_rng(seed)
        params, _ = femnist_cnn.init_params(cfg, jax.random.PRNGKey(seed))
        accs, involved_hist = [], []
        fl_mode = dataclasses.replace(flc, mode=mode)
        for rnd in range(n_rounds):
            sel = selection.select_clients(rng, flc.n_clients, flc.n_selected)
            rt = fedavg.round_transport(fl_mode, rng, sel, counts, onu)
            mask = rt["involved"]
            involved_hist.append(float(mask.sum()))
            active = sel[mask > 0]
            if len(active) == 0:
                accs.append(accs[-1] if accs else 0.0)
                continue
            pad = (-len(active)) % flc.client_chunk
            padded = np.concatenate([active, np.full(pad, active[0])])
            w = np.concatenate([counts[active], np.zeros(pad, np.float32)])
            cb = jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[femnist.client_minibatches(rng, clients[c], flc.local_steps,
                                             flc.local_batch) for c in padded])
            deltas, _ = fedavg.train_selected_clients(params, cb, _loss, flc)
            params, _ = fedavg.apply_round(
                params, deltas, jnp.asarray(w),
                jnp.concatenate([jnp.ones(len(active)), jnp.zeros(pad)]),
                jnp.asarray(onu[padded]), flc.n_onus, mode)
            accs.append(float(_loss(params, eval_batch)[1]["acc"]))
        results[mode] = {"accs": accs, "involved": involved_hist}
    return results


def test_roundloop_bit_for_bit_vs_prerefactor_trajectory():
    """RoundLoop + sfl_two_step/classical == the pre-refactor bench_accuracy
    loop, exactly, at fixed seed (3 rounds, small topology)."""
    from benchmarks import bench_accuracy
    pon = PonConfig(n_onus=4, clients_per_onu=5)
    old = _old_bench_accuracy_loop(3, 10, 0, ("classical", "sfl"), pon)
    new = bench_accuracy.run(n_rounds=3, n_selected=10, seed=0,
                             modes=("classical", "sfl"), pon=pon)
    for mode in ("classical", "sfl"):
        assert old[mode]["accs"] == new[mode]["accs"], mode
        assert old[mode]["involved"] == new[mode]["involved"], mode


def _eager_chunks(params, cb, flc, local_update):
    """The per-call eager ``jax.vmap`` that the held chunk program replaced."""
    n_sel = jax.tree.leaves(cb)[0].shape[0]
    fn = jax.vmap(lambda b: local_update(params, b, _loss, flc))
    out = [fn(jax.tree.map(lambda x: x[lo:lo + flc.client_chunk], cb))
           for lo in range(0, n_sel, flc.client_chunk)]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *out)


@pytest.mark.parametrize("strategy,kw", [("sfl_two_step", {}),
                                         ("fedprox", {"mu": 0.3})],
                         ids=["fedavg", "fedprox"])
def test_chunk_program_matches_eager_vmap(strategy, kw):
    """The held chunk program computes what the eager per-call vmap did,
    round after round with new global params, ragged last chunk included."""
    cfg = configs.get("femnist_cnn").reduced()
    params, _ = femnist_cnn.init_params(cfg, jax.random.PRNGKey(0))
    clients, _ = femnist.generate(femnist.FemnistConfig(n_clients=6, seed=11))
    flc = FLConfig(local_steps=3, local_batch=8, local_lr=0.05,
                   client_chunk=4)
    rng = np.random.default_rng(0)
    cb = jax.tree.map(lambda *xs: jnp.stack(xs), *[
        femnist.client_minibatches(rng, c, flc.local_steps, flc.local_batch)
        for c in clients])
    lu = fl.make_strategy(strategy, **kw).local_update
    program = fedavg.chunk_program(_loss, flc, lu)
    for _ in range(2):
        deltas, losses = fedavg.train_selected_clients(
            params, cb, _loss, flc, program=program)
        ref_deltas, ref_losses = _eager_chunks(params, cb, flc, lu)
        assert losses.shape == (6,)
        np.testing.assert_allclose(np.asarray(losses),
                                   np.asarray(ref_losses), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(deltas), jax.tree.leaves(ref_deltas)):
            assert a.shape[0] == 6
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)
        params = jax.tree.map(lambda p, d: p + d[0], params, deltas)


def test_backend_builds_its_chunk_program_once():
    """Rounds after the first trace and lower nothing while training: the
    chunk program takes the global params as an argument and outlives the
    round."""
    from repro import obs
    from repro.obs.context import Obs
    from repro.obs.tracer import Tracer

    cfg = configs.get("femnist_cnn").reduced()
    params, _ = femnist_cnn.init_params(cfg, jax.random.PRNGKey(0))
    pon = PonConfig(n_onus=4, clients_per_onu=5)
    flc = FLConfig(n_onus=4, clients_per_onu=5, n_selected=8, local_steps=2,
                   client_chunk=4, pon=pon)
    clients, eval_set = femnist.generate(
        femnist.FemnistConfig(n_clients=flc.n_clients, seed=11))
    backend = fl.ClientStackedBackend(
        flc, fl.make_strategy("sfl_two_step"), params, clients,
        jax.tree.map(jnp.asarray, eval_set), _loss)
    t = Tracer()
    with obs.use(Obs(tracer=t)):
        fl.RoundLoop(fl.ExperimentConfig(fl=flc, n_rounds=3), backend).run()
    train = [s for s in t.spans if s.name == "backend.train_s"]
    chunks = [s for s in t.spans if s.name == "train.chunk"]
    stages = [s for s in t.spans if s.name in ("jax.trace", "jax.lower")]
    built = [sum(tr.t0_s <= s.t1_s <= tr.t1_s for s in stages)
             for tr in train]
    assert len(train) == 3 and len(chunks) == 6
    assert built[0] >= 1 and built[1:] == [0, 0], built
    assert backend._chunk_program._cache_size() == 1


def _stacked_loop():
    """A small client-stacked RoundLoop: 10 selected in chunks of 4, so a
    round pads its involved clients with weight-0 dummies up to 4k."""
    cfg = configs.get("femnist_cnn").reduced()
    params, _ = femnist_cnn.init_params(cfg, jax.random.PRNGKey(0))
    pon = PonConfig(n_onus=4, clients_per_onu=5)
    flc = FLConfig(n_onus=4, clients_per_onu=5, n_selected=10, local_steps=2,
                   client_chunk=4, pon=pon)
    clients, eval_set = femnist.generate(
        femnist.FemnistConfig(n_clients=flc.n_clients, seed=11))
    backend = fl.ClientStackedBackend(
        flc, fl.make_strategy("sfl_two_step"), params, clients,
        jax.tree.map(jnp.asarray, eval_set), _loss)
    return fl.RoundLoop(fl.ExperimentConfig(fl=flc, n_rounds=3), backend)


def _per_client_puts(self, padded, rng):
    """The eager path the host-side stack replaced: each client's numpy
    draws converted to the device on their own, then ``jnp.stack``."""
    flc = self.fl
    return jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[self.minibatch_fn(rng, self.clients[c], flc.local_steps,
                            flc.local_batch) for c in padded])


def test_round_minibatches_stack_on_host_bit_for_bit(monkeypatch):
    """One host stack and one transfer per leaf hand the chunk program the
    arrays the per-client eager path did: the same client batches, params
    and rng stream after 3 padded rounds, bit for bit."""
    train = fedavg.train_selected_clients

    def run():
        seen = []

        def recording(params, cb, *a, **kw):
            seen.append([np.asarray(x) for x in jax.tree.leaves(cb)])
            return train(params, cb, *a, **kw)

        monkeypatch.setattr(fedavg, "train_selected_clients", recording)
        loop = _stacked_loop()
        hist = loop.run()
        return seen, hist, loop
    seen, hist, loop = run()
    with monkeypatch.context() as m:
        m.setattr(fl.ClientStackedBackend, "_stack_minibatches",
                  _per_client_puts)
        old_seen, old_hist, old_loop = run()
    assert len(seen) == len(old_seen) == 3
    # ragged: the padding dummies ride in the stack too
    assert any(b[0].shape[0] > r["involved"] for b, r in zip(seen, hist))
    for new, old in zip(seen, old_seen):
        for a, b in zip(new, old):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(a, b)
    for a, b in zip(jax.tree.leaves(loop.backend.params),
                    jax.tree.leaves(old_loop.backend.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert loop.rng.bit_generator.state == old_loop.rng.bit_generator.state
    assert hist.column("eval_loss") == old_hist.column("eval_loss")


def test_round_minibatch_span_puts_once_per_leaf():
    """Each round's ``round.minibatch`` span says how many clients it
    stacked, the bytes it sent (the padded clients' device bytes, as
    before) and that it sent them in one transfer per leaf."""
    from repro import obs
    from repro.obs.context import Obs
    from repro.obs.tracer import Tracer

    loop = _stacked_loop()
    backend, flc = loop.backend, loop.backend.fl
    one = femnist.client_minibatches(np.random.default_rng(0),
                                     backend.clients[0], flc.local_steps,
                                     flc.local_batch)
    per_client = sum(jnp.asarray(x).nbytes for x in jax.tree.leaves(one))
    t = Tracer()
    with obs.use(Obs(tracer=t)):
        hist = loop.run()
    spans = [s.args for s in t.spans if s.name == "round.minibatch"]
    assert len(spans) == len(hist) == 3
    for st, rec in zip(spans, hist):
        assert st["n_clients"] % flc.client_chunk == 0
        assert st["n_clients"] >= rec["involved"] > 0
        assert st["h2d_bytes"] == st["n_clients"] * per_client
        assert st["puts"] == len(jax.tree.leaves(one)) == 2


def _transport_loop(n_selected=10, **exp_kw):
    pon = PonConfig(n_onus=4, clients_per_onu=5)
    flc = FLConfig(n_onus=4, clients_per_onu=5, n_selected=n_selected, pon=pon)
    counts = np.random.default_rng(0).integers(
        50, 400, flc.n_clients).astype(np.float32)
    onu = fedavg.onu_of_client(flc)
    exp = fl.ExperimentConfig(fl=flc, **exp_kw)
    backend = fl.TransportBackend(fl.make_strategy(exp.strategy), counts, onu)
    return fl.RoundLoop(exp, backend)


def test_overselect_flows_through_roundloop():
    hist = _transport_loop(overselect=0.5, n_rounds=4).run()
    assert all(r["n_selected"] == 15 for r in hist)


def test_failure_model_flows_through_mask_path():
    hist = _transport_loop(p_transient=1.0, n_rounds=4).run()
    assert all(r["involved"] == 0.0 for r in hist)   # everyone failed
    # failure RNG is separate: the selection/transport stream is unperturbed
    clean = _transport_loop(n_rounds=4).run()
    assert [r["n_selected"] for r in clean] == [r["n_selected"] for r in hist]
    assert any(r["involved"] > 0 for r in clean)


def test_history_callback_sink():
    seen = []
    loop = _transport_loop(n_rounds=3)
    loop.callbacks.append(lambda lp, rec: seen.append(rec["round"]))
    hist = loop.run()
    assert seen == [0, 1, 2]
    assert len(hist) == 3
    assert hist.column("upstream_mbits")[0] > 0


# ---------------------------------------------------------------- satellites

def test_make_strategy_warns_once_per_name_on_dropped_kwargs():
    """Unknown kwargs are still dropped (one shared CLI feeds every
    strategy) but never silently: the first drop per strategy name warns
    with the dropped keys, later drops stay quiet."""
    import warnings

    from repro.fl import strategy as strategy_mod

    strategy_mod._WARNED_DROPPED.discard("fedprox")
    with pytest.warns(UserWarning, match=r"fedprox.*bogus_knob"):
        strat = fl.make_strategy("fedprox", bogus_knob=1, mu=0.5)
    assert strat.mu == 0.5                      # known kwargs still apply
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # a second warn would raise
        fl.make_strategy("fedprox", bogus_knob=2)
    # aliases share the canonical name's once-latch
    strategy_mod._WARNED_DROPPED.discard("sfl_two_step")
    with pytest.warns(UserWarning, match=r"sfl_two_step"):
        fl.make_strategy("sfl_two_step", bogus_knob=1)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        fl.make_strategy("sfl", bogus_knob=2)   # same latch → silent


def test_int8_allreduce_requires_key():
    with pytest.raises(ValueError, match="PRNG key"):
        aggregation.two_step_allreduce({"g": jnp.ones(8)}, compress="int8",
                                       key=None)


def test_yogi_optimizer_converges_on_quadratic():
    from repro.optim import make_optimizer
    opt = make_optimizer("yogi")
    params = {"x": jnp.asarray([5.0, -3.0])}
    state = opt.init(params)
    for _ in range(200):
        grads = {"x": params["x"]}          # d/dx of ||x||²/2
        params, state = opt.update(params, grads, state, 0.1)
    assert float(jnp.max(jnp.abs(params["x"]))) < 0.5
