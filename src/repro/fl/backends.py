"""RoundLoop backends — the two training regimes behind one driver.

A backend owns model state and the learning side of a round; the RoundLoop
owns selection, failures, and PON transport. Contract:

    backend.strategy        — the Strategy instance (transport + hooks)
    backend.sample_counts   — (n_clients,) k_ij
    backend.onu_ids         — (n_clients,) int
    backend.run_round(rnd, selected, mask, rt, rng) -> metrics dict

  * ``ClientStackedBackend`` — the faithful paper regime: every involved
    client trains its own model copy for H local steps (chunked vmap), the
    strategy aggregates the stacked deltas and applies the server update.
  * ``GradientBackend``      — the scalable shard_map regime: one global
    model, FL weights folded into per-row ``client_weight`` so grad(loss)
    is the K-normalized aggregate; the collective schedule (two-step vs
    flat) is picked by the sharding rules from ``strategy.transport``.
  * ``TransportBackend``     — no learning at all; for transport-only
    sweeps (DBA policies, wavelengths, background load).

Two optional extensions (implemented by ClientStacked/Transport, used by
``repro.runtime``):

    backend.replay_round(rnd, selected, mask, rt, rng)
        — consume exactly run_round's RNG draws without training, so a
          resumed run can fast-forward the stream (RoundLoop resume).
    backend.client_update(client, rng) -> (delta, weight)
    backend.apply_updates(rnd, clients, deltas, weights) -> metrics
        — the asynchronous seam: one client trains eagerly against the
          CURRENT params at dispatch time (download → H local steps; the
          math is clock-free, only the transport is simulated), and a
          buffer of possibly-stale deltas is later folded into the server
          with staleness-discounted weights (semi_sync / fedbuff policies).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fedavg
from repro.core.compression import CompressionState
from repro.core.fedavg import FLConfig
from repro.data import femnist
from repro.fl.strategy import Strategy
from repro.obs import profile
from repro.obs.context import get as _obs_get


def backend_wire_scale(backend) -> float:
    """Compressed ÷ raw wire size for what this backend puts on the wire.

    The single hook the transport accounting (loop/Orchestrator) uses to
    scale ``model_mbits``: exact when the backend holds the params pytree
    (per-leaf headers and int4 odd-element rounding included), the
    scheme's nominal ratio otherwise (TransportBackend sweeps).
    """
    spec = backend.strategy.compression_spec()
    if not spec.active:
        return 1.0
    return spec.wire_scale(getattr(backend, "params", None))


class ClientStackedBackend:
    """Per-client model copies + H local steps (reproduces Fig. 2 on CPU)."""

    def __init__(self, fl: FLConfig, strategy: Strategy, params,
                 clients, eval_batch, loss_fn: Callable,
                 sample_counts: Optional[np.ndarray] = None,
                 onu_ids: Optional[np.ndarray] = None,
                 minibatch_fn: Callable = femnist.client_minibatches,
                 eval_every: int = 1):
        self.fl = fl
        self.eval_every = max(1, eval_every)
        self.strategy = strategy
        self.params = params
        self.server_state = strategy.init_state(params)
        self.clients = clients
        self.eval_batch = eval_batch
        self.loss_fn = loss_fn
        self.sample_counts = (sample_counts if sample_counts is not None
                              else femnist.sample_counts(clients))
        self.onu_ids = onu_ids if onu_ids is not None else fedavg.onu_of_client(fl)
        self.minibatch_fn = minibatch_fn
        self._last_eval: Dict[str, float] = {}
        self._one_client = None     # lazily-jitted single-client update
        # wire compression (DESIGN.md §17): the backend owns the stateful
        # side — EF residuals + the rounding key stream — so the frozen
        # Strategy stays pure and ``compress=none`` allocates nothing
        spec = strategy.compression_spec()
        self._comp = CompressionState(spec) if spec.active else None

    def _eval(self) -> Dict[str, float]:
        obs = _obs_get()
        (loss, metrics), _ = profile.timed(
            "backend.eval_s", self.loss_fn, self.params, self.eval_batch,
            metrics=obs.metrics, tracer=obs.tracer)
        out = {"eval_loss": float(loss)}
        out.update({k: float(v) for k, v in metrics.items()})
        self._last_eval = out
        return out

    def _idle_metrics(self) -> Dict[str, float]:
        """No update this round — carry the last eval forward."""
        return dict(self._last_eval) if self._last_eval else {"acc": 0.0}

    def _apply_and_eval(self, rnd: int, stacked, weights, mask, onu_ids,
                        client_ids=None) -> Dict[str, float]:
        """Shared tail of both regimes: strategy aggregate → server update
        → uplink stats + eval cadence (any change here changes the sync
        run_round and the async apply_updates together)."""
        agg, stats = self.strategy.aggregate(stacked, weights, mask, onu_ids,
                                             self.fl.total_onus,
                                             comp=self._comp,
                                             client_ids=client_ids)
        self.params, self.server_state = self.strategy.server_update(
            self.params, agg, self.server_state)
        out = {"uplink_models": float(stats["uplink_models"])}
        if (rnd + 1) % self.eval_every == 0:
            out.update(self._eval())
        elif self._last_eval:
            out.update(self._last_eval)
        return out

    def run_round(self, rnd: int, selected: np.ndarray, mask: np.ndarray,
                  rt: Dict[str, Any], rng: np.random.Generator
                  ) -> Dict[str, float]:
        fl = self.fl
        active = selected[mask > 0]
        if len(active) == 0:
            # nothing beat the deadline
            return self._idle_metrics()
        # pad to a chunk multiple with weight-0 dummies: keeps the vmap
        # shapes constant across rounds (one jit compile total)
        pad = (-len(active)) % fl.client_chunk
        padded = np.concatenate([active, np.full(pad, active[0])])
        w = np.concatenate([self.sample_counts[active], np.zeros(pad, np.float32)])
        cb = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[self.minibatch_fn(rng, self.clients[c], fl.local_steps,
                                fl.local_batch) for c in padded])
        obs = _obs_get()
        (deltas, _), _ = profile.timed(
            "backend.train_s", fedavg.train_selected_clients,
            self.params, cb, self.loss_fn, fl,
            metrics=obs.metrics, tracer=obs.tracer,
            local_update=self.strategy.local_update)
        return self._apply_and_eval(
            rnd, deltas, jnp.asarray(w),
            jnp.concatenate([jnp.ones(len(active)), jnp.zeros(pad)]),
            jnp.asarray(self.onu_ids[padded]), client_ids=padded)

    def replay_round(self, rnd: int, selected: np.ndarray, mask: np.ndarray,
                     rt: Dict[str, Any], rng: np.random.Generator) -> None:
        """Consume run_round's minibatch draws without training (resume
        fast-forward — must mirror run_round's rng consumption exactly)."""
        fl = self.fl
        active = selected[mask > 0]
        if len(active) == 0:
            return
        pad = (-len(active)) % fl.client_chunk
        padded = np.concatenate([active, np.full(pad, active[0])])
        for c in padded:
            self.minibatch_fn(rng, self.clients[c], fl.local_steps,
                              fl.local_batch)

    # --- asynchronous seam (repro.runtime semi_sync / fedbuff) -----------

    def client_update(self, client: int, rng: np.random.Generator):
        """One client's eager local update against the CURRENT params.

        Dispatch-time semantics: the client downloads the global model the
        moment the server selects it, trains H local steps, and the
        resulting delta rides the simulated PON — so by arrival time the
        server may have moved on (staleness), which is exactly the regime
        the async policies weight for.
        """
        fl = self.fl
        batches = jax.tree.map(
            jnp.asarray,
            self.minibatch_fn(rng, self.clients[client], fl.local_steps,
                              fl.local_batch))
        if self._one_client is None:
            strategy, loss_fn = self.strategy, self.loss_fn
            self._one_client = jax.jit(
                lambda p, b: strategy.local_update(p, b, loss_fn, fl))
        delta, _ = self._one_client(self.params, batches)
        return delta, float(self.sample_counts[client])

    def apply_updates(self, rnd: int, clients, deltas, weights
                      ) -> Dict[str, float]:
        """Fold a buffer of (possibly stale) client deltas into the server.

        ``weights`` arrive already staleness-discounted by the policy; the
        strategy's weighted-mean aggregate and server_update (plain apply,
        or the fedopt AdamW/Yogi state) do the rest.
        """
        if len(deltas) == 0:
            return self._idle_metrics()
        clients = np.asarray(clients)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *deltas)
        return self._apply_and_eval(
            rnd, stacked, jnp.asarray(np.asarray(weights, np.float32)),
            jnp.ones(len(deltas), jnp.float32),
            jnp.asarray(self.onu_ids[clients]), client_ids=clients)


class GradientBackend:
    """One global model; the round's (k_ij · mask) folds into client_weight.

    Wraps ``launch.specs.make_train_step``: the strategy's transport picks
    the sharding rules (two-step FSDP schedule vs replicated flat
    all-reduce), so the collective form of the paper's aggregation is
    induced by the same Strategy object the client-stacked regime uses.
    """

    def __init__(self, model_cfg, strategy: Strategy, mesh, rules,
                 opt_name: str = "adamw", lr: float = 3e-4,
                 batch: int = 8, seq: int = 128, microbatches: int = 1,
                 seed: int = 0,
                 sample_counts: Optional[np.ndarray] = None,
                 onu_ids: Optional[np.ndarray] = None,
                 n_clients: Optional[int] = None):
        # model/data imports are lazy so `import repro.fl` stays light for
        # the client-stacked path
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.common.sharding import param_sharding_tree
        from repro.launch import specs as S
        from repro.models import transformer
        from repro.optim import make_optimizer

        self.cfg = model_cfg
        self.strategy = strategy
        self.mesh = mesh
        self.batch = batch
        self.seq = seq
        self.seed = seed
        n = n_clients if n_clients is not None else batch
        rng = np.random.default_rng(seed)
        self.sample_counts = (sample_counts if sample_counts is not None
                              else rng.integers(50, 400, n).astype(np.float32))
        self.onu_ids = (onu_ids if onu_ids is not None
                        else np.zeros(len(self.sample_counts), np.int64))
        params, logical = transformer.init_params(model_cfg,
                                                  jax.random.PRNGKey(seed))
        # place every leaf by its logical axes (FSDP rules shard it over
        # the mesh, replicated rules copy it to every device): left
        # unplaced, the step keeps sharded only what its constraints pin
        # (the embedding) and replicates the rest of the model
        shard = param_sharding_tree(mesh, rules, params, logical)
        self.params = jax.device_put(params, shard)
        self.opt = make_optimizer(opt_name)
        replicated = NamedSharding(mesh, P())
        opt_state = self.opt.init(self.params)
        opt_shard = jax.tree.map(
            lambda x: (x.sharding if isinstance(x.sharding, NamedSharding)
                       else replicated), opt_state)
        self.opt_state = jax.device_put(opt_state, opt_shard)
        # the step hands its state back as it was placed: left to choose,
        # the compiler spells the same layouts differently (a size-1 axis
        # dropped), and the second step misses the jit cache and recompiles
        self.train_step = jax.jit(
            S.make_train_step(model_cfg, rules, opt_name, lr, microbatches,
                              seed=seed),
            out_shardings=(shard, opt_shard, replicated))

    def run_round(self, rnd: int, selected: np.ndarray, mask: np.ndarray,
                  rt: Dict[str, Any], rng: np.random.Generator
                  ) -> Dict[str, float]:
        from repro.data import lm as lm_data
        weights = (self.sample_counts[selected] * mask).astype(np.float32)
        if len(weights) > self.batch:
            # over-selection: more clients than batch rows — involved
            # clients (selection order) fill the rows first, so backups
            # replace deadline stragglers instead of starving the round
            order = np.concatenate([np.where(mask > 0)[0],
                                    np.where(mask <= 0)[0]])
            weights = weights[order[:self.batch]]
        elif len(weights) < self.batch:
            weights = np.concatenate(
                [weights, np.zeros(self.batch - len(weights), np.float32)])
        batch_np = next(lm_data.lm_batches(
            self.seed * 1000 + rnd, 1, self.batch, self.seq,
            self.cfg.vocab_size))
        batch = {
            "tokens": jnp.asarray(batch_np["tokens"]),
            "client_weight": jnp.asarray(weights, jnp.float32),
        }
        obs = _obs_get()
        (self.params, self.opt_state, loss), dt = profile.timed(
            "backend.train_step_s", self.train_step,
            self.params, self.opt_state, batch,
            metrics=obs.metrics, tracer=obs.tracer)
        return {"loss": float(loss), "dt": dt}


class TransportBackend:
    """Transport-only: the driver records involvement/upstream, no model.

    Implements the async seam trivially (no deltas) so the runtime's
    semi_sync/fedbuff policies can run pure scheduling sweeps too.
    """

    def __init__(self, strategy: Strategy, sample_counts: np.ndarray,
                 onu_ids: np.ndarray):
        self.strategy = strategy
        self.sample_counts = sample_counts
        self.onu_ids = onu_ids

    def run_round(self, rnd, selected, mask, rt, rng) -> Dict[str, float]:
        return {}

    def client_update(self, client: int, rng):
        return None, float(self.sample_counts[client])

    def apply_updates(self, rnd, clients, deltas, weights) -> Dict[str, float]:
        return {}
