"""Faithful FedAvg / SFL round engine (client-stacked, H local steps).

This is the paper-scale regime: every selected client holds its own model
copy (a large dense weight as the global weight plus the client's step
history, ``core/factored.py``), runs H local SGD steps on its own
(non-IID) data, and the round ends
with the two-step aggregation (``segment_aggregate``) under the PON
simulator's participation mask. Reproduces Fig. 2 end-to-end on CPU.

The scalable gradient regime for the big LM archs lives in
``repro/launch/train.py`` (same aggregation semantics, collective form).

These are the primitives; the public API for running experiments is
``repro.fl`` (Strategy registry + RoundLoop driver, DESIGN.md §10) —
its ``sfl_two_step``/``classical`` strategies are bit-for-bit the
``mode`` branches of :func:`apply_round`, which is kept for direct use.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation, factored
from repro.obs import profile
from repro.pon import PonConfig, round_times


@dataclasses.dataclass(frozen=True)
class FLConfig:
    n_onus: int = 16                # ONUs per PON tree
    clients_per_onu: int = 20
    n_pons: int = 1                 # PON trees (multi-PON hierarchy, §12)
    n_selected: int = 48            # N in the paper (48 / 128 in Fig. 2)
    local_steps: int = 5            # H: minibatch SGD steps per round
    local_batch: int = 10           # LEAF defaults
    local_lr: float = 0.06
    mode: str = "sfl"               # sfl | classical
    sync_threshold_s: float = 25.0  # the paper's deadline
    seed: int = 0
    client_chunk: int = 16          # vmap chunking (host-memory bound)
    # transport: None = the paper's fixed-slice defaults; set to any
    # PonConfig to pick the event simulator's (dba, wavelengths,
    # background traffic, link rates) combination. FLConfig stays the
    # single source of truth for the FL topology and deadline — those
    # fields of an explicit ``pon`` are overridden (see pon_config).
    pon: Optional[PonConfig] = None

    @property
    def n_clients(self) -> int:
        """Total population across the PON forest."""
        return self.n_pons * self.n_onus * self.clients_per_onu

    @property
    def total_onus(self) -> int:
        """ONUs across all PON trees — the segment count for aggregation."""
        return self.n_pons * self.n_onus

    def pon_config(self) -> PonConfig:
        """The PON transport config for this run.

        Transport knobs (dba, wavelengths, traffic, rates) come from
        ``self.pon``; topology (n_pons, n_onus, clients_per_onu) and the
        deadline always come from this FLConfig, so the client→ONU map
        handed to the simulator can never disagree with the simulated tree.
        """
        base = self.pon if self.pon is not None else PonConfig()
        return dataclasses.replace(base,
                                   n_onus=self.n_onus,
                                   clients_per_onu=self.clients_per_onu,
                                   n_pons=self.n_pons,
                                   sync_threshold_s=self.sync_threshold_s)


def onu_of_client(fl: FLConfig) -> np.ndarray:
    """Static topology: client c hangs off GLOBAL ONU c // clients_per_onu
    (PON-major numbering — ids run across the whole forest)."""
    return np.arange(fl.n_clients) // fl.clients_per_onu


def round_transport(fl: FLConfig, rng: np.random.Generator,
                    selected: np.ndarray, sample_counts: np.ndarray,
                    onu_ids: Optional[np.ndarray] = None) -> Dict[str, Any]:
    """One round of the PON transport under ``fl``'s config path.

    Returns the ``round_times`` dict (completion times, involvement mask,
    upstream Mbits, event-simulator stats); the mask is what ``apply_round``
    expects. This is the single seam between the learning engine and the
    network simulator.
    """
    if onu_ids is None:
        onu_ids = onu_of_client(fl)
    return round_times(fl.pon_config(), rng, selected, onu_ids,
                       sample_counts, fl.mode)


def local_sgd(params, batches: Dict[str, jax.Array], loss_fn: Callable,
              lr: float, steps: int):
    """H steps of SGD on one client's minibatches.

    batches: dict of arrays with leading (steps, batch, ...) axes.
    """
    p, moved, loss = _sgd(params, batches, loss_fn, lr, steps)
    leaves, treedef = jax.tree.flatten(p)
    return treedef.unflatten([w + moved[i] if i in moved else w
                              for i, w in enumerate(leaves)]), loss


def _sgd(params, batches, loss_fn: Callable, lr: float, steps: int):
    """H SGD steps → (params, {leaf index: its change}, mean loss).

    A weight that ``factored.plan`` picks is carried as the global weight
    plus the steps' input and output-gradient rows, never as this client's
    own copy; it comes back unchanged in the params and its change, the
    rows' product, in the dict. Every other leaf takes the dense update.
    """
    batches = jax.tree.map(lambda x: x[:steps], batches)
    leaves, treedef = jax.tree.flatten(params)
    taps = factored.plan(loss_fn, leaves, treedef, batches)
    update = lambda w, gw: (w.astype(jnp.float32) - lr * gw).astype(w.dtype)
    n = jax.tree.leaves(batches)[0].shape[0]

    def step(carry, inp):
        p, rows = carry
        batch, t = inp

        def loss_of(p, tap):
            full = [factored.History(leaves[i], *rows[i], *tap[i])
                    if i in taps else w for i, w in enumerate(p)]
            return loss_fn(treedef.unflatten(full), batch)

        def append(h, v):   # step t's rows of a (steps · rows, d) history
            return jax.lax.dynamic_update_slice_in_dim(
                h, v.reshape(-1, h.shape[1]).astype(h.dtype),
                t * (h.shape[0] // n), 0)

        tap = {i: (jnp.zeros(x.shape, x.dtype), jnp.zeros(y.shape, y.dtype))
               for i, (x, y) in taps.items()}
        (l, _), (g, got) = jax.value_and_grad(
            loss_of, argnums=(0, 1), has_aux=True)(p, tap)
        p = jax.tree.map(update, p, g)
        rows = {i: (append(xs, got[i][0]), append(gs, -lr * got[i][1]))
                for i, (xs, gs) in rows.items()}
        return (p, rows), l

    rows0 = {}
    for i, (x, _) in taps.items():
        k = n * math.prod(x.shape[:-1])
        d_in, d_out = leaves[i].shape
        rows0[i] = (jnp.zeros((k, d_in), jnp.float32),
                    jnp.zeros((k, d_out), jnp.float32))
    p0 = [None if i in taps else w for i, w in enumerate(leaves)]
    (p, rows), losses = jax.lax.scan(step, (p0, rows0),
                                     (batches, jnp.arange(n)))
    p = treedef.unflatten([leaves[i] if i in taps else w
                           for i, w in enumerate(p)])
    return p, {i: xs.T @ gs for i, (xs, gs) in rows.items()}, jnp.mean(losses)


def local_sgd_prox(params, batches: Dict[str, jax.Array], loss_fn: Callable,
                   lr: float, steps: int, mu: float, ref_params):
    """H steps of proximal SGD (FedProx): grad += mu · (w − w_global).

    ``ref_params`` is the round's global model; the proximal term pulls each
    local trajectory back toward it, which tames client drift under the
    non-IID splits the PON deadline makes worse.
    """
    def step(p, batch):
        (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(p, batch)
        def upd(w, gw, rw):
            wf = w.astype(jnp.float32)
            gp = gw + mu * (wf - rw.astype(jnp.float32))
            return (wf - lr * gp).astype(w.dtype)
        p = jax.tree.map(upd, p, g, ref_params)
        return p, l
    p, losses = jax.lax.scan(step, params,
                             jax.tree.map(lambda x: x[:steps], batches))
    return p, jnp.mean(losses)


def default_local_update(global_params, batches, loss_fn: Callable,
                         fl: FLConfig):
    """One client's FedAvg local update: H SGD steps → weight delta."""
    p, moved, l = _sgd(global_params, batches, loss_fn, fl.local_lr,
                       fl.local_steps)
    leaves, treedef = jax.tree.flatten(p)
    delta = treedef.unflatten([
        moved[i] if i in moved
        else a.astype(jnp.float32) - b.astype(jnp.float32)
        for i, (a, b) in enumerate(zip(leaves, jax.tree.leaves(global_params)))])
    return delta, l


def chunk_program(loss_fn: Callable, fl: FLConfig,
                  local_update: Optional[Callable] = None) -> Callable:
    """The jitted local-train program for one chunk of clients.

    ``program(global_params, chunk_batches) -> (deltas, losses)``: the
    per-client rule vmapped over the chunk's leading axis, the global
    params broadcast. They are an argument, not a closed-over constant, so
    one program serves every round; ``loss_fn``, ``fl`` and
    ``local_update`` are static. Hold it across calls: a fresh ``jax.jit``
    traces, lowers and compiles (or reads the persistent cache) again.
    ``program.factored_params``, set when it traces, counts the parameters
    per client that its local SGD carries factored (``core/factored.py``).
    """
    if local_update is None:
        local_update = default_local_update

    def local_train_chunk(global_params, batches):
        with factored.counting() as n:
            out = local_update(global_params, batches, loss_fn, fl)
        program.factored_params = n[0]
        return out

    program = jax.jit(jax.vmap(local_train_chunk, in_axes=(None, 0)))
    return program


def train_selected_clients(global_params, client_batches, loss_fn: Callable,
                           fl: FLConfig, local_update: Optional[Callable] = None,
                           program: Optional[Callable] = None):
    """Run local training for all selected clients; returns stacked deltas.

    client_batches: dict of arrays with leading (n_sel, steps, batch, ...)
    axes, trained ``client_chunk`` clients at a time to bound memory.
    ``local_update(global_params, batches, loss_fn, fl) -> (delta, loss)``
    is the per-client rule (a ``repro.fl`` Strategy hook); default FedAvg.
    ``program`` is a :func:`chunk_program` held by the caller, reused by
    every chunk of every call (it supersedes ``local_update``); without
    one, each call builds its own.
    """
    if program is None:
        program = chunk_program(loss_fn, fl, local_update)

    n_sel = jax.tree.leaves(client_batches)[0].shape[0]
    chunk = max(1, min(fl.client_chunk, n_sel))
    deltas, losses = [], []
    for lo in range(0, n_sel, chunk):
        with profile.span("train.chunk", chunk=lo // chunk,
                          rows=min(chunk, n_sel - lo)) as sp:
            cb = jax.tree.map(lambda x: x[lo:lo + chunk], client_batches)
            d, l = program(global_params, cb)
            sp.set_metadata(factored_params=program.factored_params)
        deltas.append(d)
        losses.append(l)
    deltas = jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *deltas)
    return deltas, jnp.concatenate(losses)


def apply_round(global_params, deltas, weights, mask, onu_ids, n_onus: int,
                mode: str, server_lr: float = 1.0):
    """Aggregate client deltas and update the global model.

    Returns (new_params, stats). Both modes compute identical updates —
    the difference is the *transport* (what crosses the PON upstream),
    which the stats account for.
    """
    if mode == "sfl":
        agg, thetas, K = aggregation.segment_aggregate(
            deltas, weights, mask, onu_ids, n_onus)
        onu_active = jnp.zeros((n_onus,), jnp.float32).at[onu_ids].add(mask)
        uplink_models = jnp.sum(onu_active > 0)      # one θ per active ONU
    else:
        agg, K = aggregation.classical_aggregate(deltas, weights, mask)
        uplink_models = jnp.sum(mask)                # every involved client uploads
    new_params = jax.tree.map(
        lambda w, d: (w.astype(jnp.float32) + server_lr * d).astype(w.dtype),
        global_params, agg)
    stats = {"K": K, "uplink_models": uplink_models,
             "involved": jnp.sum(mask)}
    return new_params, stats


def evaluate(params, eval_batch, loss_fn: Callable):
    loss, metrics = loss_fn(params, eval_batch)
    return {"eval_loss": loss, **{f"eval_{k}": v for k, v in metrics.items()}}
