"""Plain reference of the paper's synchronous round (arXiv:2010.15454, §3).

Each round: the server draws N of the population without replacement;
the PON transport decides who is involved (the paper's fixed-slice model:
T = T^d + T^r + T^w per client, T^r proportional to |D| in [3, 20] s,
T^w ~ U[1, 5] s; under SFL a client counts if it reaches its ONU by
deadline - upload - ONU aggregation, and every ONU with such a client
uploads one theta); each involved client runs H steps of minibatch SGD from
the global model; the ONUs sum k * delta of their clients (theta), the CPS
divides the sum of the thetas by K = sum of k; the server adds the result.

The random stream is the one the program documents for its round driver:
one ``numpy`` generator seeded with the run's seed, drawn in the order
selection, wireless times, then one (H, batch) index draw per client row
the round trains, where the rows are the involved clients in selection
order followed by copies of the first one up to a multiple of the client
chunk. Nothing here imports the program.
"""
from __future__ import annotations

import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.gen import femnist as gen


def transport_sfl(rng: np.random.Generator, selected: np.ndarray,
                  onu_of: np.ndarray, counts: np.ndarray, dep: dict) -> dict:
    """The SFL transport outcome of one round: involved (per selected
    client), upstream Mbit and the number of thetas sent."""
    lo, hi = float(counts.min()), float(counts.max())
    t_train = dep["train_s"][0] + (counts.astype(np.float64) - lo) / max(
        hi - lo, 1e-9) * (dep["train_s"][1] - dep["train_s"][0])
    t_wireless = rng.uniform(dep["wireless_s"][0], dep["wireless_s"][1],
                             size=len(selected))
    ready = dep["downlink_s"] + t_train[selected] + t_wireless
    up = dep["model_mbits"] / dep["slice_mbps"]
    in_time = ready <= dep["deadline_s"] - up - dep["onu_agg_s"]
    onus = onu_of[selected]
    done = np.full(len(selected), np.inf)
    active = sorted(set(onus[in_time].tolist()))
    for o in active:
        mine = (onus == o) & in_time
        done[mine] = ready[mine].max() + dep["onu_agg_s"] + up
    return {"involved": (done <= dep["deadline_s"]).astype(np.float32),
            "upstream_mbits": float(len(active)) * dep["model_mbits"],
            "uplink_models": float(len(active))}


def _local_sgd(loss: Callable, lr: float):
    def one_client(params, images, labels):
        def step(p, batch):
            g = jax.grad(loss)(p, *batch)
            return jax.tree.map(lambda w, gw: (w - lr * gw).astype(w.dtype),
                                p, g), None
        p, _ = jax.lax.scan(step, params, (images, labels))
        return jax.tree.map(lambda a, b: a.astype(jnp.float32)
                            - b.astype(jnp.float32), p, params)
    return jax.jit(jax.vmap(one_client, in_axes=(None, 0, 0)))


def run(model, params0, clients, eval_set, traffic: dict, dep: dict,
        seed: int, n_rounds: int, dtype=jnp.float32, precision="highest",
        block: int = 16) -> Dict[str, list]:
    """``n_rounds`` rounds from ``params0``; returns per round the eval
    loss, the transport outcome, the per-leaf norms of the change from
    ``params0`` after each round, and round 0's aggregate under each
    planted fault (``half``: the mean over the first half of the involved
    clients alone; ``answer``: the first client's update negated).

    ``dtype``/``precision`` set the arithmetic: float32 at "highest" is the
    reference, bfloat16 the control. Clients train in blocks of ``block``.
    """
    n_onus, per_onu = dep["n_onus"], dep["clients_per_onu"]
    onu_of = np.arange(n_onus * per_onu) // per_onu
    counts = gen.sample_counts(clients)
    H, B = traffic["local_steps"], traffic["local_batch"]
    chunk = traffic["client_chunk"]
    rng = np.random.default_rng(seed)
    cast = lambda t: jax.tree.map(lambda x: jnp.asarray(x, dtype), t)
    params = cast(params0)
    start = jax.tree.map(lambda x: np.asarray(x, np.float64), params0)
    train = _local_sgd(model.loss, traffic["local_lr"])
    ev = jax.jit(model.loss)
    eval_images = jnp.asarray(eval_set["images"], dtype)
    eval_labels = jnp.asarray(eval_set["labels"])
    out: Dict[str, list] = {"eval_loss": [], "selected": [], "involved": [],
                            "upstream_mbits": [], "uplink_models": [],
                            "change_norms": []}
    with jax.default_matmul_precision(precision):
        for rnd in range(n_rounds):
            sel = rng.choice(len(clients), size=traffic["n_selected"],
                             replace=False)
            tr = transport_sfl(rng, sel, onu_of, counts, dep)
            active = sel[tr["involved"] > 0]
            rows = list(active) + [active[0]] * ((-len(active)) % chunk)
            draws = [gen.client_minibatches(rng, clients[c], H, B)
                     for c in rows][:len(active)]
            k = counts[active].astype(np.float64)
            acc = _TwoStep(n_onus, len(active) // 2)
            for lo in range(0, len(active), block):
                part = draws[lo:lo + block]
                d = train(params,
                          jnp.asarray(np.stack([x["images"] for x in part]),
                                      dtype),
                          jnp.asarray(np.stack([x["labels"] for x in part])))
                acc.add(jax.tree.map(np.asarray, d), k[lo:lo + block],
                        onu_of[active[lo:lo + block]])
            agg = acc.mean()
            if rnd == 0:
                out["faults"] = acc.faults(agg)
            params = jax.tree.map(
                lambda w, a: (w.astype(jnp.float32)
                              + jnp.asarray(a, jnp.float32)).astype(dtype),
                params, agg)
            out["eval_loss"].append(float(ev(params, eval_images,
                                             eval_labels)))
            out["selected"].append(sel)
            out["involved"].append(tr["involved"])
            out["upstream_mbits"].append(tr["upstream_mbits"])
            out["uplink_models"].append(tr["uplink_models"])
            out["change_norms"].append(leaf_norms(
                jax.tree.map(lambda x, s: np.asarray(x, np.float64) - s,
                             params, start)))
    return out


class _TwoStep:
    """theta per ONU (the sum of k * delta of its clients), then the sum
    over ONUs divided by K, in float64 on the host, one block of clients at
    a time. Keeps what round 0's planted faults need: the sums over the
    first ``half`` clients, and the first client's weighted update."""

    def __init__(self, n_onus: int, half: int):
        self.n_onus, self.half = n_onus, half
        self.theta: Dict[str, np.ndarray] = {}
        self.half_sum: Dict[str, np.ndarray] = {}
        self.first: Dict[str, np.ndarray] = {}
        self.K = self.K_half = 0.0
        self.seen = 0

    def add(self, delta: dict, k: np.ndarray, onus: np.ndarray) -> None:
        for i in range(len(k)):
            for name, d in delta.items():
                x = d[i].astype(np.float64) * k[i]
                th = self.theta.setdefault(
                    name, np.zeros((self.n_onus,) + x.shape))
                th[onus[i]] += x
                if self.seen == 0:
                    self.first[name] = x
                if self.seen < self.half:
                    hs = self.half_sum.setdefault(name, np.zeros(x.shape))
                    hs += x
            self.K += float(k[i])
            self.K_half += float(k[i]) if self.seen < self.half else 0.0
            self.seen += 1

    def mean(self) -> Dict[str, np.ndarray]:
        return {n: th.sum(0) / self.K for n, th in self.theta.items()}

    def faults(self, agg: dict) -> dict:
        """Per-leaf norms of round 0's aggregate under each planted fault:
        the mean over the first half of the clients alone; the first
        client's update negated where it is produced; no update at all."""
        return {"half": leaf_norms({n: s / self.K_half
                                    for n, s in self.half_sum.items()}),
                "answer": leaf_norms({n: agg[n] - 2 * self.first[n] / self.K
                                      for n in agg}),
                "unchanged": {n: 0.0 for n in agg}}


def leaf_norms(tree: dict) -> Dict[str, float]:
    return {n: math.sqrt(float(np.sum(np.square(np.asarray(x, np.float64)))))
            for n, x in tree.items()}
