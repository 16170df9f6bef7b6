"""Driver: the paper's client-stacked round (``repro.fl.RoundLoop`` over
``repro.fl.ClientStackedBackend``).

The configuration file gives the model (its program loss in
``repro.models.<model>``, its widths, the reference in
``reference/<model>.py``) and the deployment (the PON); the traffic file
gives the round: strategy, N, H, local batch and lr, client chunk, eval
cadence. Data come from ``gen/femnist.py`` and weights from the
reference's ``init``, both from the seed; the program is handed both.

Set-up drives the program's own round call through its first
``FIRST_ROUNDS`` rounds (the first compiles); the output check compares
those rounds with the plain reference, run after the window in float32 at
"highest" precision.
"""
from __future__ import annotations

import importlib
import statistics
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.gen import femnist as gen
from chipbench.reference import fl_round

FIRST_ROUNDS = 3
# leaves whose reference change is under this share of the median leaf's
# are left out of the norm comparisons (moved by rounding alone)
TINY_LEAF = 1e-3


def seed_key(seed: int):
    """A JAX key from any whole number (the driver's seeds pass 32 bits)."""
    words = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                              int(words[1]))


def _recording(backend_cls):
    """The program's backend, keeping the selection and the involvement
    mask that the round driver hands it while ``seen`` is a list."""
    class Recording(backend_cls):
        seen: Optional[list] = None

        def run_round(self, rnd, selected, mask, rt, rng):
            if self.seen is not None:
                self.seen.append((np.array(selected), np.array(mask)))
            return super().run_round(rnd, selected, mask, rt, rng)
    return Recording


class Session:
    def __init__(self, cell, seed: int, devices):
        from repro import fl
        from repro.core.fedavg import FLConfig
        from repro.pon import PonConfig

        self.cfg, self.seed = cell.config, seed
        # the round as the reference reads it: the traffic and the lr
        self.traffic = tf = {**cell.traffic, "local_lr": self.cfg["local_lr"]}
        dep = self.cfg["deployment"]
        self.model = importlib.import_module(
            f"chipbench.reference.{self.cfg['model']}")
        program_model = importlib.import_module(
            f"repro.models.{self.cfg['model']}")
        n_clients = dep["n_onus"] * dep["clients_per_onu"]
        self.clients, eval_set = gen.generate(n_clients, seed)
        self.eval_set = eval_set
        self.params0 = jax.jit(lambda k: self.model.init(
            self.cfg["widths"], k))(seed_key(seed))
        jax.block_until_ready(self.params0)
        self.start = jax.tree.map(lambda x: np.asarray(x, np.float64),
                                  self.params0)
        pon = PonConfig(
            slice_mbps=dep["slice_mbps"], model_mbits=dep["model_mbits"],
            downlink_s=dep["downlink_s"], onu_agg_s=dep["onu_agg_s"],
            dba=dep["dba"], n_wavelengths=dep["wavelengths"],
            background_load=dep["background_load"],
            sim_engine=tf["transport_engine"])
        flc = FLConfig(
            n_onus=dep["n_onus"], clients_per_onu=dep["clients_per_onu"],
            n_selected=tf["n_selected"], local_steps=tf["local_steps"],
            local_batch=tf["local_batch"], local_lr=tf["local_lr"],
            sync_threshold_s=dep["deadline_s"],
            client_chunk=tf["client_chunk"], pon=pon)
        skw = (() if tf["compress"] == "none"
               else (("compress", tf["compress"]),))
        exp = fl.ExperimentConfig(fl=flc, strategy=tf["strategy"],
                                  strategy_kwargs=skw,
                                  overselect=tf["overselect"], seed=seed)
        self.backend = _recording(fl.ClientStackedBackend)(
            flc, exp.make_strategy(), self.params0, self.clients,
            jax.tree.map(jnp.asarray, eval_set), program_model.loss_fn,
            sample_counts=gen.sample_counts(self.clients),
            minibatch_fn=gen.client_minibatches,
            eval_every=tf["eval_every"])
        self.backend.seen = []
        self.loop = fl.RoundLoop(exp, self.backend)
        self.first_round = FIRST_ROUNDS
        self.readings: Dict[str, list] = {
            "eval_loss": [], "upstream_mbits": [],
            "uplink_models": [], "change_norms": []}

    def round(self, rnd: int) -> dict:
        rec = self.loop.run_round(rnd)
        jax.block_until_ready(self.backend.params)
        return {"round": rnd, "involved": rec["involved"],
                "n_selected": rec["n_selected"]}

    def warm_up(self) -> None:
        """The first rounds, through the window's own call; keeps what the
        output check compares."""
        for rnd in range(FIRST_ROUNDS):
            self.round(rnd)
            rec = self.loop.history.records[-1]
            r = self.readings
            r["eval_loss"].append(float(rec["eval_loss"]))
            r["upstream_mbits"].append(float(rec["upstream_mbits"]))
            r["uplink_models"].append(float(rec["uplink_models"]))
            r["change_norms"].append(fl_round.leaf_norms(jax.tree.map(
                lambda x, s: np.asarray(x, np.float64) - s,
                self.backend.params, self.start)))
        self.readings["selected"] = [s for s, _ in self.backend.seen]
        self.readings["involved"] = [m for _, m in self.backend.seen]
        self.backend.seen = None

    def free(self) -> None:
        del self.loop, self.backend
        self.params0 = None

    def reference(self, dtype=jnp.float32, precision: str = "highest"):
        return fl_round.run(self.model, self.start, self.clients,
                            self.eval_set, self.traffic,
                            self.cfg["deployment"], self.seed, FIRST_ROUNDS,
                            dtype=dtype, precision=precision)

    def check(self) -> Dict[str, dict]:
        numbers = compare(self.readings, self.reference())
        limits = self.cfg["limits"]
        return {k: {"value": v, "limit": limits[k]}
                for k, v in numbers.items()}

    def calibrate(self, with_program: bool = True) -> dict:
        """The numbers the limits are set from: the program's (after
        ``warm_up``), the control's, and each planted fault's."""
        ref = self.reference()
        # the configuration states float32 at the default matmul precision;
        # the next precision below is bfloat16 throughout
        control = self.reference(jnp.bfloat16, "default")
        out = {"control": compare(control, ref),
               "faults": {name: norm_gap(norms, ref["change_norms"][0])
                          for name, norms in ref["faults"].items()}}
        if with_program:
            out["program"] = compare(self.readings, ref)
        return out


def norm_gap(got: Dict[str, float], ref: Dict[str, float]) -> float:
    """The worst leaf's gap between two norms, against the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    med = statistics.median(ref.values())
    return max(abs(got[k] - ref[k]) / max(ref[k], med)
               for k in ref if ref[k] >= TINY_LEAF * med)


def compare(prog: Dict[str, list], ref: Dict[str, list]) -> Dict[str, float]:
    n = len(ref["eval_loss"])
    mismatch = 0
    for r in range(n):
        for key in ("selected", "involved"):
            mismatch += int(np.sum(np.asarray(prog[key][r])
                                   != np.asarray(ref[key][r])))
        mismatch += int(prog["upstream_mbits"][r] != ref["upstream_mbits"][r])
        mismatch += int(prog["uplink_models"][r] != ref["uplink_models"][r])
    return {
        "transport_mismatch": float(mismatch),
        "loss_gap": max(abs(prog["eval_loss"][r] - ref["eval_loss"][r])
                        / abs(ref["eval_loss"][r]) for r in range(n)),
        "grad_gap": norm_gap(prog["change_norms"][0],
                             ref["change_norms"][0]),
        "change_gap": norm_gap(prog["change_norms"][n - 1],
                               ref["change_norms"][n - 1]),
    }
