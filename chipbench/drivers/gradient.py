"""Driver: the federated gradient round (``repro.fl.RoundLoop`` over
``repro.fl.GradientBackend``), one FSDP step per round.

The configuration file gives the model (``repro.configs.<model>`` at the
file's widths, the reference in ``reference/<model>.py``) and the
deployment (the PON); the traffic file gives the round: strategy, N,
rows and sequence, optimizer and lr, the ``("data", "model")`` mesh.
Tokens come from ``gen/lm.py`` (each client's own stream) and weights from
the reference's ``init``, both from the seed; the program is handed both,
and builds its sharding rules with ``launch.train.build_rules``.

Set-up drives the program's own round call through its first
``FIRST_ROUNDS`` rounds (the first compiles) and keeps what the output
check compares: the selection and involvement the loop handed the backend,
the upstream Mbit, each round's loss, AdamW's first moment after round 0
(0.1 x the reduced, client-weighted gradient) and the parameters' change
after the last, each as per-layer norms. After the window the reference
replays those rounds in float32 at "highest" precision, on parameters
kept in bfloat16 as the program keeps them.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.drivers.client_stacked import _recording, norm_gap, seed_key
from chipbench.gen import lm as gen
from chipbench.reference import fl_round

FIRST_ROUNDS = 3


def program_config(cfg: dict):
    """The program's ModelConfig: its registered model at the file's
    widths (equal to the registered one at the published widths)."""
    from repro import configs

    w = cfg["widths"]
    return dataclasses.replace(
        configs.get(cfg["model"]), n_layers=w["n_layers"],
        d_model=w["d_model"], n_heads=w["n_heads"], n_kv_heads=w["n_heads"],
        head_dim=w["d_model"] // w["n_heads"],
        d_ff=w["mlp_ratio"] * w["d_model"] // 2,
        vocab_size=w["embedding_size"], rope_theta=float(w["rope_theta"]),
        norm_eps=w["layer_norm_eps"])


def to_program(ref: dict, widths: dict) -> dict:
    """The reference's weights (OLMo's fused layout) in the program's tree:
    ``att_proj``'s thirds are q, k, v, each split into heads; ``ff_proj``'s
    first half is the value (``wu``), its second the gate (``wg``)."""
    L, d, H = widths["n_layers"], widths["d_model"], widths["n_heads"]
    hd, ff = d // H, widths["mlp_ratio"] * d // 2
    att = ref["att_proj"]
    heads = lambda x: x.reshape(L, d, H, hd)
    return {"embed": ref["wte"], "final_norm": {}, "tail": {},
            "unit": {"0_attn": {
                "norm1": {}, "norm2": {},
                "attn": {"wq": heads(att[..., :d]),
                         "wk": heads(att[..., d:2 * d]),
                         "wv": heads(att[..., 2 * d:]),
                         "wo": ref["attn_out"].reshape(L, H, hd, d)},
                "mlp": {"wu": ref["ff_proj"][..., :ff],
                        "wg": ref["ff_proj"][..., ff:],
                        "wd": ref["ff_out"]}}}}


def to_reference(tree: dict) -> dict:
    """A tree in the program's layout (weights, moments) in the
    reference's: the inverse of ``to_program``."""
    u = tree["unit"]["0_attn"]
    a, m = u["attn"], u["mlp"]
    flat = lambda x: x.reshape(x.shape[:2] + (-1,))
    return {"wte": tree["embed"],
            "att_proj": jnp.concatenate([flat(a["wq"]), flat(a["wk"]),
                                         flat(a["wv"])], axis=-1),
            "attn_out": a["wo"].reshape(a["wo"].shape[0], -1,
                                        a["wo"].shape[-1]),
            "ff_proj": jnp.concatenate([m["wu"], m["wg"]], axis=-1),
            "ff_out": m["wd"]}


@jax.jit
def _ref_change(tree: dict, start: dict) -> dict:
    now = to_reference(tree)
    return {n: now[n].astype(jnp.float32) - start[n].astype(jnp.float32)
            for n in now}


class Session:
    def __init__(self, cell, seed: int, devices):
        from repro import fl
        from repro.core.fedavg import FLConfig
        from repro.launch import specs
        from repro.launch.mesh import make_test_mesh
        from repro.launch.train import build_rules
        from repro.pon import PonConfig

        self.cfg, self.seed, self.devices = cell.config, seed, devices
        self.traffic = tf = cell.traffic
        if tf["n_selected"] != tf["rows"] or tf["overselect"]:
            raise ValueError("the reference replays one row per selected "
                             "client, with no over-selection")
        dep, w = self.cfg["deployment"], self.cfg["widths"]
        self.model = importlib.import_module(
            f"chipbench.reference.{self.cfg['model']}")
        n_clients = dep["n_onus"] * dep["clients_per_onu"]
        self.streams, self.counts = gen.generate(
            n_clients, seed, tf["client_rows"], tf["seq"], w["vocab_size"])
        self.key = seed_key(seed)
        self.mesh = make_test_mesh(tuple(tf["mesh"]), ("data", "model"))
        pon = PonConfig(
            slice_mbps=dep["slice_mbps"], model_mbits=dep["model_mbits"],
            downlink_s=dep["downlink_s"], onu_agg_s=dep["onu_agg_s"],
            dba=dep["dba"], n_wavelengths=dep["wavelengths"],
            background_load=dep["background_load"],
            sim_engine=tf["transport_engine"])
        flc = FLConfig(
            n_onus=dep["n_onus"], clients_per_onu=dep["clients_per_onu"],
            n_selected=tf["n_selected"], local_steps=tf["local_steps"],
            sync_threshold_s=dep["deadline_s"], pon=pon)
        skw = (() if tf["compress"] == "none"
               else (("compress", tf["compress"]),))
        exp = fl.ExperimentConfig(fl=flc, strategy=tf["strategy"],
                                  strategy_kwargs=skw,
                                  overselect=tf["overselect"], seed=seed)
        strategy = exp.make_strategy()
        rules = build_rules(self.mesh, strategy.transport)
        model_cfg = program_config(self.cfg)
        abstract, shard = specs.param_shardings(model_cfg, self.mesh, rules)
        made = jax.eval_shape(self._start_program, self.key)
        if (jax.tree.structure(made) != jax.tree.structure(abstract) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in zip(
                    jax.tree.leaves(made), jax.tree.leaves(abstract)))):
            raise ValueError("the reference's weights do not map onto the "
                             "program's tree")
        params = jax.jit(self._start_program, out_shardings=shard)(self.key)
        streams = self.streams
        self.backend = _recording(fl.GradientBackend)(
            model_cfg, strategy, self.mesh, rules,
            opt_name=tf["optimizer"], lr=tf["lr"], batch=tf["rows"],
            seq=tf["seq"], microbatches=tf["microbatches"], seed=seed,
            sample_counts=self.counts,
            onu_ids=np.arange(n_clients) // dep["clients_per_onu"],
            params=params,
            batch_fn=lambda rng, clients: gen.client_rows(rng, streams,
                                                          clients))
        self.backend.seen = []
        self.loop = fl.RoundLoop(exp, self.backend)
        self.first_round = FIRST_ROUNDS
        self.readings: Dict[str, list] = {"loss": [], "upstream_mbits": []}

    def _start_ref(self, key):
        return self.model.init(self.cfg["widths"], key,
                               jnp.dtype(self.cfg["dtype"]))

    def _start_program(self, key):
        return to_program(self._start_ref(key), self.cfg["widths"])

    def round(self, rnd: int) -> dict:
        with jax.set_mesh(self.mesh):
            rec = self.loop.run_round(rnd)
            jax.block_until_ready(self.backend.params)
        return {"round": rnd, "involved": rec["involved"],
                "n_selected": rec["n_selected"]}

    def warm_up(self) -> None:
        """The first rounds, through the window's own call; keeps what the
        output check compares."""
        r = self.readings
        for rnd in range(FIRST_ROUNDS):
            self.round(rnd)
            rec = self.loop.history.records[-1]
            r["loss"].append(float(rec["loss"]))
            r["upstream_mbits"].append(float(rec["upstream_mbits"]))
            if rnd == 0:
                m = jax.jit(to_reference)(self.backend.opt_state["m"])
                r["moment_norms"] = self.model.layer_norms(m)
                del m
        start = jax.jit(self._start_ref)(self.key)
        r["change_norms"] = self.model.layer_norms(
            _ref_change(self.backend.params, start))
        del start
        r["selected"] = [s for s, _ in self.backend.seen]
        r["involved"] = [m for _, m in self.backend.seen]
        self.backend.seen = None

    def free(self) -> None:
        del self.loop, self.backend

    def reference(self, dtype=jnp.float32, precision: str = "highest",
                  faults: bool = False) -> Dict[str, list]:
        """The first rounds replayed from the seed: selection and
        closed-form transport, each row one selected client's sequence,
        then the reference model's step, computed in ``dtype`` on
        parameters kept in the configuration's dtype."""
        tf, dep = self.traffic, self.cfg["deployment"]
        n_clients = dep["n_onus"] * dep["clients_per_onu"]
        onu_of = np.arange(n_clients) // dep["clients_per_onu"]
        rng = np.random.default_rng(self.seed)
        start = jax.jit(self._start_ref)(self.key)
        trainer = self.model.Trainer(self.cfg["widths"], start, tf["lr"],
                                     self.devices, dtype, precision,
                                     jnp.dtype(self.cfg["dtype"]))
        del start
        out: Dict[str, list] = {"loss": [], "selected": [], "involved": [],
                                "upstream_mbits": []}
        for rnd in range(FIRST_ROUNDS):
            sel = rng.choice(n_clients, size=tf["n_selected"], replace=False)
            tr = fl_round.transport_sfl(rng, sel, onu_of, self.counts, dep)
            weights = (self.counts[sel] * tr["involved"]).astype(np.float32)
            tokens = gen.client_rows(rng, self.streams, sel)
            step = trainer.step(tokens, weights, faults=faults and rnd == 0)
            if rnd == 0:
                out["moment_norms"] = step["moment_norms"]
                if faults:
                    out["faults"] = step["faults"]
            out["loss"].append(step["loss"])
            out["selected"].append(sel)
            out["involved"].append(tr["involved"])
            out["upstream_mbits"].append(tr["upstream_mbits"])
        out["change_norms"] = trainer.change_norms(
            jax.jit(self._start_ref)(self.key))
        return out

    def check(self) -> Dict[str, dict]:
        """The numbers the configuration sets a limit for."""
        numbers = compare(self.readings, self.reference())
        limits = self.cfg["limits"]
        return {k: {"value": v, "limit": limits[k]}
                for k, v in numbers.items() if k in limits}

    def calibrate(self, with_program: bool = True) -> dict:
        """The numbers the limits are set from: the program's (after
        ``warm_up``), the control's, and each planted fault's."""
        ref = self.reference(faults=True)
        # the configuration states bfloat16 parameters with float32
        # statistics, softmax, loss and moments; the next precision below
        # is bfloat16 throughout
        control = self.reference(jnp.bfloat16, "default")
        out = {"control": compare(control, ref),
               "faults": {name: norm_gap(norms, ref["moment_norms"])
                          for name, norms in ref["faults"].items()},
               "rounds": {"reference": {"loss": ref["loss"]},
                          "control": rounds(control, ref)}}
        if with_program:
            out["program"] = compare(self.readings, ref)
            out["rounds"]["program"] = rounds(self.readings, ref)
        return out


def rounds(got: Dict[str, list], ref: Dict[str, list]) -> dict:
    """Each round's loss and its gap to the reference's, and the three
    layers that set ``grad_gap`` (the worst first)."""
    a, b = got["moment_norms"], ref["moment_norms"]
    med = float(np.median(list(b.values())))
    gap = {k: abs(a[k] - b[k]) / max(b[k], med) for k in b}
    return {"loss": got["loss"],
            "loss_gap": [abs(x - y) / abs(y)
                         for x, y in zip(got["loss"], ref["loss"])],
            "grad_worst": dict(sorted(gap.items(), key=lambda kv: -kv[1])[:3])}


def compare(prog: Dict[str, list], ref: Dict[str, list]) -> Dict[str, float]:
    """``loss_gap`` reads every round but the last: at lr 4e-4 with no
    warm-up the second AdamW step sends the loss up (10.4 to 12-16 on four
    v5e), and the last round's loss, read there, moves with the rounding
    of every step before it; the last step is checked by ``change_gap``."""
    n = len(ref["loss"])
    mismatch = 0
    for r in range(n):
        for key in ("selected", "involved"):
            mismatch += int(np.sum(np.asarray(prog[key][r])
                                   != np.asarray(ref[key][r])))
        mismatch += int(prog["upstream_mbits"][r] != ref["upstream_mbits"][r])
    return {
        "transport_mismatch": float(mismatch),
        "loss_gap": max(abs(prog["loss"][r] - ref["loss"][r])
                        / abs(ref["loss"][r]) for r in range(n - 1)),
        "grad_gap": norm_gap(prog["moment_norms"], ref["moment_norms"]),
        "change_gap": norm_gap(prog["change_norms"], ref["change_norms"]),
    }
