"""Fig. 2c: learning accuracy over rounds — SFL vs classical benchmark.

Both run the SAME FedAvg math; the classical benchmark involves only the
clients that beat the deadline on the serialized slice (O(10)/round) while
SFL involves nearly all selected — the accuracy gap is the paper's point.

Runs through the ``repro.fl`` RoundLoop: any registered strategy is
selectable (``--strategy fedprox|fedopt|…``), and the fault-tolerance knobs
(``--overselect``, ``--p-crash``, ``--p-transient``) flow through the
loop's mask path. Under the defaults the trajectory is bit-for-bit the
pre-refactor hand-rolled loop (pinned by tests/test_fl.py).

Reduced CNN by default (CPU: ~1 s/round); --full uses the exact LEAF CNN.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro import configs, fl
from repro.core.fedavg import FLConfig
from repro.data import femnist
from repro.models import femnist_cnn
from repro.pon import PonConfig


def _loss(params, batch):
    return femnist_cnn.loss_fn(params, batch)


def run(n_rounds: int = 30, n_selected: int = 128, full: bool = False,
        seed: int = 0, modes=("classical", "sfl"), pon: PonConfig = None,
        overselect: float = 0.0, p_crash: float = 0.0,
        p_transient: float = 0.0, strategy_kwargs=None):
    """Run each strategy in ``modes`` through the RoundLoop; returns
    {mode: {"accs": [...], "involved": [...]}}."""
    cfg = configs.get("femnist_cnn") if full else configs.get("femnist_cnn").reduced()
    # FLConfig owns the FL topology — adopt the one requested via pon so
    # --onus/--clients-per-onu/--n-pons on the CLIs are honored, not
    # overridden
    topo = {} if pon is None else {"n_onus": pon.n_onus,
                                   "clients_per_onu": pon.clients_per_onu,
                                   "n_pons": pon.n_pons}
    flc = FLConfig(n_selected=n_selected, local_steps=8, local_lr=0.06,
                   pon=pon, **topo)
    data_cfg = femnist.FemnistConfig(n_clients=flc.n_clients, seed=seed + 7)
    clients, eval_set = femnist.generate(data_cfg)
    eval_batch = jax.tree.map(jnp.asarray, eval_set)
    counts = femnist.sample_counts(clients)

    results = {}
    for mode in modes:
        # per-mode knob filter: the baseline in a comparison run must not
        # absorb another strategy's kwargs (e.g. fedopt's server_lr).
        # Draws stay PAIRED across modes even under --p-crash: the crash
        # component depends only on the failure seed (same per mode), both
        # modes exclude the same clients BEFORE transport, so the
        # selection/wireless streams stay in lockstep (DESIGN.md §11).
        skw = fl.filter_strategy_kwargs(mode, strategy_kwargs)
        strategy = fl.make_strategy(mode, **skw)
        params, _ = femnist_cnn.init_params(cfg, jax.random.PRNGKey(seed))
        backend = fl.ClientStackedBackend(flc, strategy, params, clients,
                                          eval_batch, _loss,
                                          sample_counts=counts)
        exp = fl.ExperimentConfig(fl=flc, strategy=fl.canonical_name(mode),
                                  strategy_kwargs=tuple(sorted(skw.items())),
                                  overselect=overselect, p_crash=p_crash,
                                  p_transient=p_transient,
                                  n_rounds=n_rounds, seed=seed)
        hist = fl.RoundLoop(exp, backend).run()
        results[mode] = {"accs": [a if a is not None else 0.0
                                  for a in hist.column("acc")],
                         "involved": hist.column("involved")}
    return results


def rows_from_results(res) -> list:
    """Per-round rows (machine-readable) from a run() result dict."""
    modes = list(res)
    n = len(res[modes[0]]["accs"])
    rows = []
    for i in range(n):
        row = {"round": i}
        for m in modes:
            row[f"{m}_acc"] = res[m]["accs"][i]
            row[f"{m}_involved"] = res[m]["involved"][i]
        rows.append(row)
    return rows


def main(argv=None):
    """Runs the Fig. 2c comparison (12 rounds unless ``--rounds``) and
    prints the per-round accuracy/involvement table."""
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--n-selected", type=int, default=128)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    fl.add_experiment_cli_args(ap)
    args = ap.parse_args(argv)

    from repro.pon import pon_config_from_args
    t0 = time.time()
    res = run(n_rounds=args.rounds, n_selected=args.n_selected,
              full=args.full, seed=args.seed,
              modes=fl.comparison_modes(fl.canonical_name(args.strategy)),
              pon=pon_config_from_args(args),
              overselect=args.overselect, p_crash=args.p_crash,
              p_transient=args.p_transient,
              strategy_kwargs=fl.strategy_kwargs_from_args(args))
    modes = list(res)
    print("bench_accuracy (Fig 2c)")
    print("round," + ",".join(f"{m}_acc" for m in modes)
          + "," + ",".join(f"{m}_involved" for m in modes))
    n = len(res[modes[0]]["accs"])
    for i in range(0, n, max(1, n // 10)):
        print(f"{i},"
              + ",".join(f"{res[m]['accs'][i]:.3f}" for m in modes) + ","
              + ",".join(f"{res[m]['involved'][i]:.0f}" for m in modes))
    finals = {m: res[m]["accs"][-1] for m in modes}
    ca = finals.get("classical", 0.0)
    other = [m for m in modes if m != "classical"]
    if other and ca:
        sa = finals[other[0]]
        print(f"# final: classical {ca:.3f} vs {other[0]} {sa:.3f} "
              f"(+{100*(sa-ca)/max(ca,1e-9):.1f}% rel; paper: 0.77 vs 0.85, "
              f"+10%)  [{time.time()-t0:.0f}s]")
    from benchmarks import report
    return report.attach_schema(rows_from_results(res), "accuracy")


if __name__ == "__main__":
    main()
