"""End-to-end distributed training driver (the scalable gradient regime).

Runs real steps on whatever devices exist (CPU here, pods in production)
through the ``repro.fl`` RoundLoop — one step per federated round:
  * model from ``--arch`` (full or ``--smoke`` reduced config)
  * SFL semantics: per-round client selection (with ``--overselect``
    backups), PON deadline mask × synthetic ``FailureModel``
    (``--p-crash``/``--p-transient``), sample weights — folded into
    ``client_weight`` per batch row; gradients aggregate under the
    sharding-induced two-step schedule (FSDP: reduce-scatter in-pod +
    all-reduce cross-pod). ``--strategy classical`` flips the benchmark
    topology (replicated params, flat all-reduce).
  * checkpoint/restart (--ckpt dir; resumes from the latest step)
  * synthetic federated LM data (per-client Markov streams)

Example (CPU):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt /tmp/ck
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro import configs, fl, obs
from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.common.compile_cache import enable_compile_cache
from repro.common.sharding import ShardingRules
from repro.launch.mesh import make_test_mesh
from repro.obs import obs_logging


def build_rules(mesh, transport: str) -> ShardingRules:
    """Sharding rules induced by the strategy's transport: ``classical``
    replicates params (flat all-reduce benchmark); ``sfl`` and ``hier``
    both take the FSDP schedule — the in-network aggregation tiers map to
    the reduce-scatter/all-reduce stages of the same collective (the metro
    tier adds segments on the wire, not stages in the schedule)."""
    axes = tuple(mesh.axis_names)
    batch = tuple(a for a in ("pod", "data") if a in axes) or None
    rules = ShardingRules(batch=batch, fsdp="data" if "data" in axes else None,
                          tensor="model" if "model" in axes else None,
                          expert="model" if "model" in axes else None)
    return rules.replicated() if transport == "classical" else rules


def make_backend(cfg, exp: fl.ExperimentConfig, *, opt: str = "adamw",
                 lr: float = 3e-4, batch: int = 8, seq: int = 128,
                 micro: int = 1, seed: int = 0) -> fl.GradientBackend:
    """The gradient-regime backend over every device: a ``("data",
    "model")`` mesh of all devices on ``data``, with the sharding rules the
    strategy's transport induces (FSDP for sfl/hier, replicated for
    classical). Drive it under ``jax.set_mesh(backend.mesh)``."""
    strategy = exp.make_strategy()
    mesh = make_test_mesh((len(jax.devices()), 1), ("data", "model"))
    rules = build_rules(mesh, strategy.transport)
    rng = np.random.default_rng(seed)
    flc = exp.fl
    onu_ids = np.arange(flc.n_clients) // flc.clients_per_onu
    sample_counts = rng.integers(50, 400, flc.n_clients).astype(np.float32)
    return fl.GradientBackend(
        cfg, strategy, mesh, rules, opt_name=opt, lr=lr, batch=batch,
        seq=seq, microbatches=micro, seed=seed, sample_counts=sample_counts,
        onu_ids=onu_ids)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--opt", default="adamw")
    ap.add_argument("--micro", type=int, default=1)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--driver", choices=("loop", "runtime"), default="loop",
                    help="round driver: the lockstep RoundLoop or the "
                         "event-driven runtime Orchestrator (--policy picks "
                         "the aggregation policy; GradientBackend is "
                         "sync-only)")
    # strategy / PON transport / fault-tolerance / observability knobs —
    # the shared repro.fl flag set (also on bench_accuracy and the examples)
    fl.add_experiment_cli_args(ap)
    obs_logging.add_logging_cli_args(ap)
    args = ap.parse_args()
    enable_compile_cache()

    logger = obs_logging.logger_from_args(args)
    sess = obs.session_from_args(
        args, driver="orchestrator" if args.driver == "runtime" else "round_loop")

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    exp = fl.experiment_config_from_args(args, n_rounds=args.steps)
    # one selected client per batch row: client_weight aligns with the batch
    exp = exp.with_fl(n_selected=args.batch)
    backend = make_backend(cfg, exp, opt=args.opt, lr=args.lr,
                           batch=args.batch, seq=args.seq, micro=args.micro,
                           seed=args.seed)

    with jax.set_mesh(backend.mesh):
        step0 = 0
        if args.ckpt:
            last = latest_step(args.ckpt)
            if last is not None:
                (backend.params, backend.opt_state), extra, step0 = \
                    restore_checkpoint(args.ckpt, last,
                                       (backend.params, backend.opt_state))
                logger.info("[restore] resumed from step %d", step0)

        def on_round(loop, rec):
            step = rec["round"]
            if step % args.log_every == 0 or step == args.steps - 1:
                obs_logging.log_round(logger, rec)
            if args.ckpt and (step + 1) % args.ckpt_every == 0:
                save_checkpoint(args.ckpt, step + 1,
                                (backend.params, backend.opt_state))

        # n_rounds is a COUNT: a resumed run asks for the REMAINING rounds,
        # and the driver replays the skipped rounds' RNG draws so the
        # resumed trajectory is bit-for-bit the uninterrupted one
        remaining = max(0, args.steps - step0)
        if args.driver == "runtime":
            from repro import runtime
            orch = runtime.Orchestrator(exp, backend, callbacks=[on_round],
                                        obs=sess.obs)
            history = orch.run(remaining, start_round=step0)
        else:
            loop = fl.RoundLoop(exp, backend, callbacks=[on_round],
                                obs=sess.obs)
            history = loop.run(remaining, start_round=step0)
        if args.ckpt:
            save_checkpoint(args.ckpt, args.steps,
                            (backend.params, backend.opt_state))
            logger.info("[ckpt] saved final at step %d", args.steps)
        # cfg/history feed the --report-out bundle (repro.obs.audit)
        sess.finish(cfg=exp, history=history)


if __name__ == "__main__":
    main()
