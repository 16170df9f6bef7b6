"""RoundLoop — the single per-round pipeline driver.

Every federated run in this repo is the same five-stage round:

    selection (N + overselect backups) → crash injection (FailureModel)
    → PON transport (event simulator → involvement mask) → transient mask
    → backend training + strategy aggregation → eval/metrics sink

This used to be re-implemented in four places (core/fedavg callers,
launch/train.py, bench_accuracy, the example) with the strategy hard-coded
as a mode string; :func:`sync_round` owns it once, and both drivers — the
lockstep ``RoundLoop`` here and the event-driven
``repro.runtime.Orchestrator``'s ``sync`` policy — call it, which is what
makes their trajectories bit-for-bit identical.

Failure ordering matters (DESIGN.md §11): the *crash* component of the
FailureModel is injected BEFORE transport, so a crashed client never
reaches the PON edge — it contributes zero upstream Mbits, never occupies
a wavelength grant, and cannot delay its ONU's θ. *Transient* slowness
stays a transport-side phenomenon: the client transmits (and is billed)
but its update is discarded by the aggregation mask. The PON deadline
mask, the crash/transient components, and over-selection backups all meet
in one (selected,)-shaped involvement vector — the paper's own
straggler-drop renormalization handles the rest (DESIGN.md §7).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import numpy as np

from repro.core import selection
from repro.fl.backends import backend_wire_scale
from repro.fl.config import ExperimentConfig
from repro.obs import profile
from repro.obs.context import Obs
from repro.obs.context import get as _obs_get
from repro.pon import round_times


class History:
    """Per-round record sink: a list of flat dicts + column extraction."""

    def __init__(self):
        self.records: List[Dict[str, Any]] = []

    def append(self, rec: Dict[str, Any]) -> None:
        self.records.append(rec)

    def column(self, key: str, default=None) -> List[Any]:
        return [r.get(key, default) for r in self.records]

    def last(self) -> Dict[str, Any]:
        return self.records[-1] if self.records else {}

    def __len__(self) -> int:
        return len(self.records)

    def __iter__(self):
        return iter(self.records)


Callback = Callable[[Any, Dict[str, Any]], None]


def _expand_rt(rt: Dict[str, Any], live: np.ndarray) -> Dict[str, Any]:
    """Re-align per-client transport arrays from the live (non-crashed)
    subset back to the full selection: crashed clients never completed
    (``t_done``/``ready`` = inf, ``involved`` = 0)."""
    out = dict(rt)
    n = len(live)
    inv = np.zeros(n, np.float32)
    inv[live] = np.asarray(rt["involved"], np.float32)
    out["involved"] = inv
    for key in ("t_done", "ready"):
        if key in rt:
            arr = np.full(n, np.inf)
            arr[live] = np.asarray(rt[key], np.float64)
            out[key] = arr
    return out


def _transport_stage(cfg: ExperimentConfig, backend, failures,
                     rng: np.random.Generator, rnd: int, obs=None
                     ) -> Tuple[np.ndarray, np.ndarray, Dict[str, Any]]:
    """selection → crash injection → PON transport → transient mask.

    Returns ``(selected, mask, rt)`` with ``mask``/``rt`` arrays shaped to
    the full selection. Consumes the shared rng in a fixed order (selection
    draw, then transport draws for the *live* clients) — the replay path
    must mirror this exactly.
    """
    fl = cfg.fl
    sel = selection.select_clients(rng, fl.n_clients, fl.n_selected,
                                   cfg.overselect)
    crash_alive = transient_alive = None
    if failures is not None:
        crash_alive, transient_alive = failures.step_components(rnd,
                                                                fl.n_clients)
    live = (crash_alive[sel] if crash_alive is not None
            else np.ones(len(sel), bool))
    pon = fl.pon_config()
    spec = backend.strategy.compression_spec()
    if spec.active:
        # the compressed payload is what actually rides the wire: scale the
        # model size handed to the event simulator so grants/queueing/
        # deadline physics AND the Mbits accounting all see the same bytes
        # (wire_mbits is the single per-model wire size, DESIGN.md §17)
        pon = dataclasses.replace(
            pon, model_mbits=pon.model_mbits * backend_wire_scale(backend))
    rt = round_times(pon, rng, sel[live], backend.onu_ids,
                     backend.sample_counts, backend.strategy.transport,
                     obs=obs)
    if spec.active:
        rt["wire_mbits"] = pon.model_mbits
        rt["compress"] = spec.scheme
    if not live.all():
        rt = _expand_rt(rt, live)
    mask = np.asarray(rt["involved"], np.float32)
    if transient_alive is not None:
        mask = mask * transient_alive[sel].astype(np.float32)
    return sel, mask, rt


# History key → registry counter (window value IS the round's value) for
# the per-segment accounting; maxima are point-in-round gauges
_SEG_COUNTERS = {"upstream_mbits": "pon.upstream_mbits",
                 "metro_mbits": "metro.mbits",
                 "trunk_mbits": "trunk.mbits"}
_SEG_GAUGES = {"pon_mbits_max": "pon.mbits_max",
               "metro_mbits_max": "metro.mbits_max",
               "n_pons": "fl.n_pons"}


def sync_round(cfg: ExperimentConfig, backend, failures,
               rng: np.random.Generator, rnd: int,
               obs: Optional[Obs] = None) -> Dict[str, Any]:
    """One synchronous deadline round; returns the History record.

    The shared round pipeline behind both drivers (``RoundLoop`` and the
    Orchestrator's ``sync`` policy) — any change here changes both, which
    keeps them bit-for-bit interchangeable by construction.

    All bandwidth accounting routes through ``obs.metrics`` (the registry
    is the single source of truth): each segment's Mbits are added to its
    counter and the History record reads the drained window back — one add
    per take, so the record values are bit-for-bit the transport's floats
    while ``counter.total`` accumulates the run totals for free.
    """
    if obs is None:
        obs = _obs_get()
    trc = obs.tracer
    if trc.enabled:
        # retroactive spans inside this round land on a global timeline,
        # offset to the round's start in the lockstep window cadence
        window = cfg.fl.pon_config().sync_threshold_s
        trc.offset_s = rnd * window
        trc.add_span("round", 0.0, window, lane=("fl", "rounds"), cat="round",
                     args={"round": rnd})
    with profile.span("round.transport") as sp:
        sel, mask, rt = _transport_stage(cfg, backend, failures, rng, rnd,
                                         obs)
        involved = float(mask.sum())
        sp.set_metadata(n_selected=len(sel), involved=involved)
    metrics = backend.run_round(rnd, sel, mask, rt, rng)
    reg = obs.metrics
    rec = {"round": rnd, "n_selected": len(sel),
           "sim_engine": rt.get("sim_engine", "event"),
           "involved": involved}
    reg.histogram("fl.involved").observe(rec["involved"])
    # per-segment accounting from the transport (DESIGN.md §12)
    for key, cname in _SEG_COUNTERS.items():
        if key in rt:
            c = reg.counter(cname)
            c.add(float(rt[key]))
            rec[key] = c.take()
    for key, gname in _SEG_GAUGES.items():
        if key in rt:
            g = reg.gauge(gname)
            g.set(float(rt[key]))
            rec[key] = g.value
    if "wire_mbits" in rt:
        # compressed per-model wire size (absent ⇒ uncompressed run; the
        # budget oracle and health monitors key off this, DESIGN.md §17)
        g = reg.gauge("fl.wire_mbits")
        g.set(float(rt["wire_mbits"]))
        rec["wire_mbits"] = g.value
        rec["compress"] = rt["compress"]
    rec.update(metrics)
    if obs.health is not None:
        # online health monitors (repro.obs.audit); incidents surface in
        # the row only when fired, so disabled/healthy runs stay identical
        new = obs.health.observe_round(rec, cfg=cfg, tracer=trc)
        if new:
            rec["incidents"] = len(new)
    return rec


def replay_sync_round(cfg: ExperimentConfig, backend, failures,
                      rng: np.random.Generator, rnd: int) -> None:
    """Consume exactly :func:`sync_round`'s RNG draws without training.

    Fast-forwards a resumed run: replaying the selection/transport draws
    (and, via the backend's optional ``replay_round`` hook, its minibatch
    draws) for the skipped rounds leaves the rng stream — and the stateful
    FailureModel — in the identical state an uninterrupted run would have
    reached, so resumed and uninterrupted trajectories match bit for bit.
    """
    # replayed rounds are invisible to observability: a throwaway disabled
    # Obs keeps fast-forward from double-emitting spans or skewing metrics
    sel, mask, rt = _transport_stage(cfg, backend, failures, rng, rnd,
                                     obs=Obs())
    replay = getattr(backend, "replay_round", None)
    if replay is not None:
        replay(rnd, sel, mask, rt, rng)


def fast_forward(cfg: ExperimentConfig, backend, failures,
                 rng: np.random.Generator, consumed: int, start_round: int
                 ) -> int:
    """Replay rounds ``[consumed, start_round)``; returns the new consumed
    count. The single resume path shared by both drivers (RoundLoop and
    the Orchestrator's sync policy) so their replay semantics cannot
    drift."""
    for rnd in range(consumed, start_round):
        replay_sync_round(cfg, backend, failures, rng, rnd)
    return max(consumed, start_round)


class RoundLoop:
    """Drives rounds of ``cfg`` against a backend; collects a History.

    The per-round RNG stream is a single ``np.random.default_rng(cfg.seed)``
    consumed in a fixed order (selection draw, transport draws, minibatch
    draws) — with ``overselect=0`` and no failure model this reproduces the
    pre-refactor drivers bit for bit. The FailureModel keeps its own RNG so
    enabling it does not perturb the selection/minibatch stream (crash
    injection does change *which* clients reach the transport, so the
    wireless draws shift — that is physics, not bookkeeping).
    """

    def __init__(self, cfg: ExperimentConfig, backend,
                 callbacks: Iterable[Callback] = (),
                 obs: Optional[Obs] = None):
        self.cfg = cfg
        self.backend = backend
        self.callbacks: List[Callback] = list(callbacks)
        self.rng = np.random.default_rng(cfg.seed)
        self.failures = cfg.make_failure_model()
        self.history = History()
        # private registry (sweeps build many loops; run totals must not
        # bleed across them) sharing the ambient tracer (one timeline) and
        # health engine; registered as a child so the session can export
        # one merged metrics artifact for a whole sweep
        self.obs = obs if obs is not None else _obs_get().child()
        # run-level label on every exported metrics record: which upstream
        # engine produced these numbers (repro.obs.diff keys on it to
        # localize engine-choice divergences between run bundles)
        self.obs.metrics.tag("sim_engine",
                             getattr(cfg.fl.pon_config(), "sim_engine",
                                     "event"))
        self.rounds_consumed = 0    # rounds whose RNG draws have been used
        n = cfg.fl.n_clients
        if len(backend.sample_counts) < n or len(backend.onu_ids) < n:
            raise ValueError(
                f"backend covers {len(backend.sample_counts)} clients but "
                f"cfg.fl.n_clients={n}; selection would index out of range — "
                "size the backend's sample_counts/onu_ids to the FL population "
                "(GradientBackend: pass sample_counts/onu_ids or n_clients)")

    @property
    def strategy(self):
        return self.backend.strategy

    @property
    def metrics(self):
        """The loop's private MetricsRegistry (accounting source of truth)."""
        return self.obs.metrics

    @property
    def total_upstream_mbits(self) -> float:
        return self.obs.metrics.counter("pon.upstream_mbits").total

    def run_round(self, rnd: int) -> Dict[str, Any]:
        rec = sync_round(self.cfg, self.backend, self.failures, self.rng, rnd,
                         obs=self.obs)
        self.rounds_consumed += 1
        self.history.append(rec)
        for cb in self.callbacks:
            cb(self, rec)
        return rec

    def run(self, n_rounds: Optional[int] = None, start_round: int = 0
            ) -> History:
        """Run ``n_rounds`` rounds (a COUNT, not an end index) from
        ``start_round``.

        ``run(5, start_round=5)`` therefore trains rounds 5..9 — a resumed
        driver asks for "the remaining rounds", not "rounds up to N" (the
        old conflation silently trained fewer rounds on resume,
        launch/train.py:102). When resuming on a fresh loop, the rounds
        before ``start_round`` are fast-forwarded by replaying their
        selection/transport/minibatch draws so the resumed trajectory is
        bit-for-bit the uninterrupted one (tests/test_runtime.py).
        """
        n = n_rounds if n_rounds is not None else self.cfg.n_rounds
        self.rounds_consumed = fast_forward(self.cfg, self.backend,
                                            self.failures, self.rng,
                                            self.rounds_consumed, start_round)
        for rnd in range(start_round, start_round + n):
            self.run_round(rnd)
        return self.history
