"""The output check must fail: the control, and a run with the timed path
broken underneath, each come out not correct (CPU, the tiny cell).

Each fault is planted in the program while the harness drives a whole run
past its look for a chip: a round that leaves the model unchanged; half
of the involved clients left out of the aggregate, the mean taken over the
rest; one client's update altered where it is produced. The control is
the plain reference in bfloat16 put in the program's place.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from chipbench_tiny import SEED, make_tree, run


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return make_tree(tmp_path_factory.mktemp("tiny"))


def test_sound_run_is_correct(tree):
    out = run(tree)
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["transport_mismatch"]["value"] == 0


def test_control_is_not_correct(tree):
    from chipbench import run as harness
    cell = harness.Cell(tree, "tiny")
    driver = cell.plugin("drivers", cell.config["driver"])
    session = driver.Session(cell, SEED, jax.devices()[:1])
    session.free()
    numbers = session.calibrate(with_program=False)["control"]
    limits = cell.config["limits"]
    assert any(numbers[k] > limits[k] for k in numbers), numbers


def _unchanged(monkeypatch):
    from repro.fl import strategy
    monkeypatch.setattr(strategy.SflTwoStep, "server_update",
                        lambda self, params, agg, state: (params, state))


def _half(monkeypatch):
    from repro.fl import strategy
    aggregate = strategy.SflTwoStep.aggregate

    def half(self, deltas, weights, mask, onu_ids, n_onus, **kw):
        keep = jnp.cumsum(mask) <= jnp.sum(mask) // 2
        return aggregate(self, deltas, weights, mask * keep, onu_ids,
                         n_onus, **kw)
    monkeypatch.setattr(strategy.SflTwoStep, "aggregate", half)


def _answer(monkeypatch):
    from repro.core import fedavg
    train = fedavg.train_selected_clients

    def altered(*a, **kw):
        deltas, losses = train(*a, **kw)
        return jax.tree.map(lambda d: d.at[0].multiply(-1.0), deltas), losses
    monkeypatch.setattr(fedavg, "train_selected_clients", altered)


@pytest.mark.parametrize("fault", [_unchanged, _half, _answer],
                         ids=["unchanged", "half", "answer"])
def test_fault_is_not_correct(tree, monkeypatch, fault):
    fault(monkeypatch)
    out = run(tree)
    assert out["correct"] is False, out["checks"]
