"""The OLMo cell (``olmo1b_sfl_fsdp4``) on the CPU: its files, the
reference against the program, the output check on the tiny OLMo cell,
the counts against XLA, and the readers of its new metrics on made-up
intervals, a tiny traced round and a trace recorded on four v5e chips."""
from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_tiny import ROOT, SEED, load_module
from olmo_tiny import CELL, make_olmo_tree, run
from chipbench import hostspans as hs
from chipbench import progtrace as pt
from chipbench import trace as tr
from chipbench.gen import lm as gen
from chipbench.reference import olmo_1b as ref

gradient = load_module(ROOT / "chipbench/drivers/gradient.py")
counts = load_module(ROOT / "chipbench/counts/olmo_1b.py")
CONFIG = json.loads((ROOT / "chipbench/configs/olmo_1b.json").read_text())
NEW = ("train_step_s.round", "collective_exposed_s.round",
       "token_batch_s.round")
MS = 1e6
# OLMo at the program's smoke widths, multi-head as published
SMALL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "mlp_ratio": 4,
         "vocab_size": 256, "embedding_size": 256, "rope_theta": 10000,
         "layer_norm_eps": 1e-05}


def read(name: str, ctx):
    return load_module(ROOT / "chipbench/metrics" / f"{name}.py").read(ctx)


# ---------------------------------------------------------------------------
# the cell's files
# ---------------------------------------------------------------------------

def test_new_cell_is_found_from_new_files_alone():
    from chipbench import run as harness

    cell = harness.Cell(ROOT, CELL)
    assert cell.chips == 4 and cell.config["driver"] == "gradient"
    assert cell.traffic["rows"] == cell.traffic["n_selected"] == 32
    assert cell.plugin("drivers", "gradient").Session
    assert cell.plugin("counts", cell.config["model"]).round_work
    names = [m["name"] for m in cell.per_layer()]
    assert set(NEW) <= set(names)
    # the accepted metrics with no list of cells apply here too
    assert {"device_idle_share", "mfu", "host_only_s.round"} <= set(names)
    for name in names:
        assert callable(cell.plugin("metrics", name).read), name


def test_published_widths():
    """The file's widths are the registered model's, and its parameter
    count is OLMo-1B's; 32 rows of 2048 tokens are 4.89e14 operations."""
    from repro import configs

    cfg = configs.get("olmo_1b")
    assert gradient.program_config(CONFIG) == cfg
    assert counts.params(CONFIG["widths"]) == cfg.param_count == 1_176_764_416
    assert CONFIG["params"] == cfg.param_count
    assert CONFIG["deployment"]["model_mbits"] == cfg.param_count * 16 / 1e6
    work = counts.round_work(CONFIG, {"seq": 2048}, {"involved": 32})
    assert work["flops"] == pytest.approx(4.89e14, rel=2e-3)


def test_tokens_are_the_programs_client_streams():
    """``gen/lm.py`` is a copy of the program's streams: the same seed
    gives each client the same sequences, and a round's rows the same
    draws."""
    from repro.data import lm

    streams, k = gen.generate(6, SEED, 3, 16, 300)
    assert k.min() >= 50 and k.max() < 400
    for c in range(6):
        np.testing.assert_array_equal(
            streams[c], lm.client_lm_batches(SEED, c, 3, 1, 16,
                                             300)["tokens"][:, 0])
    clients = np.array([4, 0, 4, 2])
    np.testing.assert_array_equal(
        gen.client_rows(np.random.default_rng(1), streams, clients),
        lm.client_rows(SEED, 16, 300, n_rows=3)(np.random.default_rng(1),
                                                clients))


def test_reference_maps_onto_the_program_tree():
    """``to_program`` pins OLMo's fused layout to the program's tree: q, k,
    v are att_proj's thirds, split into heads; ff_proj's first half is the
    value (wu), its second the gate (wg); ``to_reference`` inverts it."""
    from repro.launch import specs
    from repro.launch.mesh import make_test_mesh
    from repro.launch.train import build_rules

    w = SMALL
    p = jax.jit(lambda k: ref.init(w, k, jnp.float32))(jax.random.PRNGKey(0))
    prog = gradient.to_program(p, w)
    mesh = make_test_mesh((1, 1), ("data", "model"))
    abstract, _ = specs.param_shardings(
        gradient.program_config({"model": "olmo_1b", "widths": w}), mesh,
        build_rules(mesh, "sfl"))
    assert jax.tree.structure(prog) == jax.tree.structure(abstract)
    for a, b in zip(jax.tree.leaves(prog), jax.tree.leaves(abstract)):
        assert a.shape == b.shape
    a = prog["unit"]["0_attn"]["attn"]
    m = prog["unit"]["0_attn"]["mlp"]
    att, d, hd, ff = np.asarray(p["att_proj"]), 64, 16, 128
    for i, name in enumerate(("wq", "wk", "wv")):
        np.testing.assert_array_equal(
            np.asarray(a[name])[1, 5, 2, 3], att[1, 5, i * d + 2 * hd + 3])
    np.testing.assert_array_equal(np.asarray(a["wo"])[0, 3, 7, 9],
                                  np.asarray(p["attn_out"])[0, 3 * hd + 7, 9])
    np.testing.assert_array_equal(np.asarray(m["wu"]),
                                  np.asarray(p["ff_proj"])[..., :ff])
    np.testing.assert_array_equal(np.asarray(m["wg"]),
                                  np.asarray(p["ff_proj"])[..., ff:])
    back = gradient.to_reference(prog)
    for n in p:
        np.testing.assert_array_equal(np.asarray(back[n]), np.asarray(p[n]))


def test_program_step_matches_the_reference_in_float32():
    """The program's FSDP step (``GradientBackend`` on one CPU device, the
    model in float32) against the reference at "highest": the weighted
    loss, AdamW's first moment (0.1 x the gradient) and the weights after
    the step. Float32 sums taken in another order differ by about 1e-7 of
    each; the tolerances leave a factor ten to a hundred. A weight whose
    gradient is near 0 may take AdamW's step of lr the other way: the
    change is compared by its norm, to 1e-3."""
    from repro import configs, fl
    from repro.launch.mesh import make_test_mesh
    from repro.launch.train import build_rules

    w, lr, S = SMALL, 4e-4, 32
    model_cfg = dataclasses.replace(
        configs.get_smoke("olmo_1b", n_kv_heads=4, d_ff=128),
        dtype="float32")
    start = jax.jit(lambda k: ref.init(w, k, jnp.float32))(
        jax.random.PRNGKey(5))
    rng = np.random.default_rng(5)
    tokens = rng.integers(0, 256, size=(4, S)).astype(np.int32)
    k = np.array([120.0, 80.0, 200.0, 60.0], np.float32)
    mask = np.array([1.0, 0.0, 1.0, 1.0], np.float32)
    exp = fl.ExperimentConfig(strategy="sfl_two_step", seed=0)
    strategy = exp.make_strategy()
    mesh = make_test_mesh((1, 1), ("data", "model"))
    backend = fl.GradientBackend(
        model_cfg, strategy, mesh, build_rules(mesh, strategy.transport),
        lr=lr, batch=4, seq=S, sample_counts=k, onu_ids=np.zeros(4, int),
        params=gradient.to_program(start, w),
        batch_fn=lambda rng, clients: tokens)
    with jax.set_mesh(mesh), jax.default_matmul_precision("highest"):
        out = backend.run_round(0, np.arange(4), mask, {}, rng)
    trainer = ref.Trainer(w, start, lr, jax.devices()[:1])
    step = trainer.step(tokens, k * mask)
    assert out["loss"] == pytest.approx(step["loss"], rel=1e-6)
    got_m = gradient.to_reference(backend.opt_state["m"])
    for n in trainer.m:
        a, b = np.asarray(got_m[n]), np.asarray(trainer.m[n])
        assert np.linalg.norm(a - b) <= 1e-5 * np.linalg.norm(b), n
    got = ref.layer_norms(gradient._ref_change(backend.params, start))
    want = trainer.change_norms(start)
    for n in want:
        assert got[n] == pytest.approx(want[n], rel=1e-3), n


def test_reference_keeps_parameters_in_the_configured_dtype():
    """With bfloat16 parameters the reference computes each AdamW step in
    float32 and rounds its result, as the program's optimizer does: after
    one step its weights are the float32 reference's rounded to bfloat16
    (the first gradient is the same, the start being bfloat16)."""
    w, S = SMALL, 32
    start = jax.jit(lambda k: ref.init(w, k))(jax.random.PRNGKey(3))
    tokens = np.random.default_rng(3).integers(0, 256, (2, S)).astype(
        np.int32)
    weights = np.array([150.0, 90.0], np.float32)
    kept = ref.Trainer(w, start, 4e-4, jax.devices()[:1],
                       param_dtype=jnp.bfloat16)
    full = ref.Trainer(w, start, 4e-4, jax.devices()[:1])
    a, b = kept.step(tokens, weights), full.step(tokens, weights)
    assert a["loss"] == b["loss"]
    for n in start:
        got = np.asarray(kept.params[n])
        assert got.dtype == jnp.bfloat16, n
        want = np.asarray(full.params[n].astype(jnp.bfloat16))
        # float32 sums of the same gradient in two programs may differ in
        # the last bit, which can flip a rounding: at most 1e-3 of weights
        assert np.mean(got != want) <= 1e-3, n


# ---------------------------------------------------------------------------
# the output check on the tiny OLMo cell
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return make_olmo_tree(tmp_path_factory.mktemp("olmo"))


def test_sound_run_is_correct(tree):
    out = run(tree)
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["transport_mismatch"]["value"] == 0


def test_control_is_not_correct(tree):
    from chipbench import run as harness

    cell = harness.Cell(tree, "tiny_olmo")
    session = cell.plugin("drivers", "gradient").Session(
        cell, SEED, jax.devices()[:1])
    session.free()
    numbers = session.calibrate(with_program=False)["control"]
    limits = cell.config["limits"]
    assert any(numbers[k] > limits[k] for k in limits), numbers


def _weights(monkeypatch, change):
    from repro.fl import backends
    rows = backends.GradientBackend._rows

    def altered(self, selected, mask):
        clients, weights = rows(self, selected, mask)
        return clients, change(weights.copy())
    monkeypatch.setattr(backends.GradientBackend, "_rows", altered)


def _half(monkeypatch):
    def half(w):
        live = np.flatnonzero(w > 0)
        w[live[len(live) // 2:]] = 0.0
        return w
    _weights(monkeypatch, half)


def _answer(monkeypatch):
    def negate(w):
        w[np.flatnonzero(w > 0)[0]] *= -1.0
        return w
    _weights(monkeypatch, negate)


def _unchanged(monkeypatch):
    from repro.launch import specs
    make = specs.make_optimizer

    def frozen(name, *a, **kw):
        opt = make(name, *a, **kw)
        return dataclasses.replace(opt, update=lambda p, g, s, lr: (
            p, opt.update(p, g, s, lr)[1]))
    monkeypatch.setattr(specs, "make_optimizer", frozen)


def _in_round(monkeypatch, at: int, rows=None, tokens=None, after=None):
    """A fault in round ``at`` alone: ``rows(clients, weights)`` alters the
    row clients and weights, ``tokens(made, last)`` the token rows (the
    round's draw still made), ``after(backend, before)`` the state after
    the step."""
    from repro.fl import backends
    cls = backends.GradientBackend
    run_round, make_rows = cls.run_round, cls._rows
    seen = {"rnd": None, "last": None}

    def altered_rows(self, selected, mask):
        clients, weights = make_rows(self, selected, mask)
        if rows is not None and seen["rnd"] == at:
            clients, weights = rows(clients.copy(), weights.copy())
        return clients, weights

    def altered_round(self, rnd, selected, mask, rt, rng):
        seen["rnd"], make = rnd, self.batch_fn

        def draw(r, clients):
            made = make(r, clients)
            out = (tokens(made, seen["last"])
                   if tokens is not None and rnd == at else made)
            seen["last"] = made
            return out
        before = self.params
        self.batch_fn = draw
        try:
            out = run_round(self, rnd, selected, mask, rt, rng)
        finally:
            self.batch_fn = make
        if after is not None and rnd == at:
            self.params = after(self, before)
        return out
    monkeypatch.setattr(cls, "_rows", altered_rows)
    monkeypatch.setattr(cls, "run_round", altered_round)


def _stale_tokens_round1(monkeypatch):
    _in_round(monkeypatch, 1, tokens=lambda made, last: last)


def _other_clients_round1(monkeypatch):
    _in_round(monkeypatch, 1, rows=lambda c, w: (c[::-1], w))


def _weights_reversed_round1(monkeypatch):
    _in_round(monkeypatch, 1, rows=lambda c, w: (c, w[::-1]))


def _ascent(at):
    def plant(monkeypatch):
        def up(backend, before):
            return jax.tree.map(lambda b, a: (2 * b.astype(jnp.float32)
                                              - a.astype(jnp.float32)
                                              ).astype(a.dtype),
                                before, backend.params)
        _in_round(monkeypatch, at, after=up)
    return plant


@pytest.mark.parametrize(
    "fault", [_unchanged, _half, _answer, _stale_tokens_round1,
              _other_clients_round1, _weights_reversed_round1, _ascent(1),
              _ascent(2)],
    ids=["unchanged", "half", "answer", "stale_tokens_round1",
         "other_clients_round1", "weights_reversed_round1",
         "ascent_round1", "ascent_round2"])
def test_fault_is_not_correct(tree, monkeypatch, fault):
    """Planted in the program: a step that leaves the weights unchanged;
    half of the weighted rows left out; one row's weight negated; and in
    one later round alone, round 1 trained on round 0's token rows or on
    the rows of other clients (the row order reversed), round 1's row
    weights reversed, round 1's or round 2's step taken up the gradient.
    (Faults confined to round 2's rows pass: PERF.md, Open questions.)"""
    fault(monkeypatch)
    out = run(tree)
    assert out["correct"] is False, out["checks"]


# ---------------------------------------------------------------------------
# operations against XLA
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d_model", [64, 128])
def test_counts_match_xla(d_model):
    """The reference's forward and backward at small widths, one layer
    (XLA counts a loop's body once), no remat: the operations counted from
    shapes (attention over every pair, as the masked reference computes
    it) are XLA's to within 3% (XLA adds the elementwise work: norms,
    softmax, rotary, SwiGLU's gate)."""
    w = dict(SMALL, n_layers=1, d_model=d_model, embedding_size=512)
    rows, S = 2, 64
    params = jax.eval_shape(lambda k: ref.init(w, k, jnp.float32),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((rows, S), jnp.int32)
    weights = jax.ShapeDtypeStruct((rows,), jnp.float32)
    fn = jax.grad(lambda p, t, wt: ref.weighted_nll(p, t, wt, w,
                                                    remat=False))
    xla = jax.jit(fn).lower(params, tokens, weights).compile(
        ).cost_analysis()["flops"]
    ours = rows * counts.train_flops(w, S, causal=False)
    assert ours <= xla <= 1.03 * ours, (ours, xla)
    # the cell counts the causal half of attention
    assert counts.train_flops(w, S) == ours / rows - 6 * S * S * d_model


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

def _made_up():
    """One 10 ms round on two devices. Host: minibatch 1-2 ms, the step
    2-9. Device 0: a loop 3-9 whose body runs compute 3-4, an all-gather
    4-5 with compute 4.5-5, compute 5-6, a reduce-scatter fusion 6-7.5
    alone, compute 7.5-9. Device 1: an async collective done 3-3.5 alone,
    compute 3.5-8."""
    ag = "%all-gather.1 = bf16[8]{0} all-gather(bf16[2]{0} %p), dimensions={0}"
    rs = ("%fusion.7 = bf16[2]{0} fusion(bf16[8]{0} %g), kind=kOutput, "
          "calls=%all-reduce-scatter.3")
    done = ("%async-collective-done.2 = bf16[8]{0} fusion(%async-collective"
            "-start.2), kind=kCustom, calls=%fused_computation.9")
    use = "%fusion.8 = bf16[8]{0} fusion(bf16[8]{0} %all-gather.1), kind=kLoop"
    loop = "%while.3 = (s32[], bf16[8]{0}) while((s32[], bf16[8]{0}) %t)"
    t = tr.Trace(ops={
        "/device:TPU:0": [(loop, 3 * MS, 9 * MS), (use, 3 * MS, 4 * MS),
                          (ag, 4 * MS, 5 * MS), (use, 4.5 * MS, 5 * MS),
                          (use, 5 * MS, 6 * MS), (rs, 6 * MS, 7.5 * MS),
                          (use, 7.5 * MS, 9 * MS)],
        "/device:TPU:1": [(done, 3 * MS, 3.5 * MS), (use, 3.5 * MS, 8 * MS)]},
        spans={tr.ROUND_SPAN: [(0, 10 * MS)]})
    host = {"backend.train_step_s": [(2 * MS, 9 * MS, {"rows_weighted": 3})],
            "round.minibatch": [(1 * MS, 2 * MS, {"rows": 4})],
            tr.ROUND_SPAN: [(0, 10 * MS, {})]}
    return {"trace": t, "rounds": [{}], "chips": 2, "host_spans": host}


def test_new_readers_on_made_up_intervals():
    ctx = _made_up()
    assert read("train_step_s.round", ctx) == pytest.approx(0.007)
    assert read("token_batch_s.round", ctx) == pytest.approx(0.001)
    # device 0: the all-gather alone 4-4.5, the reduce-scatter 6-7.5
    assert read("collective_exposed_s.round", ctx) == pytest.approx(0.002)


def test_op_kinds():
    ctx = _made_up()
    kinds = [hs.op_kind(n) for n, _, _ in ctx["trace"].ops["/device:TPU:0"]]
    assert kinds == ["container", "compute", "collective", "compute",
                     "compute", "collective", "compute"]


def test_new_readers_find_nothing_without_their_spans_or_ops():
    ctx = _made_up()
    ctx["host_spans"] = {n: [] for n in hs.HOST_SPANS}
    assert read("train_step_s.round", ctx) is None
    assert read("token_batch_s.round", ctx) is None
    ctx["host_spans"] = None         # the trace could not be found again
    assert read("train_step_s.round", ctx) is None
    ctx["trace"].ops = {d: [(n, s, e) for n, s, e in ops
                            if hs.op_kind(n) != "collective"]
                        for d, ops in ctx["trace"].ops.items()}
    assert read("collective_exposed_s.round", ctx) is None


@pytest.fixture(scope="module")
def tiny_traced(tmp_path_factory):
    root = make_olmo_tree(tmp_path_factory.mktemp("olmo_spans"))
    result = run(root, trace=True)
    return result, hs.load(tr.find(root / pt.TRACE_DIR))


def test_gradient_round_spans_and_their_stats(tiny_traced):
    """Each round opens ``round.minibatch`` (rows, tokens, h2d_bytes) and
    then ``backend.train_step_s`` (rows_weighted), and the harness's run
    reads both."""
    result, spans = tiny_traced
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert got["train_step_s.round"] > 0 and got["token_batch_s.round"] > 0
    assert "collective_exposed_s.round" not in got     # the CPU: no op line
    rounds = spans[tr.ROUND_SPAN]
    assert len(rounds) == result["attempted"]
    for r0, r1, _ in rounds:
        inside = lambda evs: [e for e in evs if r0 <= e[0] <= e[1] <= r1]
        (mb,) = inside(spans["round.minibatch"])
        (step,) = inside(spans["backend.train_step_s"])
        assert mb[1] <= step[0]
        assert (mb[2]["rows"], mb[2]["tokens"]) == (4, 4 * 32)
        assert mb[2]["h2d_bytes"] == 4 * 32 * 4 + 4 * 4
        assert 0 <= step[2]["rows_weighted"] <= 4


# a trace recorded on four TPU v5e chips by record_gradient_trace.py: two
# rounds of the gradient regime, OLMo at 2 layers of width 512, FSDP
CHIP_TRACE = ROOT / "tests/chipbench/gradient_trace.xplane.pb"


@pytest.fixture(scope="module")
def chip():
    t = tr.load(CHIP_TRACE)
    return {"trace": t, "rounds": [{}] * len(t.rounds()), "chips": 4,
            "host_spans": hs.load(CHIP_TRACE),
            "program": pt.load(CHIP_TRACE)}


def test_new_readers_read_the_chip_trace(chip):
    """The values on the recorded trace are pinned here."""
    assert chip["trace"].devices == [f"/device:TPU:{i}" for i in range(4)]
    assert len(chip["trace"].rounds()) == 2
    pinned = {"train_step_s.round": 0.005184115,
              "token_batch_s.round": 0.0021128095,
              "collective_exposed_s.round": 0.000610877}
    for name, value in pinned.items():
        assert read(name, chip) == pytest.approx(value, rel=1e-12), name


def test_chip_trace_spans_and_collectives(chip):
    """Each round's ``round.minibatch`` (8 rows of 256 tokens, int32
    tokens and float32 weights to the devices) precedes its
    ``backend.train_step_s`` (7 rows weighted); the step's FSDP
    collectives are there under the names ``op_kind`` reads, and the
    exposed share of them is a small part of the step."""
    spans = chip["host_spans"]
    for mb, step in zip(spans["round.minibatch"],
                        spans["backend.train_step_s"]):
        assert mb[1] <= step[0]
        assert mb[2] == {"rows": 8, "tokens": 2048,
                         "h2d_bytes": 8 * 256 * 4 + 8 * 4}
        assert step[2] == {"rows_weighted": 7}
    ops = chip["trace"].ops["/device:TPU:0"]
    kinds = {}
    for name, _, _ in ops:
        kinds.setdefault(hs.op_kind(name), []).append(name)
    coll = " ".join(kinds["collective"])
    for word in ("%all-gather", "%all-reduce", "%async-collective-start",
                 "%async-collective-done", "calls=%all-reduce-scatter"):
        assert word in coll, word
    assert any("calls=%async_collective_fusion" in n
               for n in kinds["compute"])
    assert kinds["container"] and all(") while(" in n
                                      for n in kinds["container"])
    exposed = read("collective_exposed_s.round", chip)
    assert 0 < exposed < 0.25 * read("train_step_s.round", chip)
