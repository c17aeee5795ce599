"""Each driver rehearsed on the CPU at smoke widths through the whole of a
run except the look for a chip; the same runs with the timed path broken,
which must come out not correct; and the control, which must too."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from bench import calibrate, compare, run

TRAIN, SERVE = "smollm360m.train.ring_d2", "smollm360m.serve.chat"
SEED = 2 ** 33 + 11


def _execute(cell, seconds=0.5):
    return run.execute(cell, SEED, seconds, False, jax.devices()[:1])


def _below_control(res, control):
    """The limits are set from chip runs at the cell's size; at smoke size
    the sound run's readings must still lie below the control's."""
    got = {k: v["value"] for k, v in res["checks"].items()}
    assert all(got[k] < control[k] for k in got), (got, control)


def _train_rehearsal(cell):
    res = _execute(cell)
    _below_control(res, calibrate.train_control(cell, SEED))
    assert set(res["metrics"]) == {"train_tok_s", "setup_s"}
    assert res["failed"] == 0 and res["attempted"] > 3
    assert res["notes"]["compiles_in_window"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"loss_gap", "grad_gap", "change_gap"}
    return res


def _serve_rehearsal(cell):
    res = _execute(cell)
    served = calibrate.serve_sample(cell, SEED, 0.5, jax.devices()[:1])
    _below_control(res, calibrate.serve_control(cell, SEED, served))
    assert set(res["metrics"]) == {"serve_ttft_p95_ms", "serve_tpot_p95_ms",
                                   "serve_tok_s", "setup_s"}
    assert res["failed"] == 0
    assert res["attempted"] == round(cell.traffic["rate"] * 0.5)
    assert res["notes"]["compiles_in_window"] == 0
    assert res["notes"]["compared_tokens"] > 0
    return res


def test_train_cell_rehearsal(smoke_cell):
    res = _train_rehearsal(smoke_cell(TRAIN))
    assert res["correct"], res["checks"]


def test_serve_cell_rehearsal(smoke_cell):
    res = _serve_rehearsal(smoke_cell(SERVE))
    assert res["correct"], res["checks"]


REHEARSAL = {TRAIN: _train_rehearsal, SERVE: _serve_rehearsal}


@pytest.mark.parametrize("name", [TRAIN, SERVE])
def test_olmo_rehearsal(smoke_cell, name):
    res = REHEARSAL[name](smoke_cell(name, "olmo"))
    assert res["correct"], res["checks"]


# held to the control alone: the limits are the cell's, set for its tied
# head, whose logits are smaller than a head of its own gives (the
# embedding's std 0.02 against the head's d ** -0.5)
@pytest.mark.parametrize("name", [TRAIN, SERVE])
def test_untied_head_rehearsal(smoke_cell, name):
    REHEARSAL[name](smoke_cell(name, "llama_untied"))


# ---------------------------------------------------------------------------
# The timed path broken underneath: `correct` must come out false
# ---------------------------------------------------------------------------

def _broken_train(monkeypatch, cell, fault):
    drv = cell.driver
    build = drv.build_engine

    def broken(cell_, seed):
        engine, state = build(cell_, seed)
        real = engine.step_fn
        if fault == "state_unchanged":
            # the step's metrics, the state handed back as it came
            engine.step_fn = lambda s, b: (
                s, real(jax.tree.map(jnp.copy, s), b)[1])
        elif fault == "half_batch":
            engine.step_fn = lambda s, b: real(
                s, {k: v[: v.shape[0] // 2] for k, v in b.items()})
        elif fault == "ring_unwritten":
            # the ring keeps the initial weights: every read is of step 0
            def unwritten(s, b):
                hist = jax.tree.map(jnp.copy, s.hist)
                new, metrics = real(s, b)
                return dataclasses.replace(new, hist=hist), metrics
            engine.step_fn = unwritten
        return engine, state
    monkeypatch.setattr(drv, "build_engine", broken)
    if fault == "ring_gather_row0":
        from repro.kernels import ops
        gather = ops.ring_gather
        monkeypatch.setattr(ops, "ring_gather",
                            lambda hist, idx: gather(hist, 0 * idx))


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "ring_unwritten", "ring_gather_row0"])
def test_train_fault_is_not_correct(smoke_cell, monkeypatch, fault):
    cell = smoke_cell(TRAIN)
    _broken_train(monkeypatch, cell, fault)
    res = _execute(cell)
    assert not res["correct"], res["checks"]


def _broken_serve(monkeypatch, cell, fault):
    drv = cell.driver
    build = drv.build_engine
    vocab = cell.config["vocab_size"]

    def broken(cell_, seed):
        engine = build(cell_, seed)
        real = engine._decode
        if fault == "token_altered":
            def decode(*a):
                toks, cache = real(*a)
                return (toks + 1) % vocab, cache
        else:                                 # the step keeps its state
            def decode(p, c, *a):
                return real(p, c, *a)[0], c
        engine._decode = decode
        return engine
    monkeypatch.setattr(drv, "build_engine", broken)


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_serve_fault_is_not_correct(smoke_cell, monkeypatch, fault):
    cell = smoke_cell(SERVE)
    _broken_serve(monkeypatch, cell, fault)
    res = _execute(cell)
    assert not res["correct"], res["checks"]


def test_olmo_faults_are_not_correct(smoke_cell, monkeypatch):
    train = smoke_cell(TRAIN, "olmo")
    _broken_train(monkeypatch, train, "half_batch")
    serve = smoke_cell(SERVE, "olmo")
    _broken_serve(monkeypatch, serve, "token_altered")
    for cell in (train, serve):
        res = _execute(cell)
        assert not res["correct"], res["checks"]


# ---------------------------------------------------------------------------
# The control: the reference in float8 in the program's place
# ---------------------------------------------------------------------------

def test_train_control_is_not_correct(smoke_cell):
    cell = smoke_cell(TRAIN)
    readings = calibrate.train_control(cell, SEED)
    assert not compare.verdict(compare.checks(readings, cell.limits)), \
        readings


def test_serve_control_is_not_correct(smoke_cell):
    cell = smoke_cell(SERVE)
    served = calibrate.serve_sample(cell, SEED, 0.5, jax.devices()[:1])
    readings = calibrate.serve_control(cell, SEED, served)
    assert not compare.verdict(compare.checks(readings, cell.limits)), \
        readings


def test_run_refuses_without_a_chip(capsys, monkeypatch):
    import os
    for var in ("REPRO_KERNEL_IMPL", "JAX_COMPILATION_CACHE_DIR"):
        monkeypatch.setenv(var, os.environ.get(var, ""))    # restored after
    was = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        rc = run.main(["--workload", TRAIN, "--seed", "1", "--seconds", "1"])
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", was)
    out, err = capsys.readouterr()
    assert rc == 2 and out == "" and "no TPU" in err
    assert jnp.zeros(1).devices().pop().platform == "cpu"
