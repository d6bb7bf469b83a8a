"""A whole run, past the chip check, at a tiny size on the CPU: sound, it
is correct; with the timed path broken underneath, ``correct`` is false.
The limits are the cells' own, from their configuration files."""
import jax
import pytest
import tiny

import run


@pytest.fixture(autouse=True)
def fresh_programs():
    """Programs traced under a planted fault must not be reused."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _run(kind, seconds=1.5):
    return run.run_cell(tiny.cell(kind), 2 ** 33 + 17, seconds, False,
                        tiny.CPU)


@pytest.mark.parametrize("kind", ["open_loop", "closed_loop", "train"])
def test_sound_runs_are_correct(kind):
    out = _run(kind)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("kind", ["open_loop", "closed_loop"])
def test_a_token_altered_where_it_is_produced(monkeypatch, kind):
    from repro.serve import engine

    real = engine.sample_batch

    def altered(logits, keys, temperature, top_k):
        return (real(logits, keys, temperature, top_k) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "sample_batch", altered)
    out = _run(kind)
    assert not out["correct"], out["checks"]


def _patch_step(monkeypatch, wrap):
    from repro.train import train_step

    real = train_step.make_train_step
    monkeypatch.setattr(train_step, "make_train_step",
                        lambda *a, **k: wrap(real(*a, **k)))


def test_a_step_that_returns_its_state_unchanged(monkeypatch):
    def wrap(step):
        def unchanged(params, opt_state, batch, i):
            _, _, metrics = step(params, opt_state, batch, i)
            return params, opt_state, metrics
        return unchanged

    _patch_step(monkeypatch, wrap)
    out = _run("train")
    assert not out["correct"], out["checks"]


def test_half_the_batch_left_out(monkeypatch):
    def wrap(step):
        def half(params, opt_state, batch, i):
            t = batch["tokens"]
            return step(params, opt_state, {"tokens": t[: t.shape[0] // 2]}, i)
        return half

    _patch_step(monkeypatch, wrap)
    out = _run("train")
    assert not out["correct"], out["checks"]
