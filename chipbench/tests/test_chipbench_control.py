"""The comparisons' controls come out as not correct: the float32
reference with every matrix rounded to float8, put in the program's place,
fails the cells' own limits (from their configuration files) at a size a
test run holds."""
import jax.numpy as jnp
import numpy as np
import pytest
import tiny

from harness import check, weights


def _model(**kw):
    c = tiny.config(**kw)
    model, gen = tiny.model(c)
    return c, model, gen(weights.seed_words(2 ** 34 + 9))


def _greedy(model, w, prompt, n):
    """The reference's own greedy answer: a served answer with gap 0."""
    seq = jnp.asarray(prompt)[None]
    out = []
    for _ in range(n):
        h = model.final_hidden(w, seq)
        t = int(jnp.argmax(h[0, -1] @ model.head_matrix(w)))
        out.append(t)
        seq = jnp.concatenate([seq, jnp.array([[t]], seq.dtype)], axis=1)
    return np.array(out, np.int32)


def test_serving_control_fails_the_served_gap_limit():
    c, model, w = _model(hidden_size=512, intermediate_size=1024,
                         num_hidden_layers=6, num_attention_heads=8,
                         num_key_value_heads=2, head_dim=64,
                         vocab_size=8192)
    rng = np.random.default_rng(0)
    samples = []
    for _ in range(3):
        p = rng.integers(0, c["vocab_size"], 48).astype(np.int32)
        samples.append((p, _greedy(model, w, p, 24)))
    out = check.served_gap(model, w, samples, control=True)
    limit = c["limits"]["serve"]["served_gap"]
    assert out["served_gap"] == 0.0
    assert out["control_gap"] > limit


def test_training_control_and_fault_fail_a_train_limit():
    c, model, w = _model(model_type="llama")
    tokens = np.random.default_rng(1).integers(
        0, c["vocab_size"], (3, 4, 64)).astype(np.int32)
    refr = check.reference_train(model, lambda: dict(w), list(tokens), 3e-4)
    limits = c["limits"]["train"]
    for variant in ("float8", "half_batch", "dropped_shards"):
        other = check.reference_train(model, lambda: dict(w), list(tokens),
                                      3e-4, variant=variant)
        gaps = check.compare_train(other, refr)
        assert any(gaps[k] > limits[k] for k in limits), (variant, gaps)
    same = check.compare_train(refr, refr)
    assert all(v == pytest.approx(0.0) for v in same.values())
