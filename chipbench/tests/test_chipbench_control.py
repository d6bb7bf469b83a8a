"""The comparisons' controls come out as not correct: the float32
reference with every matrix rounded to float8, put in the program's place,
fails the cells' own limits (from their configuration files) at a size a
test run holds."""
import jax.numpy as jnp
import numpy as np
import pytest
import tiny

from harness import check, spec, weights
from harness.arith import Arch
from reference import dense_gqa as ref


def _model(**kw):
    c = tiny.config(**kw)
    arch = Arch.from_config(c)
    gen = weights.make_generator(arch, tied=c["tie_word_embeddings"],
                                 qk_norm=c["model_type"] == "qwen3",
                                 dtype=jnp.bfloat16)
    return c, spec.reference_spec(c), gen(weights.seed_words(2 ** 34 + 9))


def _greedy(rspec, w, prompt, n):
    """The reference's own greedy answer: a served answer with gap 0."""
    seq = jnp.asarray(prompt)[None]
    out = []
    for _ in range(n):
        h = ref.final_hidden(rspec, w, seq)
        t = int(jnp.argmax(h[0, -1] @ ref.head_matrix(w)))
        out.append(t)
        seq = jnp.concatenate([seq, jnp.array([[t]], seq.dtype)], axis=1)
    return np.array(out, np.int32)


def test_serving_control_fails_the_served_gap_limit():
    c, rspec, w = _model(hidden_size=512, intermediate_size=1024,
                         num_hidden_layers=6, num_attention_heads=8,
                         num_key_value_heads=2, head_dim=64,
                         vocab_size=8192)
    rng = np.random.default_rng(0)
    samples = []
    for _ in range(3):
        p = rng.integers(0, c["vocab_size"], 48).astype(np.int32)
        samples.append((p, _greedy(rspec, w, p, 24)))
    out = check.served_gap(rspec, w, samples, control=True)
    limit = c["limits"]["serve"]["served_gap"]
    assert out["served_gap"] == 0.0
    assert out["control_gap"] > limit


def test_training_control_and_fault_fail_a_train_limit():
    c, rspec, w = _model(model_type="llama")
    tokens = np.random.default_rng(1).integers(
        0, c["vocab_size"], (3, 4, 64)).astype(np.int32)
    refr = check.reference_train(rspec, w, list(tokens), 3e-4)
    limits = c["limits"]["train"]
    for variant in ("float8", "half_batch"):
        other = check.reference_train(rspec, w, list(tokens), 3e-4,
                                      variant=variant)
        gaps = check.compare_train(other, refr)
        assert any(gaps[k] > limits[k] for k in limits), (variant, gaps)
    same = check.compare_train(refr, refr)
    assert all(v == pytest.approx(0.0) for v in same.values())
