"""At a tiny size, in float32, the plain reference agrees with the
program's logits and loss on the benchmark's weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import tiny

from harness import spec, weights
from reference.common import HIGHEST


def _setup(model_type, tied):
    c = tiny.config(model_type=model_type, dtype="float32",
                    tie_word_embeddings=tied)
    model, gen = tiny.model(c)
    w = gen(weights.seed_words(2 ** 35 + 3))
    return c, spec.program_config(c), model, w


@pytest.mark.parametrize("model_type,tied", [("qwen3", True), ("llama", True),
                                             ("qwen3", False)])
def test_reference_matches_program_logits_and_loss(model_type, tied):
    from repro.models import lm

    c, pcfg, model, w = _setup(model_type, tied)
    # float32 weights: the program computes in float32 on them
    params = model.to_program(w)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                                c["vocab_size"])
    want, _ = lm.prefill(pcfg, params, {"tokens": tokens})
    h = model.final_hidden(w, tokens)
    got = jnp.matmul(h[:, -1], model.head_matrix(w), precision=HIGHEST)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    loss_p, _ = lm.train_loss(pcfg, params, {"tokens": tokens}, remat=False)
    loss_r = model.loss(w, tokens)
    assert float(loss_r) == pytest.approx(float(loss_p), rel=1e-5)


def test_weights_depend_on_the_whole_seed():
    c, _, _, w = _setup("qwen3", True)
    _, gen = tiny.model(c)
    other = gen(weights.seed_words(3))          # same low word, no high one
    assert not np.array_equal(w["wq"], other["wq"])
    again = gen(weights.seed_words(2 ** 35 + 3))
    assert all(np.array_equal(w[k], again[k]) for k in w)


def test_a_narrower_reference_moves_the_logits():
    c, _, model, w = _setup("qwen3", True)
    tokens = jnp.arange(20)[None] % c["vocab_size"]
    full = model.final_hidden(w, tokens)
    narrow = model.final_hidden(w, tokens, jnp.float8_e4m3fn)
    assert 0 < float(jnp.max(jnp.abs(full - narrow))) < 1.0
