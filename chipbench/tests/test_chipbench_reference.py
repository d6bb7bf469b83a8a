"""At a tiny size, in float32, the plain reference agrees with the
program's logits and loss on the benchmark's weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import tiny

from harness import spec, weights
from harness.arith import Arch
from reference import dense_gqa as ref


def _setup(model_type, tied):
    c = tiny.config(model_type=model_type, dtype="float32",
                    tie_word_embeddings=tied)
    arch = Arch.from_config(c)
    gen = weights.make_generator(arch, tied=tied,
                                 qk_norm=model_type == "qwen3",
                                 dtype=jnp.float32)
    w = gen(weights.seed_words(2 ** 35 + 3))
    return c, spec.program_config(c), spec.reference_spec(c), w


@pytest.mark.parametrize("model_type,tied", [("qwen3", True), ("llama", True),
                                             ("qwen3", False)])
def test_reference_matches_program_logits_and_loss(model_type, tied):
    from repro.models import lm

    c, pcfg, rspec, w = _setup(model_type, tied)
    # float32 weights: the program computes in float32 on them
    params = weights.program_tree(w)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                                c["vocab_size"])
    want, _ = lm.prefill(pcfg, params, {"tokens": tokens})
    h = ref.final_hidden(rspec, w, tokens)
    got = jnp.matmul(h[:, -1], ref.head_matrix(w), precision=ref.HIGHEST)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    loss_p, _ = lm.train_loss(pcfg, params, {"tokens": tokens}, remat=False)
    loss_r = ref.loss(rspec, w, tokens)
    assert float(loss_r) == pytest.approx(float(loss_p), rel=1e-5)


def test_weights_depend_on_the_whole_seed():
    c, _, _, w = _setup("qwen3", True)
    gen = weights.make_generator(Arch.from_config(c), tied=True, qk_norm=True,
                                 dtype=jnp.float32)
    other = gen(weights.seed_words(3))          # same low word, no high one
    assert not np.array_equal(w["wq"], other["wq"])
    again = gen(weights.seed_words(2 ** 35 + 3))
    assert all(np.array_equal(w[k], again[k]) for k in w)


def test_a_narrower_reference_moves_the_logits():
    c, _, rspec, w = _setup("qwen3", True)
    tokens = jnp.arange(20)[None] % c["vocab_size"]
    full = ref.final_hidden(rspec, w, tokens)
    narrow = ref.final_hidden(rspec, w, tokens, jnp.float8_e4m3fn)
    assert 0 < float(jnp.max(jnp.abs(full - narrow))) < 1.0
