"""A configuration file and a reference module are all a family needs:
the program's ``ModelConfig`` is built from the file's fields for every
family the registry has, the reference is found by the name the file
gives, and the dense cells read exactly as they did before."""
import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import tiny

import run
from harness import check, spec, traffic, weights
from harness.spec import BENCH, Cell
from harness.train import Trainer
from repro.configs.registry import ARCH_IDS

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
SEED = 2 ** 34 + 9

# Read from the harness before the configuration files named their
# reference (at the tiny dense size of ``tiny.config``, seed ``SEED``): the
# weights' sha256 (names in order, then bytes), the served gap of three
# fixed answers, and the gaps of the program's first three steps.
GOLDEN = {
    ("qwen3", True): (
        "d2fc6fb60055cba85c8e3ae62f1e9a178f43ba6b932405cbc393d70d9809c6a4",
        5.250166416168213, 7.041202105792917e-05, 0.00370881566293596,
        0.005434069761469988),
    ("llama", True): (
        "b4ffbde099ac4069385b7ef8f0daf9aa835bf4d36d55b7fa2b514b3e41866654",
        4.928668975830078, 9.302934900577933e-06, 0.002136072847979252,
        0.0016423964028871496),
    ("qwen3", False): (
        "35b9f613b2c06eebbc30a45b37890e3b9f4b440b550254729cb28ce2212156cd",
        4.7160868644714355, 0.0003433221377806986, 0.0025223318470613126,
        0.002345249113412015),
}

# The layout of the full-size cells' weights as it was drawn before:
# {name: [shape, per layer, init]}.
LAYOUT = {
    "qwen3-4b": {
        "embed": [[151936, 2560], False, "embed"],
        "final_norm": [[2560], False, "norm"],
        "ln1": [[2560], True, "norm"], "wq": [[2560, 4096], True, "fan_in"],
        "wk": [[2560, 1024], True, "fan_in"],
        "wv": [[2560, 1024], True, "fan_in"],
        "wo": [[4096, 2560], True, "fan_in"], "ln2": [[2560], True, "norm"],
        "up": [[2560, 9728], True, "fan_in"],
        "gate": [[2560, 9728], True, "fan_in"],
        "down": [[9728, 2560], True, "fan_in"],
        "q_norm": [[128], True, "norm"], "k_norm": [[128], True, "norm"]},
    "smollm-360m": {
        "embed": [[49152, 960], False, "embed"],
        "final_norm": [[960], False, "norm"],
        "ln1": [[960], True, "norm"], "wq": [[960, 960], True, "fan_in"],
        "wk": [[960, 320], True, "fan_in"], "wv": [[960, 320], True, "fan_in"],
        "wo": [[960, 960], True, "fan_in"], "ln2": [[960], True, "norm"],
        "up": [[960, 2560], True, "fan_in"],
        "gate": [[960, 2560], True, "fan_in"],
        "down": [[2560, 960], True, "fan_in"]},
}


def _config_file(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as fh:
        return json.load(fh)


def _from_registry(cfg, reference="program_shaped") -> dict:
    """A configuration file for the registry's ``cfg``."""
    fields = dataclasses.asdict(cfg)
    return {"name": fields.pop("name"), "family": fields.pop("family"),
            "reference": reference, "dtype": cfg.dtype,
            "model_config": fields}


@pytest.fixture
def fixture_references(monkeypatch):
    monkeypatch.setattr(spec, "REFERENCES", FIXTURES)


@pytest.mark.parametrize("name,qk_norm", [("qwen3-4b", True),
                                          ("smollm-360m", False),
                                          ("qwen3-4b-stage", True)])
def test_configuration_files_give_the_program_config(name, qk_norm):
    """As the published keys gave it before the files stated the fields."""
    from repro.configs.base import ModelConfig

    c = _config_file(name)
    assert spec.program_config(c) == ModelConfig(
        name=c["name"], family="dense",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        qk_norm=qk_norm, rope_theta=float(c["rope_theta"]),
        act=c["hidden_act"], norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"], dtype=c["dtype"])


@pytest.mark.parametrize("name", sorted(LAYOUT))
def test_full_size_weights_are_laid_out_as_before(name):
    model = spec.reference_model(_config_file(name))
    got = {k: [list(t.shape), t.per_layer, t.init]
           for k, t in model.layout().items()}
    assert got == LAYOUT[name]
    assert all(t.dtype is None for t in model.layout().values())


@pytest.mark.parametrize("model_type,tied", sorted(GOLDEN))
def test_golden_readings_of_the_dense_harness(model_type, tied):
    """Weights, served gap and the program's first steps against the
    reference read as they did before the reference was found by name."""
    c = tiny.config(model_type=model_type, tie_word_embeddings=tied)
    model, gen = tiny.model(c)
    w = gen(weights.seed_words(SEED))
    h = hashlib.sha256()
    for k in sorted(w):
        h.update(k.encode())
        h.update(np.asarray(w[k]).tobytes())
    rng = np.random.default_rng(5)
    samples = [(rng.integers(0, 512, 20).astype(np.int32),
                rng.integers(0, 512, 6).astype(np.int32)) for _ in range(3)]
    served = check.served_gap(model, w, samples)["served_gap"]

    mix = tiny.TRAIN_MIX
    cell = tiny.cell("train", c)
    model, pcfg, gen = run._model(cell)
    params = run._program_params(model, gen, pcfg, SEED)
    batches = traffic.train_batches(mix, SEED, model.vocab)
    first = np.asarray(batches[:3])
    w0 = lambda: gen(weights.seed_words(SEED))
    prog = Trainer(pcfg, model, mix, params, batches).first_steps(
        model.from_program, w0)
    gaps = check.compare_train(
        prog, check.reference_train(model, w0, list(first), mix["lr"]))

    digest, *numbers = GOLDEN[(model_type, tied)]
    assert h.hexdigest() == digest
    got = [served, gaps["loss_gap"], gaps["grad_gap"], gaps["change_gap"]]
    assert got == pytest.approx(numbers, rel=1e-6)


@pytest.mark.parametrize("arch", sorted(ARCH_IDS))
def test_every_registry_family(arch, fixture_references):
    """The registry's reduced preset written as a configuration file gives
    the registry's ``ModelConfig``, and weights drawn from a reference's
    layout have the program's parameter tree."""
    from repro.configs.registry import reduced_config
    from repro.models import lm

    cfg = reduced_config(arch)
    c = _from_registry(cfg)
    assert spec.program_config(c) == cfg
    model = spec.reference_model(c)
    gen = weights.make_generator(model.layout(), model.layers,
                                 jnp.dtype(c["dtype"]))
    weights.check_matches(
        model.to_program(jax.eval_shape(gen, weights.seed_words(7))),
        jax.eval_shape(lambda: lm.init_lm(cfg, jax.random.PRNGKey(0))))


@pytest.mark.parametrize("spoil,named,read", [
    (lambda c: c.update(family="quantum"), "'quantum'", spec.program_config),
    (lambda c: c["model_config"].update(num_layerz=2), "'num_layerz'",
     spec.program_config),
    (lambda c: c["model_config"]["moe"].update(top_kk=2), "'top_kk'",
     spec.program_config),
    (lambda c: c.update(reference="no_such_reference"), "no_such_reference",
     spec.reference_model),
])
def test_an_unknown_family_field_or_reference_stops_the_run(spoil, named,
                                                            read):
    from repro.configs.registry import reduced_config

    c = _from_registry(reduced_config("olmoe-1b-7b"), "dense_gqa")
    spoil(c)
    with pytest.raises(SystemExit, match=named):
        read(c)


def test_a_reference_found_by_name_runs_a_cell(fixture_references):
    """A mamba2 configuration whose reference is a fixture module runs
    through the whole training path of ``run.py`` on the CPU."""
    from repro.configs.registry import reduced_config

    jax.clear_caches()
    c = dict(_from_registry(reduced_config("mamba2-130m").replace(
        dtype="float32")), limits=tiny.limits("smollm-360m"))
    cell = Cell("mamba2.train", 1, c, tiny.TRAIN_MIX, "train",
                [{"name": n, "unit": "x"} for n in tiny.E2E["train"]], [])
    out = run.run_cell(cell, 2 ** 33 + 5, 1.5, False, tiny.CPU)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["checks"]) == {"grad_gap", "change_gap"}
