"""A tiny cell of each kind, for CPU tests of the harness."""
from __future__ import annotations

import json
import os

from harness.spec import BENCH, Cell

E2E = {"serve": ["serve_tok_s", "ttft_p90_ms", "itl_p99_ms", "setup_s"],
       "train": ["train_tok_s", "setup_s"]}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def limits(config: str) -> dict:
    with open(os.path.join(BENCH, "configs", f"{config}.json")) as fh:
        return json.load(fh)["limits"]


def config(model_type: str = "qwen3", dtype: str = "bfloat16", **kw) -> dict:
    """A dense GQA configuration file: published keys, and the registry's
    fields for them (qk-norm for ``qwen3``, none for ``llama``)."""
    c = {"name": "tiny", "model_type": model_type, "hidden_size": 64,
         "intermediate_size": 128, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "vocab_size": 512, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
         "tie_word_embeddings": True, "hidden_act": "silu", "dtype": dtype,
         "family": "dense", "reference": "dense_gqa",
         "qk_norm": model_type == "qwen3",
         "limits": {**limits("qwen3-4b"), **limits("smollm-360m")}}
    c.update(kw)
    c["model_config"] = {
        "num_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
        "num_heads": c["num_attention_heads"],
        "num_kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"],
        "d_ff": c["intermediate_size"], "vocab_size": c["vocab_size"],
        "qk_norm": c["qk_norm"], "rope_theta": float(c["rope_theta"]),
        "act": c["hidden_act"], "norm_eps": c["rms_norm_eps"],
        "tie_embeddings": c["tie_word_embeddings"], "dtype": c["dtype"]}
    return c


def model(c: dict):
    """The reference model of ``c`` and the generator of the weights its
    layout gives, in the configuration's dtype."""
    import jax.numpy as jnp

    from harness import spec, weights

    m = spec.reference_model(c)
    return m, weights.make_generator(m.layout(), m.layers,
                                     jnp.dtype(c["dtype"]))


def serve_mix(kind: str = "open_loop") -> dict:
    m = {"kind": kind,
         "engine": {"kv_layout": "dense", "slots": 4, "max_seq": 128,
                    "mode": "fused", "steps_per_sync": 4,
                    "prefill_chunk": 16},
         "rate_per_s": 6.0, "interarrival": {"dist": "gamma", "cv": 2.0},
         "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                           "min": 4, "max": 80},
         "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.7,
                           "min": 2, "max": 40},
         "preroll_s": 0.3, "drain_limit_s": 10.0, "check_requests": 4,
         "block": 8}
    if kind == "closed_loop":
        m.update(clients=4, requests=400)
    return m


TRAIN_MIX = {"kind": "train", "seq_len": 64, "batch": 4, "batches": 8,
             "lr": 3e-4, "clip_norm": 1.0, "remat": True, "check_steps": 3}


def cell(kind: str, cfg: dict | None = None) -> Cell:
    mix = TRAIN_MIX if kind == "train" else serve_mix(kind)
    names = E2E["train" if kind == "train" else "serve"]
    return Cell(f"tiny.{kind}", 1, cfg or config(), mix, kind,
                [{"name": n, "unit": "x"} for n in names], [])
