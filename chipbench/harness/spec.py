"""What a cell is: its entry in ``BENCHMARK.json`` and the data files that
entry names.  Files are found by name: ``configs`` entries give their file,
a traffic mix is ``traffic/<name>.json`` and a per-layer metric's reader is
``metrics/<name>.py``.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file
    traffic: dict           # the traffic file
    traffic_name: str
    end_to_end: list        # this cell's end-to-end metric entries
    per_layer: list         # this cell's per-layer metric entries


def _read_json(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return Cell(name=name, chips=w["chips"],
                config=_read_json(os.path.join(root, conf["file"])),
                traffic=_read_json(os.path.join(BENCH, "traffic",
                                                f"{w['traffic']}.json")),
                traffic_name=w["traffic"], end_to_end=e2e, per_layer=layer)


def metric_reader(name: str):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def program_config(c: dict):
    """The program's ``ModelConfig`` for configuration file ``c``."""
    from repro.configs.base import ModelConfig

    qk_norm = {"qwen3": True, "llama": False}[c["model_type"]]
    return ModelConfig(
        name=c["name"], family="dense",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        qk_norm=qk_norm, rope_theta=float(c["rope_theta"]),
        act=c["hidden_act"], norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"], dtype=c["dtype"])


def reference_spec(c: dict):
    from reference.dense_gqa import Spec

    return Spec(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                heads=c["num_attention_heads"],
                kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                rope_theta=float(c["rope_theta"]), eps=c["rms_norm_eps"],
                qk_norm=c["model_type"] == "qwen3")
