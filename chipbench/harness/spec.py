"""What a cell is: its entry in ``BENCHMARK.json`` and the data files that
entry names.  Files are found by name: ``configs`` entries give their file,
a configuration's plain reference is ``reference/<its "reference">.py``, a
traffic mix is ``traffic/<name>.json`` and a per-layer metric's reader is
``metrics/<name>.py``.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
import sys
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
    return _load(os.path.join(BENCH, "metrics", f"{name}.py"),
                 f"chipbench_metric_{name.replace('.', '_')}").read


def _known_families() -> set:
    """The families of the registry's models (``configs/registry.py``)."""
    from repro.configs.registry import ARCH_IDS, get_config

    return {get_config(a).family for a in ARCH_IDS}


def _build(cls, fields: dict, where: str):
    """``cls(**fields)`` for a dataclass of the registry, with each nested
    dataclass built from its dict; a field ``cls`` lacks stops the run."""
    import dataclasses
    import typing

    hints = typing.get_type_hints(cls)
    known = {f.name for f in dataclasses.fields(cls)}
    unknown = sorted(set(fields) - known)
    if unknown:
        raise SystemExit(f"{where}: {cls.__name__} has no field "
                         f"{', '.join(map(repr, unknown))}")
    kw = {}
    for k, v in fields.items():
        sub = [t for t in typing.get_args(hints[k]) or (hints[k],)
               if dataclasses.is_dataclass(t)]
        kw[k] = _build(sub[0], v, f"{where}.{k}") \
            if sub and isinstance(v, dict) else v
    return cls(**kw)


def program_config(c: dict):
    """The program's ``ModelConfig`` for configuration file ``c``: its
    ``name``, its ``family`` and the registry's field values under
    ``model_config``.  A family the registry does not have, or a field
    ``ModelConfig`` does not have, stops the run with its name."""
    from repro.configs.base import ModelConfig

    families = _known_families()
    if c.get("family") not in families:
        raise SystemExit(f"configuration {c.get('name')!r}: unknown family "
                         f"{c.get('family')!r}; the registry has "
                         f"{sorted(families)}")
    return _build(ModelConfig, {"name": c["name"], "family": c["family"],
                                **c["model_config"]},
                  f"configuration {c['name']!r}")


@functools.cache
def _load(path: str, module: str):
    spec = importlib.util.spec_from_file_location(module, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[module] = mod       # dataclasses look their module up here
    spec.loader.exec_module(mod)
    return mod


# where a configuration's ``"reference"`` is looked up
REFERENCES = os.path.join(BENCH, "reference")


def reference_model(c: dict):
    """The plain reference of configuration file ``c``: ``build(c)`` of
    ``reference/<c["reference"]>.py``."""
    path = os.path.join(REFERENCES, f"{c['reference']}.py")
    if not os.path.isfile(path):
        raise SystemExit(f"configuration {c['name']!r}: no reference "
                         f"module {path}")
    return _load(path, f"chipbench_reference_{c['reference']}").build(c)
