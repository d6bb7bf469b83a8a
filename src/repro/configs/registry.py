"""Architecture registry: ``--arch <id>`` resolution + reduced smoke configs."""
from __future__ import annotations

import dataclasses
import importlib

from repro.configs.base import ModelConfig, MoEConfig, SSMConfig, MLAConfig
from repro.configs.shapes import SHAPES, ShapeConfig, get_shape, shape_applicable

_ARCH_MODULES = {
    "smollm-360m": "repro.configs.smollm_360m",
    "granite-20b": "repro.configs.granite_20b",
    "qwen3-4b": "repro.configs.qwen3_4b",
    "chatglm3-6b": "repro.configs.chatglm3_6b",
    "deepseek-v3-671b": "repro.configs.deepseek_v3_671b",
    "deepseek-v2-lite": "repro.configs.deepseek_v2_lite",
    "olmoe-1b-7b": "repro.configs.olmoe_1b_7b",
    "phi-3-vision-4.2b": "repro.configs.phi3_vision_4_2b",
    "jamba-v0.1-52b": "repro.configs.jamba_v0_1_52b",
    "mamba2-130m": "repro.configs.mamba2_130m",
    "musicgen-medium": "repro.configs.musicgen_medium",
}

ARCH_IDS = tuple(_ARCH_MODULES)


def get_config(name: str) -> ModelConfig:
    if name not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_ARCH_MODULES)}")
    return importlib.import_module(_ARCH_MODULES[name]).CONFIG


def cells(include_inapplicable: bool = False):
    """All (arch, shape) cells of the assigned grid, in registry order."""
    out = []
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in SHAPES.values():
            if include_inapplicable or shape_applicable(cfg, shape):
                out.append((arch, shape.name))
    return out


def reduced_config(name: str) -> ModelConfig:
    """A tiny same-family config for CPU smoke tests.

    Keeps every structural feature of the full config (GQA ratio, MLA with
    or without query compression, YaRN, MoE top-k and a held share of the
    experts, hybrid interleave, codebooks ...) at toy width/depth.
    """
    cfg = get_config(name)
    kw: dict = dict(
        num_layers=min(cfg.num_layers, 4),
        d_model=128,
        vocab_size=256,
        tie_embeddings=cfg.tie_embeddings,
    )
    if cfg.attention_kind == "gqa":
        # preserve the q:kv ratio where possible
        ratio = max(1, cfg.num_heads // max(cfg.num_kv_heads, 1))
        kv = max(1, 4 // ratio) if ratio <= 4 else 1
        kw.update(num_heads=kv * min(ratio, 4), num_kv_heads=kv, head_dim=32)
    if cfg.d_ff:
        kw.update(d_ff=256)
    if cfg.d_ff_dense:
        kw.update(d_ff_dense=256)
    if cfg.moe is not None:
        m = cfg.moe
        share = m.held_first or m.held_count
        kw["moe"] = dataclasses.replace(
            m,
            num_experts=min(m.num_experts, 8),
            top_k=min(m.top_k, 2),
            d_ff_expert=64,
            first_k_dense=min(m.first_k_dense, 1),
            # a share keeps a share: half of the reduced experts
            held_first=0,
            held_count=min(m.num_experts, 8) // 2 if share else 0,
        )
    if cfg.ssm is not None:
        kw["ssm"] = dataclasses.replace(
            cfg.ssm, d_state=16, head_dim=32, chunk=32
        )
    if cfg.mla is not None:
        kw["mla"] = MLAConfig(
            q_lora_rank=64 if cfg.mla.q_lora_rank else 0,
            kv_lora_rank=32,
            qk_nope_head_dim=32,
            qk_rope_head_dim=16,
            v_head_dim=32,
        )
        kw.update(num_heads=4, num_kv_heads=4, head_dim=32)
    if cfg.hybrid_block:
        kw.update(num_layers=8, hybrid_block=4, hybrid_attn_index=2)
        kw["moe"] = dataclasses.replace(kw["moe"], first_k_dense=1, every=2)
    if cfg.num_image_tokens:
        kw.update(num_image_tokens=8)
    if cfg.mtp_depth:
        kw.update(mtp_depth=1)
    return cfg.replace(**kw)


REDUCED_SHAPE = ShapeConfig("smoke", seq_len=64, global_batch=2, kind="train")


def segment_counts(cfg) -> list[int]:
    """Scanned-unit counts per segment (layers, or super-blocks for hybrid).
    Mirrors repro.models.lm.segments."""
    if cfg.hybrid_block:
        return [cfg.num_layers // cfg.hybrid_block]
    if cfg.moe is not None and cfg.moe.first_k_dense:
        return [cfg.moe.first_k_dense,
                cfg.num_layers - cfg.moe.first_k_dense]
    return [cfg.num_layers]


def with_segment_counts(cfg: ModelConfig, counts: list[int]) -> ModelConfig:
    """Rebuild the config with new scanned-unit counts per segment (for
    unrolled roofline probes — see launch/sweep_dryrun.py)."""
    cur = segment_counts(cfg)
    assert len(counts) == len(cur), (counts, cur)
    if cfg.hybrid_block:
        return cfg.replace(num_layers=counts[0] * cfg.hybrid_block)
    if cfg.moe is not None and cfg.moe.first_k_dense:
        fk, nm = counts
        return cfg.replace(
            num_layers=fk + nm,
            moe=dataclasses.replace(cfg.moe, first_k_dense=fk))
    return cfg.replace(num_layers=counts[0])

__all__ = [
    "ARCH_IDS",
    "get_config",
    "get_shape",
    "cells",
    "reduced_config",
    "shape_applicable",
    "REDUCED_SHAPE",
    "SHAPES",
]
