import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=" +
                           os.environ.get("REPRO_DRYRUN_DEVICES", "512")).strip()
# NOTE: the lines above MUST run before any other import (including jax
# and repro.*): jax locks the platform and device count on first backend
# init.  The dry run compiles on forced host devices, never on a chip.

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh and extract roofline terms from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b \
        --shape train_4k [--multi-pod] [--json out.json] [--variant k=v ...]

Succeeding here proves the distribution config is coherent: shardings
resolve, collectives lower, and the memory analysis is reported per cell.
Exercised for the 16x16 single-pod mesh AND the 2x16x16 multi-pod mesh.

Variants (perf hillclimbing knobs; defaults = paper-faithful baseline):
    remat=dots|none|full   activation checkpointing policy
    seq_shard=0|1          shard sequence dim over 'data' (SP)
    zero1=0|1              ZeRO-1 optimizer-state sharding
    optimizer=adamw|adafactor
    donate=0|1             donate params/opt buffers
    flash_block_q / flash_block_k (informational on CPU)
"""
import argparse
import contextlib
import json
import math
import sys
import time


def parse_variant(pairs):
    out = {"remat": "dots", "seq_shard": 0, "zero1": 1,
           "optimizer": "adamw", "donate": 1}
    for p in pairs or []:
        k, v = p.split("=", 1)
        out[k] = int(v) if v.isdigit() else v
    return out


def tree_local_bytes(tree) -> float:
    """Per-device bytes of a ShapeDtypeStruct tree honouring shardings."""
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        nbytes = math.prod(leaf.shape) * leaf.dtype.itemsize \
            if leaf.shape else leaf.dtype.itemsize
        sh = getattr(leaf, "sharding", None)
        if sh is not None:
            with contextlib.suppress(Exception):
                local = sh.shard_shape(leaf.shape)
                nbytes = math.prod(local) * leaf.dtype.itemsize
        total += nbytes
    return float(total)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             variant: dict | None = None, mesh_shape=None, mesh_axes=None,
             seg_counts=None, verbose: bool = True) -> dict:
    import jax
    from repro.configs import get_config, get_shape, shape_applicable
    from repro.configs.analysis import model_flops, param_counts
    from repro.launch import roofline as R
    from repro.launch.inputs import input_specs
    from repro.launch.mesh import make_mesh, make_production_mesh
    from repro.models import lm
    from repro.sharding.rules import make_rules, use_rules
    from repro.train.optimizer import get_optimizer
    from repro.train.schedule import warmup_cosine
    from repro.train.train_step import make_train_step

    from repro.configs.registry import with_segment_counts

    variant = dict(variant or {})
    v = parse_variant([])
    v.update(variant)
    cfg = get_config(arch)
    if seg_counts is not None:
        cfg = with_segment_counts(cfg, list(seg_counts))
    unroll = bool(v.get("unroll", 0))
    if unroll:
        os.environ["REPRO_UNROLL_INNER"] = "1"
        os.environ.setdefault("REPRO_SSD_CHUNK", "512")
    if v.get("moe"):                      # MoE dispatch strategy (§Perf)
        os.environ["REPRO_MOE"] = str(v["moe"])
    if v.get("flash_block"):              # KV block size of the flash path
        os.environ["REPRO_FLASH_BLOCK"] = str(v["flash_block"])
    if v.get("xent_chunk"):               # loss chunk length
        os.environ["REPRO_XENT_CHUNK"] = str(v["xent_chunk"])
    shape = get_shape(shape_name)
    if not shape_applicable(cfg, shape):
        return {"arch": arch, "shape": shape_name, "status": "inapplicable",
                "note": "full-attention arch at 500k (by design; DESIGN.md)"}

    t0 = time.time()
    mesh = (make_mesh(tuple(mesh_shape), tuple(mesh_axes))
            if mesh_shape is not None
            else make_production_mesh(multi_pod=multi_pod))
    chips = math.prod(mesh.shape.values())
    mesh_desc = "x".join(f"{k}{v_}" for k, v_ in mesh.shape.items())
    rules = make_rules(mesh, seq_shard=bool(v["seq_shard"]))
    if v.get("kv_shard_model"):
        # decode-cell fix: shard the KV/latent cache's sequence dim over the
        # (otherwise idle at decode) TP axis -> cache bytes/device /16 and
        # attention reads become a psum over 'model'
        rules.table.update(seq_kv=("model",))
    if v.get("sp_model"):
        # Megatron-style sequence parallelism: residual/norm activations
        # sharded over the TP axis on the sequence dim -> XLA turns the
        # per-layer all-reduces into reduce-scatter + all-gather pairs
        rules.table.update(seq=("model",))
    if v.get("dp_only"):
        # §Perf sharding-scheme variant: fold the 'model' axis into data
        # parallelism (no TP) — right-sizes tiny models on the fixed mesh
        rules.table.update(
            batch=tuple(mesh.axis_names),
            heads=(), kv_heads=(), ffn=(), vocab=(), experts=(),
        )

    opt_name = v["optimizer"]
    opt = get_optimizer(opt_name)
    remat = v["remat"] != "none"

    with mesh, use_rules(rules):
        if shape.kind == "train":
            lr_fn = warmup_cosine(3e-4, 100, 10_000)
            step_fn = make_train_step(cfg, opt, lr_fn, remat=remat,
                                      unroll=unroll)
            args = input_specs(cfg, shape, rules, opt=opt, opt_name=opt_name,
                               zero1=bool(v["zero1"]))
            donate = (0, 1) if v["donate"] else ()
            jitted = jax.jit(step_fn, donate_argnums=donate)
        elif shape.kind == "prefill":
            args = input_specs(cfg, shape, rules)
            jitted = jax.jit(
                lambda p, b: lm.prefill(cfg, p, b, unroll=unroll))
        else:
            args = input_specs(cfg, shape, rules)
            donate = (2,) if v["donate"] else ()
            jitted = jax.jit(
                lambda p, b, c: lm.decode_step(cfg, p, b, c, unroll=unroll),
                donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem_note = ""
    try:
        mem = compiled.memory_analysis()
        mem_note = str(mem)
    except Exception as e:  # CPU backend may not support it
        mem_note = f"memory_analysis unavailable on this backend: {e}"
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
    except Exception:
        cost = {}
    hlo = compiled.as_text()

    bytes_per_device = tree_local_bytes(args)
    mf = model_flops(cfg, shape)
    roof = R.analyze(arch=arch, shape=shape_name, mesh_desc=mesh_desc,
                     chips=chips, cost=cost, hlo_text=hlo, model_flops=mf,
                     bytes_per_device=bytes_per_device)
    pc = param_counts(cfg)
    out = {
        "arch": arch, "shape": shape_name, "mesh": mesh_desc,
        "status": "ok", "chips": chips,
        "variant": v, "seg_counts": seg_counts,
        "num_layers": cfg.num_layers,
        "params_total": pc.total, "params_active": pc.active,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory_analysis": mem_note,
        "bytes_per_device_inputs": bytes_per_device,
        "roofline": json.loads(roof.to_json()),
        "hlo_bytes_len": len(hlo),
    }
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} on {mesh_desc}: "
              f"lower {t_lower:.1f}s compile {t_compile:.1f}s | "
              f"inputs {bytes_per_device/1e9:.2f} GB/device | "
              f"dominant={roof.dominant} "
              f"compute={roof.compute_s*1e3:.2f}ms "
              f"memory={roof.memory_s*1e3:.2f}ms "
              f"collective={roof.collective_s*1e3:.2f}ms "
              f"useful={roof.useful_ratio:.2f} "
              f"roofline_frac={roof.roofline_fraction:.3f}")
        print(f"[dryrun] memory_analysis: {mem_note[:400]}")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mesh-shape", type=int, nargs="*", default=None,
                    help="override mesh (tests), e.g. --mesh-shape 2 4")
    ap.add_argument("--mesh-axes", type=str, nargs="*", default=None)
    ap.add_argument("--variant", nargs="*", default=[])
    ap.add_argument("--seg-counts", type=int, nargs="*", default=None)
    ap.add_argument("--json", type=str, default=None)
    args = ap.parse_args(argv)

    res = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   variant=parse_variant(args.variant),
                   mesh_shape=args.mesh_shape, mesh_axes=args.mesh_axes,
                   seg_counts=args.seg_counts)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(res, f, indent=1)
    if res["status"] != "ok" and res["status"] != "inapplicable":
        sys.exit(1)


if __name__ == "__main__":
    main()
