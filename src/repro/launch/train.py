"""Training launcher: --arch <id> with optional host-device mesh.

    # CPU-sized smoke run:
    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --preset reduced --steps 50

    # sharded run on host devices (sets the device count BEFORE jax init):
    REPRO_TRAIN_DEVICES=8 PYTHONPATH=src python -m repro.launch.train \
        --arch smollm-360m --preset reduced --steps 20 --mesh 2 4

On a TPU host, drop REPRO_TRAIN_DEVICES and pass the chips' mesh as
--mesh (``python chip_smoke.py --four-chips`` runs that path on a 2x2
host); restarts resume from --ckpt-dir automatically (ExpoCloud
reassignment-compatible, see examples/train_lm.py for the task wrapper).
"""
import os

if os.environ.get("REPRO_TRAIN_DEVICES"):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=" +
                               os.environ["REPRO_TRAIN_DEVICES"]).strip()

import argparse


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--preset", choices=["reduced", "full"],
                    default="reduced")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", type=int, nargs="*", default=None,
                    help="e.g. --mesh 2 4 for a (data=2, model=4) mesh")
    ap.add_argument("--no-zero1", action="store_true")
    args = ap.parse_args(argv)

    from repro.configs import get_config, reduced_config
    from repro.data.synthetic import data_config_for
    from repro.launch import compile_cache
    from repro.train.loop import TrainJob, run_training

    compile_cache.enable()
    cfg = (reduced_config(args.arch) if args.preset == "reduced"
           else get_config(args.arch))
    dc = data_config_for(cfg, seq_len=args.seq, batch_size=args.batch)
    rules = None
    if args.mesh:
        from repro.launch.mesh import make_mesh
        from repro.sharding.rules import make_rules

        axes = ("data", "model")[:len(args.mesh)] if len(args.mesh) <= 2 \
            else ("pod", "data", "model")
        rules = make_rules(make_mesh(tuple(args.mesh), axes))
    job = TrainJob(total_steps=args.steps, ckpt_every=args.ckpt_every,
                   ckpt_dir=args.ckpt_dir, base_lr=args.lr,
                   optimizer=args.optimizer, zero1=not args.no_zero1,
                   log_every=max(1, args.steps // 10))
    hist, final, _ = run_training(cfg, dc, job, rules=rules)
    print(f"[launch.train] {args.arch} ({args.preset}) done at step {final}; "
          f"loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
