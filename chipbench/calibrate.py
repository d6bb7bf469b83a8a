"""Readings the benchmark's limits and rates are set from; the benchmark's
own runs never run this.

    python chipbench/calibrate.py readings --workload <cell> --seeds 1,2,3 \\
        --control-seeds 1,2,3 --seconds <s>
    python chipbench/calibrate.py sweep --workload <cell> --rates 0.3,0.4 \\
        --seconds <s>

``readings`` runs the cell once per seed in one process, at the cell's own
load and sizes, and prints each number the comparison reads; on the
control seeds it also reads the control (the reference in float8) and, for
a training cell, the planted half-batch fault and, on a ZeRO-1 mesh, the
planted fault that drops half of each tensor's update.  ``sweep`` offers
an open loop at each rate in turn to one engine and prints how the backlog
and the time to first token grow, to find the highest rate the engine
sustains.
Each line of standard output is one JSON object.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run


def readings(args, cell, device) -> None:
    control = {int(s) for s in args.control_seeds.split(",") if s}
    driver = run.train_cell if cell.traffic["kind"] == "train" \
        else run.serve_cell
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = driver(cell, seed, args.seconds, None, time.perf_counter, t,
                     device["count"], control=seed in control)
        print(json.dumps({"seed": seed, "readings": out["readings"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "metrics": out["metrics"],
                          "memory_peak_bytes": out["memory_peak_bytes"]}),
              flush=True)
        del out
        gc.collect()


def sweep(args, cell, device) -> None:
    from harness import serve, traffic
    from repro.serve.engine import DecodeEngine

    mix, e = cell.traffic, cell.traffic["engine"]
    model, pcfg, gen = run._model(cell)
    params = run._program_params(model, gen, pcfg, 0)
    eng = DecodeEngine(pcfg, params, batch_slots=e["slots"],
                       max_seq=e["max_seq"], rng_seed=0, mode=e["mode"],
                       steps_per_sync=e["steps_per_sync"],
                       prefill_chunk=e["prefill_chunk"],
                       kv_layout=e["kv_layout"])
    serve.warm_up(eng, mix, model.vocab, 0)
    for rate in (float(r) for r in args.rates.split(",")):
        m = dict(mix, rate_per_s=rate)
        items = traffic.open_loop(m, 1, args.seconds, model.vocab)
        loop = serve.Loop(eng, m, items, args.seconds, time.perf_counter)
        queue = []
        step = loop._step

        def observed_step():
            rec = step()
            queue.append((rec.t1, len(eng.queue)))
            return rec

        loop._step = observed_step
        loop.run(preroll=m["preroll_s"])
        res = loop.results()
        inside = [q for t, q in queue if loop.w0 <= t < loop.w1]
        half = len(inside) // 2
        print(json.dumps({
            "rate": rate, **res["metrics"],
            "attempted": res["attempted"],
            "queue_first_half": sum(inside[:half]) / max(1, half),
            "queue_second_half": sum(inside[half:]) / max(1, len(inside) - half),
            "lateness_p50_s": sorted(loop.lateness)[len(loop.lateness) // 2]}),
            flush=True)
        eng.queue.clear()
        eng.run_until_drained()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("readings", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)

    from harness import chip, spec

    cell = spec.load_cell(args.workload)
    run.log(f"compile cache: {run.enable_compile_cache()}")
    device = chip.require_chip(cell.chips)
    (readings if args.mode == "readings" else sweep)(args, cell, device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
