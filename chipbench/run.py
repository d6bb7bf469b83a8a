"""Run one benchmark cell once and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic mix,
plain reference and per-layer metrics are files under ``chipbench/`` found
by name.
The run makes the weights and inputs from ``--seed``, compiles (or loads
from the compile cache) and warms the cell's programs, measures for
``--seconds``, checks what the timed path produced against the plain
float32 reference, and prints one JSON object as the last line of standard
output.  With ``--trace 0`` its metrics are the cell's end-to-end metrics;
with ``--trace 1`` its per-layer metrics, read from a profiler trace of the
first seconds of the window.  Without a TPU of a kind in the peak table, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

# seconds of the window a traced run records
TRACE_SECONDS = 8.0


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    or ``<checkout>/.jax_cache``, a fixed path, caching every program."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Records when backend compilations end, so a run can show that none
    fell in its window."""

    def __init__(self, clock=time.perf_counter):
        import jax

        self.clock, self.times = clock, []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.times.append(self.clock())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t < t1 for t in self.times)


def _model(cell):
    """The configuration's reference model, the program's ``ModelConfig``
    and the weights generator that the reference's layout gives."""
    import jax.numpy as jnp

    from harness import spec, weights

    c = cell.config
    model = spec.reference_model(c)
    gen = weights.make_generator(model.layout(), model.layers,
                                 jnp.dtype(c["dtype"]))
    return model, spec.program_config(c), gen


def _program_params(model, gen, pcfg, seed):
    import jax

    from harness import weights
    from repro.models import lm

    params = model.to_program(gen(weights.seed_words(seed)))
    weights.check_matches(params, jax.eval_shape(
        lambda: lm.init_lm(pcfg, jax.random.PRNGKey(0))))
    return jax.block_until_ready(params)


def _checks(values: dict, limits: dict) -> dict:
    return {k: {"value": values[k], "limit": limits[k]} for k in limits}


def serve_cell(cell, seed, seconds, trace_dir, clock, t_start, chips,
               control=False):
    import numpy as np

    from harness import chip, check, serve, traffic, weights
    from repro.serve.engine import DecodeEngine

    mix, e = cell.traffic, cell.traffic["engine"]
    model, pcfg, gen = _model(cell)
    params = _program_params(model, gen, pcfg, seed)
    eng = DecodeEngine(pcfg, params, batch_slots=e["slots"],
                       max_seq=e["max_seq"], rng_seed=0, mode=e["mode"],
                       steps_per_sync=e["steps_per_sync"],
                       prefill_chunk=e["prefill_chunk"],
                       kv_layout=e["kv_layout"])
    serve.warm_up(eng, mix, model.vocab, seed)
    items = (traffic.open_loop(mix, seed, seconds, model.vocab)
             if mix["kind"] == "open_loop"
             else traffic.requests(mix, seed, mix["requests"], model.vocab))
    loop = serve.Loop(eng, mix, items, seconds, clock,
                      trace_seconds=min(seconds, TRACE_SECONDS),
                      trace_dir=trace_dir)
    loop.run(preroll=mix["preroll_s"])
    out = loop.results()
    out["metrics"]["setup_s"] = loop.w0 - t_start
    out["window"] = (loop.w0, loop.w1)
    out["memory_peak_bytes"] = chip.memory_peak_bytes(chips)
    picked = serve.sample_finished(loop.finished(), mix["check_requests"],
                                   seed)
    samples = [(t.item.prompt, np.asarray(t.req.output, np.int32).reshape(-1))
               for t in picked]
    out["run"] = SimpleNamespace(
        records=loop.traced, span=serve.SPAN, slots=e["slots"],
        steps_per_sync=e["steps_per_sync"], model=model, chips=chips)
    loop.eng = eng = params = None
    gc.collect()
    log(f"device bytes in use before the reference: {chip.bytes_in_use()}")
    t_ref = time.perf_counter()
    gaps = check.served_gap(model, gen(weights.seed_words(seed)), samples,
                            control=control)
    log(f"compared {gaps['served_tokens']} served tokens of "
        f"{len(samples)} requests in {time.perf_counter() - t_ref:.1f} s")
    out["checks"] = _checks(gaps, cell.config["limits"]["serve"])
    out["readings"] = gaps
    return out


def train_cell(cell, seed, seconds, trace_dir, clock, t_start, chips,
               control=False):
    import jax
    import numpy as np

    from harness import chip, check, traffic, weights
    from harness.train import SPAN, Trainer

    mix = cell.traffic
    model, pcfg, gen = _model(cell)
    params = _program_params(model, gen, pcfg, seed)
    batches = traffic.train_batches(mix, seed, model.vocab)
    first = np.asarray(batches[: mix["check_steps"]])
    tr = Trainer(pcfg, model, mix, params, batches)
    del params, batches
    w0 = lambda: gen(weights.seed_words(seed))
    prog = tr.first_steps(model.from_program, w0)
    jax.block_until_ready(tr.params)
    t0 = clock()
    out = tr.window(seconds, clock, trace_dir,
                    min(seconds, TRACE_SECONDS))
    out["metrics"]["setup_s"] = t0 - t_start
    out["window"] = (t0, clock())
    out["memory_peak_bytes"] = chip.memory_peak_bytes(chips)
    out["run"] = SimpleNamespace(records=[], span=SPAN, model=model,
                                 chips=chips, batch=mix["batch"],
                                 seq=mix["seq_len"])
    tr = None
    gc.collect()
    log(f"device bytes in use before the reference: {chip.bytes_in_use()}")
    t_ref = time.perf_counter()
    devices = jax.devices()[:chips]
    refr = check.reference_train(model, w0, list(first), mix["lr"],
                                 devices=devices)
    gaps = check.compare_train(prog, refr)
    log(f"losses: program {prog['losses']} reference {refr['losses']} "
        f"(reference {time.perf_counter() - t_ref:.1f} s)")
    out["checks"] = _checks(gaps, cell.config["limits"]["train"])
    out["readings"] = {"program": gaps}
    if control:
        for variant in ("float8", "half_batch") + (
                ("dropped_shards",) if mix.get("zero1") else ()):
            other = check.reference_train(model, w0, list(first), mix["lr"],
                                          variant=variant, devices=devices)
            out["readings"][variant] = check.compare_train(other, refr)
    return out


def per_layer(cell, run, trace_dir, device: dict) -> tuple[dict, dict]:
    """The cell's per-layer metrics from the trace, and the breakdown."""
    from harness import spec, trace
    from harness.chip import peaks

    tr = trace.load(trace_dir)
    t0, t1 = tr.window(run.span)
    run.trace, run.t0, run.t1 = tr, t0, t1
    run.peaks = peaks(device["kind"]) if device["platform"] == "tpu" \
        else None
    spans = [s for s in tr.spans if s.name == run.span]
    for rec, s in zip(run.records, spans, strict=False):
        rec.span = s
    metrics = {}
    for m in cell.per_layer:
        v = spec.metric_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device["busy_s"] = trace.busy_ns(tr, t0, t1) / 1e9
    device["window_s"] = (t1 - t0) / 1e9
    breakdown = {"device_ops": trace.op_totals(tr, t0, t1),
                 "idle_gaps": trace.idle_gaps(tr, t0, t1)}
    return metrics, breakdown


def run_cell(cell, seed: int, seconds: float, traced: bool, device: dict,
             clock=time.perf_counter, t_start: float = T_START,
             compiles: CompileCounter | None = None) -> dict:
    """One run of ``cell``; returns the result object."""
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced \
        else None
    try:
        driver = train_cell if cell.traffic["kind"] == "train" else serve_cell
        out = driver(cell, seed, seconds, trace_dir, clock, t_start,
                     device["count"])
        dev = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
        breakdown = None
        if traced:
            metrics, breakdown = per_layer(cell, out["run"], trace_dir, dev)
        else:
            metrics = {}
            for m in cell.end_to_end:
                metrics[m["name"]] = {"value": out["metrics"][m["name"]],
                                      "unit": m["unit"]}
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    if compiles is not None:
        log(f"backend compiles: {len(compiles.times)} in the process, "
            f"{compiles.between(*out['window'])} in the window")
    checks = out["checks"]
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    result = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import chip, spec

    cell = spec.load_cell(args.workload)
    log(f"compile cache: {enable_compile_cache()}")
    device = chip.require_chip(cell.chips)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), device,
                      compiles=CompileCounter())
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
