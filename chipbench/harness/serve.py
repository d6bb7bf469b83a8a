"""The serving driver: the program's ``DecodeEngine`` under an open or a
closed loop of requests, timed on the host clock.

One thread offers the load and steps the engine, so the engine sees a
request at the first step boundary after it is due; how late that was is
reported.  Tails are taken over every sample of the window:

* ``ttft_p90_ms``: from a request's due time to the step that delivered
  its first token, over all requests due in the window.  The loop keeps
  running after the window closes, with the load still offered, until each
  of those has its first token (or the drain limit passes, and it counts as
  failed).
* ``itl_p99_ms``: gaps between consecutive deliveries of one request whose
  later delivery falls in the window.  A fused sync delivers several tokens
  at once: the first gap is the time since the previous delivery, the rest
  are 0.
* ``serve_tok_s``: prompt tokens processed (chunked prefill or forced
  decode) plus tokens generated, by steps inside the window (a step across
  an edge counts pro rata), over the window.
"""
from __future__ import annotations

import collections
import contextlib
import sys
import time
from dataclasses import dataclass, field

import jax
import numpy as np

from harness import quiet, traffic
from harness.stats import percentile

SPAN = "chipbench.engine_step"


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


@dataclass
class Track:
    item: traffic.Item
    req: object
    due: float                       # absolute host time
    submitted: float
    first: float | None = None
    last: float | None = None
    seen: int = 0
    work: int = 0                    # prompt tokens processed + generated
    finished: float | None = None


@dataclass
class StepRecord:
    t0: float
    t1: float
    decode: list = field(default_factory=list)   # [(start_pos, n_steps)]


def _prompt_done(eng, slot: int) -> int:
    if eng.pf_done[slot] < eng.pf_target[slot]:
        return int(eng.pf_done[slot])
    return int(min(eng.pos[slot], eng.plen[slot]))


class Loop:
    """Offers ``items`` to ``eng`` and records what each step delivered."""

    def __init__(self, eng, mix: dict, items, seconds: float, clock,
                 trace_seconds: float = 0.0, trace_dir: str | None = None):
        self.eng, self.mix, self.clock = eng, mix, clock
        self.closed = mix["kind"] == "closed_loop"
        self.items = collections.deque(items)
        self.seconds = seconds
        self.trace_seconds, self.trace_dir = trace_seconds, trace_dir
        self.tracks: list[Track] = []
        self.live: dict[int, Track] = {}
        self.slot_of: dict[int, int] = {}
        self.steps: list[StepRecord] = []
        self.traced: list[StepRecord] = []
        self.itl: list[float] = []
        self.window_work = 0.0
        self.lateness: list[float] = []

    # -- load --------------------------------------------------------------
    def _submit(self, item, due: float, now: float) -> None:
        from repro.serve.engine import Request

        req = Request(prompt=item.prompt, max_new_tokens=item.max_new_tokens)
        self.eng.submit(req)
        tr = Track(item, req, due, now)
        self.tracks.append(tr)
        self.live[id(req)] = tr
        self.lateness.append(now - due)

    def _offer(self, now: float) -> None:
        if self.closed:
            return
        while self.items and self.t_sched + self.items[0].due <= now:
            it = self.items.popleft()
            self._submit(it, self.t_sched + it.due, now)

    def _busy(self) -> bool:
        return bool(self.eng.queue) or any(
            r is not None for r in self.eng.slot_req)

    # -- one step ----------------------------------------------------------
    def _step(self) -> StepRecord:
        eng = self.eng
        pos0, live0 = eng.pos.copy(), eng.live.copy()
        before = {id(r): s for s, r in enumerate(eng.slot_req) if r is not None}
        t0 = self.clock()
        with (jax.profiler.TraceAnnotation(SPAN) if self.tracing
              else contextlib.nullcontext()):
            eng.step()
        t1 = self.clock()
        rec = StepRecord(t0, t1)
        after = {id(r): s for s, r in enumerate(eng.slot_req) if r is not None}
        for rid, tr in list(self.live.items()):
            slot = after.get(rid, before.get(rid))
            if slot is not None:
                started = live0[slot] and rid in before
                begin = int(pos0[slot]) if started else int(eng.pf_target[slot])
                decoding = started or eng.live[slot] or tr.req.done
                if decoding and eng.pos[slot] > begin:
                    rec.decode.append((begin, int(eng.pos[slot]) - begin))
            self._observe(tr, slot, rec)
        return rec

    def _observe(self, tr: Track, slot, rec: StepRecord) -> None:
        eng, req, t = self.eng, tr.req, rec.t1
        n = len(req.output)
        if req.done or slot is None:
            prompt = len(tr.item.prompt) if req.done and not req.failed else 0
        else:
            prompt = _prompt_done(eng, slot)
        work = prompt + n
        self._count(work - tr.work, rec)
        tr.work = work
        if n > tr.seen:
            if tr.first is None:
                tr.first = t
            elif self.w0 <= t < self.w1:
                self.itl.append(t - tr.last)
            if self.w0 <= t < self.w1:
                self.itl += [0.0] * (n - tr.seen - 1)
            tr.last, tr.seen = t, n
        if req.done:
            tr.finished = t
            del self.live[id(req)]
            if self.closed:
                self._next_for_client(t)

    def _count(self, delta: int, rec: StepRecord) -> None:
        if delta <= 0:
            return
        a, b = rec.t0, rec.t1
        inside = max(0.0, min(b, self.w1) - max(a, self.w0))
        self.window_work += delta * (inside / (b - a) if b > a else
                                     float(self.w0 <= b < self.w1))

    def _next_for_client(self, now: float) -> None:
        if self.items:
            self._submit(self.items.popleft(), now, now)

    # -- the run -----------------------------------------------------------
    def run(self, preroll: float) -> None:
        with quiet.no_collection():
            self._run(preroll)

    def _run(self, preroll: float) -> None:
        self.tracing = False
        self.t_sched = self.clock()
        self.w0 = self.t_sched + preroll
        self.w1 = self.w0 + self.seconds
        if self.closed:
            for _ in range(self.mix["clients"]):
                self._next_for_client(self.t_sched)
        drain_end = self.w1 + self.mix["drain_limit_s"]
        trace_end = None
        while True:
            now = self.clock()
            if self.trace_dir and trace_end is None and now >= self.w0:
                jax.profiler.start_trace(self.trace_dir)
                self.tracing, trace_end = True, self.clock() + self.trace_seconds
            if self.tracing and now >= trace_end:
                jax.profiler.stop_trace()
                self.tracing = False
            self._offer(now)
            if now >= self.w1 and (now >= drain_end or all(
                    t.first is not None or t.req.failed
                    for t in self.tracks if self.w0 <= t.due < self.w1)):
                break
            if not self._busy():
                if self.closed or not self.items:
                    break
                time.sleep(max(0.0, min(self.t_sched + self.items[0].due,
                                        self.w1) - now))
                continue
            rec = self._step()
            self.steps.append(rec)
            if self.tracing:
                self.traced.append(rec)
        if self.tracing:
            jax.profiler.stop_trace()
            self.tracing = False

    # -- results -----------------------------------------------------------
    def results(self) -> dict:
        due = [t for t in self.tracks if self.w0 <= t.due < self.w1]
        failed = [t for t in due if t.req.failed or t.first is None]
        ttft = [t.first - t.due for t in due if t.first is not None]
        late = sorted(self.lateness)
        log(f"load generator lateness over {len(late)} submissions: "
            f"median {1e3 * percentile(late, 50):.3f} ms, "
            f"p99 {1e3 * percentile(late, 99):.3f} ms, "
            f"max {1e3 * late[-1]:.3f} ms")
        log(f"time to first token (ms) of the {len(ttft)} requests due in "
            f"the window: {sorted(round(1e3 * x, 1) for x in ttft)}")
        log(f"window: {len(due)} requests due, {len(failed)} failed, "
            f"{len(ttft)} first tokens, {len(self.itl)} gaps, "
            f"{sum(1 for s in self.steps if self.w0 <= s.t1 < self.w1)} "
            f"steps, engine stats {self.eng.stats}")
        if not ttft or not self.itl:
            raise RuntimeError("the window saw no first token or no gap")
        return {
            "attempted": len(due), "failed": len(failed),
            "metrics": {
                "serve_tok_s": self.window_work / self.seconds,
                "ttft_p50_ms": 1e3 * percentile(ttft, 50),
                "ttft_p90_ms": 1e3 * percentile(ttft, 90),
                "itl_p99_ms": 1e3 * percentile(self.itl, 99),
            },
        }

    def finished(self) -> list[Track]:
        return [t for t in self.tracks if t.req.done and not t.req.failed]


def warm_up(eng, mix: dict, vocab: int, seed: int) -> None:
    """Drive every program the window will use once: a prompt long enough
    for a prefill chunk and forced decode, and enough output for a sync."""
    from repro.serve.engine import Request

    e = mix["engine"]
    rng = traffic.rng_for(seed, 2)
    plen = (e["prefill_chunk"] or 1) + 2
    req = Request(prompt=rng.integers(0, vocab, plen, dtype=np.int32),
                  max_new_tokens=2 * e["steps_per_sync"])
    eng.submit(req)
    eng.run_until_drained()
    if not req.done or req.failed:
        raise RuntimeError("the warm-up request did not complete")


def sample_finished(tracks: list[Track], k: int, seed: int) -> list[Track]:
    """The longest finished request and ``k - 1`` others drawn from the
    seed."""
    size = lambda t: len(t.item.prompt) + len(t.req.output)
    longest = max(tracks, key=size)
    rest = [t for t in tracks if t is not longest]
    rng = traffic.rng_for(seed, 3)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]
