"""Drives a ``ServingEngine`` on the wall clock and records what a
client would see.

The engine is stepped from one thread.  Between macro-steps the driver
submits what is due, and after each step it stamps, on the host clock:

- per request: when it was due, submitted, admitted (the start of the
  macro-step that admitted it), when its first and its last token
  reached the host, and how many tokens it has received;
- per macro-step: its start and end, its engine ticks, whether it
  carried prompt tokens, and the runs of cache positions each slot
  processed in it (what ``costs.py`` turns into operations and bytes).

Two loops: ``open_loop`` sends each request when it is due whether or
not earlier ones have finished; ``backlog`` keeps at least ``depth``
requests waiting.  Each runs for a fixed number of seconds and returns
the window's bounds.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import clock


def _annotate(name: str):
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


class TraceWindow:
    """Starts the JAX profiler at a step boundary once ``start`` seconds
    of the window have passed and stops it ``length`` seconds later, so
    the trace holds whole macro-steps."""

    def __init__(self, logdir: str, start: float, length: float):
        self.logdir, self.start, self.length = logdir, start, length
        self.on = False
        self.done = False
        self.first_step = self.last_step = -1
        self.t0 = self.t1 = 0.0
        self._ann = None

    def poll(self, elapsed: float, n_steps: int) -> None:
        import jax
        if not self.on and not self.done and elapsed >= self.start:
            jax.profiler.start_trace(self.logdir)
            self._ann = _annotate("chipbench.traced")
            self._ann.__enter__()
            self.on, self.first_step, self.t0 = True, n_steps, clock.now()
        elif self.on and elapsed >= self.start + self.length:
            self.close(n_steps)

    def close(self, n_steps: int) -> None:
        if self.on:
            import jax
            self._ann.__exit__(None, None, None)
            self.t1 = clock.now()
            jax.profiler.stop_trace()
            self.on, self.done, self.last_step = False, True, n_steps


class Driver:
    def __init__(self, eng, counters=("serve.prefill_s", "serve.decode_s",
                                      "serve.commit_s", "serve.audit_s")):
        self.eng = eng
        self.counter_names = counters
        self.records: Dict[int, Dict] = {}
        self.inflight: set = set()
        self.steps: List[Dict] = []

    # ------------------------------------------------------------ intake
    def submit(self, reqs: List[Dict], due: Optional[List[float]] = None):
        t = clock.now()
        with _annotate("submit"):
            for i, r in enumerate(reqs):
                self.records[r["id"]] = {
                    "id": r["id"], "due": t if due is None else due[i],
                    "submit": t, "admit": None, "first": None,
                    "last": None, "n": 0, "done": False,
                    "prompt": r["prompt"], "prompt_len": len(r["prompt"]),
                    "max_new": int(r["max_new_tokens"])}
                self.inflight.add(r["id"])
            self.eng.submit(reqs)

    def counters(self) -> Dict[str, float]:
        m = self.eng.obs.metrics
        return {k: float(m.value(k)) for k in self.counter_names}

    def occupancy(self):
        h = self.eng.obs.metrics.histogram("serve.occupancy")
        return h.sum, h.count

    # -------------------------------------------------------------- step
    def step(self) -> bool:
        eng = self.eng
        before = [(s.request_id, s.pos) for s in eng.slots]
        prefill0 = float(eng.obs.metrics.value("serve.prefill_s"))
        tick0, model0 = eng.tick, eng.steps
        t0 = clock.now()
        with _annotate("engine.step"):
            busy = eng.step()
        t1 = clock.now()
        runs = []
        for (prid, ppos), s in zip(before, eng.slots):
            if s.active:
                base = ppos if prid == s.request_id else 0
                if s.pos > base:
                    runs.append((base, s.pos))
            elif prid >= 0 and s.pos > ppos:     # finished in this step
                runs.append((ppos, s.pos))
        by_rid = {s.request_id: s for s in eng.slots if s.active}
        meta = eng.request_meta
        for rid in list(self.inflight):
            rec, m = self.records[rid], meta[rid]
            if rec["admit"] is None and m["admitted_tick"] >= 0:
                rec["admit"] = t0
            if rec["first"] is None and m["first_token_tick"] >= 0:
                rec["first"] = t1
            if m["finished_tick"] >= 0:
                n, rec["done"], rec["end"] = rec["max_new"], True, t1
                self.inflight.discard(rid)
            elif rid in by_rid:
                s = by_rid[rid]
                n = min(len(s.generated), s.to_generate)
            else:
                n = rec["n"]
            if n > rec["n"]:
                rec["n"], rec["last"] = n, t1
        self.steps.append({
            "t0": t0, "t1": t1, "ticks": eng.tick - tick0,
            "model": eng.steps > model0,
            "prefill": float(eng.obs.metrics.value("serve.prefill_s"))
            > prefill0,
            "runs": runs})
        return busy

    def idle(self) -> bool:
        return not self.eng.sched.any_active and not self.eng.sched.depth()

    # ------------------------------------------------------------- loops
    def open_loop(self, schedule: List[Dict], seconds: float,
                  trace: Optional[TraceWindow] = None):
        """Send each request of ``schedule`` at its ``due`` offset from the
        window's start; step the engine whenever it has work."""
        start = clock.now()
        end = start + seconds
        k = 0
        while True:
            now = clock.now()
            if trace is not None:
                trace.poll(now - start, len(self.steps))
            if now >= end:
                break
            j = k
            while j < len(schedule) and start + schedule[j]["due"] <= now:
                j += 1
            if j > k:
                self.submit(schedule[k:j],
                            [start + r["due"] for r in schedule[k:j]])
                k = j
            if not self.idle():
                self.step()
            else:
                nxt = (start + schedule[k]["due"] if k < len(schedule)
                       else end)
                with _annotate("driver.wait"):
                    clock.sleep_until(min(nxt, end))
        if trace is not None:
            trace.close(len(self.steps))
        return start, clock.now()

    def backlog(self, source, depth: int, seconds: float,
                trace: Optional[TraceWindow] = None):
        """Keep at least ``depth`` requests queued after every admission;
        ``source.take(n)`` hands out the next ``n`` requests."""
        start = clock.now()
        end = start + seconds
        while True:
            now = clock.now()
            if trace is not None:
                trace.poll(now - start, len(self.steps))
            if now >= end:
                break
            free = sum(1 for s in self.eng.slots if not s.active)
            need = depth + free - self.eng.sched.depth()
            if need > 0:
                self.submit(source.take(need))
            self.step()
        if trace is not None:
            trace.close(len(self.steps))
        return start, clock.now()
