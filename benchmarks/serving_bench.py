"""Open-loop serving benchmark: continuous batching vs the fixed-slot
baseline — the CI gate for the serving-engine rebuild.

An open-loop load generator (arrivals don't wait for completions —
Poisson by default, a bursty built-in or a replayed JSON trace
otherwise; arrival times are in ENGINE TICKS, the one time unit both
schedules share) drives two ``ServingEngine`` instances over the
*same* seeded request trace and arrival schedule:
``scheduling="continuous"``
(per-tick admit/evict + fused chunked prefill) and ``scheduling="fixed"``
(batch-synchronous admission, prompts token-by-token through decode —
the engine this repo shipped before the rebuild).  Both engines are
warmed up first (every pow2 fused-chunk width bucket) so compile time
never lands in the measured window.

Measured per policy, from the engine's own metrics registry:

- **goodput** — tokens/s of SLO-meeting requests (time-to-first-token
  within ``--slo-ticks`` engine ticks of submission) over measured
  serving wall-clock; also raw tokens/s and total engine ticks;
- **time to first token** — p50/p99 wall seconds from submission to
  the first token on the host (``serve.ttft_s``), and p99 of the wait
  from submission to admission (``serve.queue_wait_s``);
- **slot occupancy** — mean/p50 of the per-tick occupied-slot fraction,
  plus mean time-to-first-token in ticks.

A separate verified phase (trust on, audit_rate=1.0) checks the trust
contract of the rebuild on a smaller trace: per-request verdict maps
must be EQUAL across schedules — every honest request finalizes in
both, tampering the same request post-serve revokes it in both — and
reports the batched-commitment amortization (Merkle appends per tick
vs per-stream leaves).

Writes ``BENCH_serving.json`` and exits non-zero (the CI gate) if
continuous goodput does not beat fixed by ``--min-speedup``, if
time-to-first-token percentiles are missing, if the two schedules' token streams
differ, or if the verdict maps diverge.

Env: ``REPRO_BENCH_SERVE_REQUESTS`` overrides the measured request
count (default 32; hundreds work — the generator is open-loop).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

from benchmarks.common import row
from repro.configs import get_config
from repro.obs import Observability
from repro.serve.engine import ServingEngine
from repro.train.loop import init_model
from repro.trust.protocol import TrustConfig

ARCH = "smollm-360m"
MAX_DRIVER_STEPS = 200_000


# ------------------------------------------------------------ workload
def make_requests(num, vocab, *, max_prompt, max_new, seed, id_base=0):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(num):
        plen = int(rng.integers(4, max_prompt))
        out.append({"id": id_base + i,
                    "prompt": rng.integers(0, vocab, size=plen)
                    .astype(np.int32),
                    "max_new_tokens": int(rng.integers(1, max_new))})
    return out


def arrival_schedule(kind, num, rate, seed, trace_path=None):
    """Request index -> arrival time in ENGINE TICKS.  Ticks are the
    one time unit both schedules share (a fixed-slot step is one tick,
    a fused continuous step is C ticks), so the same schedule applies
    the same load to both.  Open loop: the schedule is fixed up front,
    arrivals never wait for completions."""
    if kind == "trace":
        with open(trace_path) as f:
            steps = [int(e["at_tick"]) for e in json.load(f)][:num]
        if len(steps) < num:
            raise SystemExit(f"trace has {len(steps)} arrivals, need {num}")
        return steps
    if kind == "bursty":
        # deterministic closed-form burst train: 1/4 of the load at once
        # every burst/rate ticks — stresses queue drain + admission
        burst = max(num // 4, 1)
        gap = max(int(burst / max(rate, 1e-9)), 1)
        return [(i // burst) * gap for i in range(num)]
    rng = np.random.default_rng(seed + 101)
    gaps = rng.exponential(1.0 / max(rate, 1e-9), size=num)
    return np.floor(np.cumsum(gaps)).astype(int).tolist()


# -------------------------------------------------------------- driver
def drive(eng, schedule, requests, *, stop_at_done=False):
    """Open-loop drive: submit each arrival once the engine clock
    reaches its tick, step the engine, and fast-forward the clock over
    idle gaps (an idle engine waiting for the next arrival models idle
    wall time, not compute).  Returns macro-steps consumed."""
    order = sorted(range(len(requests)), key=lambda i: schedule[i])
    k = 0
    for i in range(MAX_DRIVER_STEPS):
        batch = []
        while k < len(order) and schedule[order[k]] <= eng.tick:
            batch.append(requests[order[k]])
            k += 1
        if batch:
            eng.submit(batch)
        busy = eng.step()
        draining = (k < len(order) or eng.sched.any_active
                    or eng.sched.depth())
        if not draining and (stop_at_done or not busy):
            return i + 1
        if not busy and not eng.sched.any_active and k < len(order) \
                and not eng.sched.depth():
            eng.tick = max(eng.tick, int(schedule[order[k]]))
    raise RuntimeError("driver did not converge")


def warmup(eng):
    """Compile every fused-step width bucket before the measured window
    (``ServingEngine.warmup``), then reset the engine's metrics so
    compiles never count."""
    eng.warmup()
    eng.obs = Observability()          # fresh registry: measured-only


def measure(policy, cfg, params, requests, schedule, args):
    eng = ServingEngine(cfg, params, batch_slots=args.slots,
                        cache_len=args.cache_len, scheduling=policy,
                        prefill_chunk=args.prefill_chunk)
    warmup(eng)
    base_done = dict(eng._done)
    base_steps = eng.steps
    steps = drive(eng, schedule, requests)
    rep = eng.obs_report()
    done = {rid: toks for rid, toks in eng._done.items()
            if rid not in base_done}
    meta = eng.request_meta
    ttft = {r["id"]: meta[r["id"]]["first_token_tick"]
            - meta[r["id"]]["submitted_tick"]
            for r in requests if meta[r["id"]]["first_token_tick"] >= 0}
    slo_ok = [rid for rid, t in ttft.items() if t <= args.slo_ticks]
    wall = rep["tick_s"]
    tokens = sum(len(v) for v in done.values())
    good_tokens = sum(len(done[rid]) for rid in slo_ok if rid in done)
    lat = rep["ttft"]
    return {
        "policy": policy,
        "driver_steps": steps,
        "engine_ticks": rep["ticks"],
        "compiled_dispatches": eng.steps - base_steps,
        "wall_s": wall,
        "tokens": tokens,
        "throughput_tok_s": tokens / max(wall, 1e-9),
        "goodput_tok_s": good_tokens / max(wall, 1e-9),
        "slo_met_requests": len(slo_ok),
        "requests": len(done),
        "ttft_p50_s": lat["p50"],
        "ttft_p99_s": lat["p99"],
        "queue_wait_p99_s": rep["queue_wait"]["p99"],
        "ttft_ticks_mean": float(np.mean(list(ttft.values()))) if ttft
        else 0.0,
        "occupancy_mean": rep["occupancy"]["mean"],
        "prefill_s": rep["prefill_s"],
        "decode_s": rep["decode_s"],
    }, done


# ----------------------------------------------------- verified phase
def verdict_run(policy, cfg, params, requests, schedule, args,
                tamper_rid=None):
    trust = TrustConfig(audit_rate=1.0, num_verifiers=1,
                        challenge_window=args.challenge_window)
    eng = ServingEngine(cfg, params, batch_slots=args.slots,
                        cache_len=args.cache_len, scheduling=policy,
                        prefill_chunk=args.prefill_chunk, trust=trust)
    drive(eng, schedule, requests, stop_at_done=True)
    if tamper_rid is not None:
        rec = eng.records[tamper_rid]
        rec.tokens = [t ^ 1 for t in rec.tokens]
    done = eng.run()
    verdicts = {rid: ("revoked" if eng.records[rid].revoked
                      else "finalized" if rid in done else "open")
                for rid in sorted(eng.records)}
    rep = eng.obs_report()
    return verdicts, rep["commit_appends"], rep["commit_leaves"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=int(os.environ.get(
        "REPRO_BENCH_SERVE_REQUESTS", "32")))
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--cache-len", type=int, default=96)
    ap.add_argument("--max-prompt", type=int, default=48)
    ap.add_argument("--max-new", type=int, default=10)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--arrivals", choices=("poisson", "bursty", "trace"),
                    default="poisson")
    ap.add_argument("--trace",
                    help="JSON [{'at_tick': int}, ...] replay")
    ap.add_argument("--rate", type=float, default=0.25,
                    help="mean arrivals per engine tick (open loop)")
    ap.add_argument("--slo-ticks", type=int, default=120,
                    help="TTFT SLO in engine ticks for goodput")
    ap.add_argument("--challenge-window", type=int, default=400)
    ap.add_argument("--min-speedup", type=float, default=1.0,
                    help="required continuous/fixed goodput ratio")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default="BENCH_serving.json")
    args = ap.parse_args()

    cfg = get_config(ARCH, smoke=True)
    params = init_model(cfg, seed=args.seed)
    requests = make_requests(args.requests, cfg.vocab_size,
                             max_prompt=args.max_prompt,
                             max_new=args.max_new, seed=args.seed)
    schedule = arrival_schedule(args.arrivals, args.requests, args.rate,
                                args.seed, args.trace)

    results, outputs = {}, {}
    for policy in ("continuous", "fixed"):
        results[policy], outputs[policy] = measure(
            policy, cfg, params, requests, schedule, args)
        r = results[policy]
        row(f"serve.{policy}", 1e6 * r["wall_s"] / max(r["tokens"], 1),
            f"goodput={r['goodput_tok_s']:.1f}tok/s "
            f"ttft_p99={r['ttft_p99_s'] * 1e3:.2f}ms "
            f"occ={r['occupancy_mean']:.2f}")

    # trust contract: same verdict map under both schedules, honest and
    # tampered, on a smaller verified trace
    vreqs = make_requests(min(args.requests, 8), cfg.vocab_size,
                          max_prompt=24, max_new=6, seed=args.seed + 7,
                          id_base=10_000)
    vsched = arrival_schedule("poisson", len(vreqs), args.rate,
                              args.seed + 7)
    honest, appends, leaves = {}, 0, 0
    tampered = {}
    tamper_rid = vreqs[len(vreqs) // 2]["id"]
    for policy in ("continuous", "fixed"):
        honest[policy], a, l = verdict_run(policy, cfg, params, vreqs,
                                           vsched, args)
        if policy == "continuous":
            appends, leaves = a, l
        tampered[policy], _, _ = verdict_run(policy, cfg, params, vreqs,
                                             vsched, args,
                                             tamper_rid=tamper_rid)

    speedup = results["continuous"]["goodput_tok_s"] \
        / max(results["fixed"]["goodput_tok_s"], 1e-9)
    # per-request verdict contract: honest maps EQUAL across schedules;
    # under tamper the altered session is revoked in both.  The full
    # tampered maps are reported but not compared — dependent-revocation
    # blast radius follows tick overlap, which schedules differently by
    # design (continuous co-batches across admissions).
    verdicts_equal = honest["continuous"] == honest["fixed"]
    streams_equal = outputs["continuous"] == outputs["fixed"]
    all_finalized = all(v == "finalized"
                        for v in honest["continuous"].values())
    tamper_caught = (tampered["continuous"].get(tamper_rid) == "revoked")

    out = {
        "workload": {"arch": ARCH, "requests": args.requests,
                     "slots": args.slots, "cache_len": args.cache_len,
                     "max_prompt": args.max_prompt,
                     "max_new": args.max_new,
                     "prefill_chunk": args.prefill_chunk,
                     "arrivals": args.arrivals, "rate": args.rate,
                     "slo_ticks": args.slo_ticks, "seed": args.seed},
        "continuous": results["continuous"],
        "fixed": results["fixed"],
        "goodput_speedup": speedup,
        # one fused macro-step covers C engine ticks, so continuous makes
        # far fewer compiled dispatches for the same served tokens
        "dispatch_reduction": 1.0
        - results["continuous"]["compiled_dispatches"]
        / max(results["fixed"]["compiled_dispatches"], 1),
        "streams_equal": streams_equal,
        "trust": {
            "verdicts_equal": verdicts_equal,
            "honest_all_finalized": all_finalized,
            "tamper_caught_both": tamper_caught
            and tampered["fixed"].get(tamper_rid) == "revoked",
            "commit_appends": appends,
            "commit_leaves": leaves,
            "amortization": leaves / max(appends, 1),
        },
    }
    with open(args.json, "w") as f:
        json.dump(out, f, indent=2)

    row("serve.speedup", 0.0, f"goodput_speedup={speedup:.2f} "
        f"dispatch_reduction={out['dispatch_reduction']:.2f}")
    failures = []
    if speedup < args.min_speedup:
        failures.append(f"goodput speedup {speedup:.3f} < "
                        f"{args.min_speedup} (continuous vs fixed)")
    for policy in ("continuous", "fixed"):
        if results[policy]["ttft_p99_s"] <= 0:
            failures.append(f"{policy}: missing time-to-first-token "
                            "percentiles")
    if not streams_equal:
        failures.append("token streams differ across schedules")
    if not verdicts_equal:
        failures.append(f"honest verdict maps diverge: {honest}")
    if not all_finalized:
        failures.append(f"honest requests did not finalize: "
                        f"{honest['continuous']}")
    if not out["trust"]["tamper_caught_both"]:
        failures.append("tampered session not revoked in both schedules")
    if failures:
        for msg in failures:
            print(f"[serving-bench] GATE FAILED: {msg}", file=sys.stderr)
        return 1
    print(f"[serving-bench] ok: goodput {speedup:.2f}x, "
          f"{out['dispatch_reduction']:.0%} fewer dispatches, "
          f"amortization {out['trust']['amortization']:.1f} "
          f"leaves/append -> {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
