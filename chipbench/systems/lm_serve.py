"""A language model served by ``repro.serve.engine.ServingEngine``.

Set-up makes the weights from the seed, builds the engine at the
configuration's slots and cache, compiles its step for every chunk
width, and serves one short request end to end so that admission,
the cache reset, commitments and audits have all run once.  The window
then drives the engine with the mix's traffic (``serve_driver.py``).

After the window: the peak device memory is read; with verified
sessions, no honest session may have been revoked, and a stream altered
after the fact must be revoked by its audits; the engine is freed; and
a sample of the finished requests, the longest among them, is compared
with the plain reference (``lm_reference.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import sys
from types import SimpleNamespace
from typing import Dict, List

import numpy as np

import clock
import lm_weights
from serve_driver import Driver, TraceWindow
from traffic import Backlog, open_loop_schedule, rng_for

WARM_ID = 2_000_000_000

# the configuration file's keys, as the model's published config.json
# names them, and the names the benchmark's modules and the program's
# ModelConfig give the same sizes
SOURCE_KEYS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
               "num_attention_heads": "num_heads",
               "num_key_value_heads": "num_kv_heads",
               "intermediate_size": "moe_d_ff",
               "num_local_experts": "num_experts",
               "num_experts_per_tok": "num_experts_per_tok",
               "vocab_size": "vocab_size", "rope_theta": "rope_theta",
               "rms_norm_eps": "norm_eps",
               "tie_word_embeddings": "tie_embeddings"}

# keys of the resolved configuration that are the program's ModelConfig
# fields of the same name
MODEL_KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads",
              "head_dim", "d_ff", "moe_d_ff", "vocab_size", "num_experts",
              "num_experts_per_tok", "num_shared_experts", "rope_theta",
              "norm_eps", "tie_embeddings")


def resolve(c: Dict) -> Dict:
    """The configuration file in the names the benchmark reads: every
    published key translated, the head size as the source derives it
    (hidden size over heads), no shared expert and no dense MLP beside
    the experts.  A published key the benchmark cannot serve as stated
    is refused."""
    if c.get("hidden_act", "silu") != "silu" or c.get("sliding_window"):
        raise ValueError("the reference covers SwiGLU experts and full "
                         "attention only")
    out = {k: v for k, v in c.items() if k not in SOURCE_KEYS}
    out.update({SOURCE_KEYS[k]: c[k] for k in SOURCE_KEYS})
    out.setdefault("head_dim", out["d_model"] // out["num_heads"])
    out.update(num_shared_experts=0, d_ff=out["moe_d_ff"])
    return out


def program_config(c: Dict):
    """The program's ``ModelConfig`` for configuration file ``c``: the
    named preset with every size the file gives, checked field by
    field, so the file is what runs."""
    from repro.configs import get_config
    base = get_config(c["program_config"])
    cfg = dataclasses.replace(
        base, **{k: c[k] for k in MODEL_KEYS if k in c},
        num_blocks=c["num_layers"] // len(base.block_pattern)).validate()
    for k in MODEL_KEYS:
        if k in c and getattr(cfg, k) != c[k]:
            raise ValueError(f"{k}: program has {getattr(cfg, k)!r}, "
                             f"configuration file {c[k]!r}")
    if any(s.kind != "attn" or s.mlp != "moe"
           for s in cfg.block_pattern + cfg.remainder):
        raise ValueError("the reference covers attention + MoE layers only")
    if cfg.padded_vocab != cfg.vocab_size or cfg.kv_cache_dtype != "default":
        raise ValueError("padded vocabulary or quantized cache: not covered "
                         "by the reference")
    if cfg.qkv_bias or cfg.qk_norm or cfg.attn_logit_softcap:
        raise ValueError("projection biases, query-key norms or a logit "
                         "cap: not covered by the reference")
    return cfg


def _trust(mix: Dict, seed: int):
    if not mix.get("trust"):
        return None
    from repro.trust.protocol import TrustConfig
    return TrustConfig(seed=seed % (2**31), **mix["trust"])


def setup(c: Dict, mix: Dict, seed: int):
    """Weights, engine, compiled steps, one warm request.  Returns
    (params, engine)."""
    import jax
    from repro.serve.engine import ServingEngine
    marks = [("start", clock.since_start())]
    cfg = program_config(c)
    params = jax.block_until_ready(lm_weights.make(c, seed))
    marks.append(("weights", clock.since_start()))
    sv = c["serving"]
    eng = ServingEngine(cfg, params, batch_slots=sv["slots"],
                        cache_len=sv["cache_len"],
                        prefill_chunk=sv["prefill_chunk"],
                        trust=_trust(mix, seed))
    marks.append(("engine", clock.since_start()))
    eng.warmup()
    marks.append(("warmup", clock.since_start()))
    warm = rng_for(seed, 9).integers(0, c["vocab_size"], 2 * sv["prefill_chunk"])
    eng.submit([{"id": WARM_ID, "prompt": warm.astype(np.int32),
                 "max_new_tokens": 8}])
    eng.run()
    marks.append(("warm request", clock.since_start()))
    print("chipbench: set-up " + ", ".join(
        f"{name} at {t:.2f} s" for name, t in marks), file=sys.stderr)
    return params, eng


def drive(eng, c: Dict, mix: Dict, seed: int, seconds: float,
          trace: TraceWindow = None) -> SimpleNamespace:
    """Run the window.  Returns what the metric readers read."""
    d = Driver(eng)
    cnt0, occ0 = d.counters(), d.occupancy()
    if mix["driver"] == "open_loop":
        sched = open_loop_schedule(mix, seconds, c["vocab_size"], seed)
        t0, t1 = d.open_loop(sched, seconds, trace)
    elif mix["driver"] == "backlog":
        t0, t1 = d.backlog(Backlog(mix, c["vocab_size"], seed),
                           mix["backlog"], seconds, trace)
    else:
        raise ValueError(f"unknown driver {mix['driver']!r}")
    cnt1, occ1 = d.counters(), d.occupancy()
    return SimpleNamespace(
        driver=d, t0=t0, t1=t1, seconds=t1 - t0,
        records=[r for r in d.records.values() if r["due"] < t1],
        steps=[s for s in d.steps if s["t0"] >= t0],
        counters={k: cnt1[k] - cnt0[k] for k in cnt0},
        occupancy=(occ1[0] - occ0[0], occ1[1] - occ0[1]),
        delivered=sum(r["n"] for r in d.records.values()))


def sample(eng, win: SimpleNamespace, mix: Dict, seed: int) -> List[Dict]:
    """Finished requests to compare: the longest (prompt and output) and
    then others in an order drawn from the seed, until the sample holds
    ``check.sample_tokens`` served tokens."""
    done = [r for r in win.records if r["done"] and r["end"] <= win.t1]
    if not done:
        return []
    verified = eng.trust is not None
    served = (lambda rid: list(eng.records[rid].tokens)) if verified \
        else (lambda rid: list(eng.completed[rid]))
    longest = max(done, key=lambda r: r["prompt_len"] + r["max_new"])
    order = [longest] + [done[i] for i in rng_for(seed, 5).permutation(
        len(done)) if done[i] is not longest]
    out, total = [], 0
    for r in order:
        toks = served(r["id"])
        out.append({"id": r["id"], "prompt": r["prompt"], "served": toks})
        total += len(toks)
        if total >= mix["check"]["sample_tokens"]:
            break
    return out


def trust_checks(eng, win: SimpleNamespace, seed: int) -> Dict[str, Dict]:
    """Verified sessions: honest streams are never revoked; a finished
    stream altered after its commitment is revoked by its audits."""
    if eng.trust is None:
        return {}
    revoked = sum(1 for rid, r in eng.records.items() if r.revoked)
    done = [r["id"] for r in win.records
            if r["done"] and eng.records[r["id"]].root]
    caught = 0
    if done:
        rid = done[int(rng_for(seed, 6).integers(len(done)))]
        rec = eng.records[rid]
        rec.tokens = [t ^ 1 for t in rec.tokens]
        for v in range(eng.trust.num_verifiers):
            eng.audit_session(rid, v)
        caught = int(rec.revoked)
    return {"honest_revoked": {"value": revoked, "limit": 0, "le": True},
            "tamper_revoked": {"value": caught, "limit": 1, "le": False}}


def run(cell: Dict, c: Dict, mix: Dict, seed: int, seconds: float,
        trace_dir: str = None, on_window=None) -> SimpleNamespace:
    """Set-up, window, checks.  Returns the window's record with
    ``setup_s``, ``memory_peak_bytes`` and ``checks`` added."""
    import jax
    from lm_reference import Reference, readings
    params, eng = setup(c, mix, seed)
    trace = (TraceWindow(trace_dir, mix.get("trace_from", 0.4) * seconds,
                         mix.get("trace_seconds", 4.0))
             if trace_dir else None)
    setup_s = clock.since_start()
    if on_window is not None:
        on_window(True)
    win = drive(eng, c, mix, seed, seconds, trace)
    if on_window is not None:
        on_window(False)
    win.setup_s = setup_s
    win.trace = trace
    stats = jax.devices()[0].memory_stats() or {}
    win.memory_peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    chosen = sample(eng, win, mix, seed)        # before any stream is altered
    checks = trust_checks(eng, win, seed)
    win.attempted = len(win.records)
    win.failed = checks.get("honest_revoked", {}).get("value", 0)
    del eng
    win.driver.eng = None
    gc.collect()
    got = readings(Reference(c, params), chosen)["served"] if chosen else {}
    win.info = {f"{k}_logit_gap" if k != "mismatch" else "mismatch_share":
                v for k, v in got.items() if k in ("widest", "mismatch")}
    checks = {"mean_logit_gap": {"value": got.get("mean"),
                                 "limit": mix["check"]["mean_logit_gap"],
                                 "le": True},
              "sampled_tokens": {"value": got.get("tokens", 0),
                                 "limit": mix["check"]["min_tokens"],
                                 "le": False},
              **checks}
    win.checks = checks
    return win
