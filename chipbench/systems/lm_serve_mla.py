"""A DeepSeek-V3-style language model (latent attention, leading dense
layers, sigmoid-routed experts held as one chip's share) served by
``repro.serve.engine.ServingEngine``, as ``lm_serve`` serves Mixtral.

Set-up makes the weights from the seed (``mla_weights.py``), builds
the engine at the configuration's slots and cache (donated to the
step, so that the device holds one 5.4 GB latent cache and not two
during a call), compiles its step
for every chunk width, reads from the compiled step at the widest chunk
the names of the device ops its HLO tags with the named scope ``mla``
(the profiler's trace keeps op names, not scopes), and serves one short
request end to end.  The window is ``lm_serve.drive``'s backlog loop
with the stream drawn in blocks of one batch of slots (``drive``); the
program's counter ``serve.held_assignments`` is read around it.  After the
window, a sample of the finished requests (``lm_serve.sample``) is
compared with the plain reference (``mla_reference.py``) by
``lm_reference.readings``.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
from pathlib import Path
from types import SimpleNamespace
from typing import Dict

import numpy as np

import clock
import lm_reference
import mla_weights
from serve_driver import Driver, TraceWindow
from systems.lm_serve import WARM_ID, _trust, sample, trust_checks
from traffic import Backlog, rng_for

# the configuration file's keys, as the model's published config.json
# names them, and the names the benchmark's modules and the program's
# ModelConfig give the same sizes
SOURCE_KEYS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
               "num_attention_heads": "num_heads",
               "num_key_value_heads": "num_kv_heads",
               "intermediate_size": "d_ff",
               "moe_intermediate_size": "moe_d_ff",
               "n_routed_experts": "held_experts",
               "router_experts": "num_experts",
               "num_experts_per_tok": "num_experts_per_tok",
               "n_shared_experts": "num_shared_experts",
               "first_k_dense_replace": "leading_dense",
               "kv_lora_rank": "kv_lora_rank",
               "qk_nope_head_dim": "qk_nope_head_dim",
               "qk_rope_head_dim": "qk_rope_head_dim",
               "v_head_dim": "v_head_dim",
               "scoring_func": "router_scoring",
               "routed_scaling_factor": "routed_scaling_factor",
               "vocab_size": "vocab_size", "rope_theta": "rope_theta",
               "rms_norm_eps": "norm_eps",
               "tie_word_embeddings": "tie_embeddings"}

# keys of the resolved configuration that are the program's ModelConfig
# fields of the same name
MODEL_KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads", "d_ff",
              "moe_d_ff", "num_experts", "num_experts_per_tok",
              "num_shared_experts", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "router_scoring",
              "routed_scaling_factor", "vocab_size",
              "rope_theta", "norm_eps", "tie_embeddings", "matmul_precision")

SCOPE = "mla"


def resolve(c: Dict) -> Dict:
    """The configuration file in the names the benchmark reads.  A
    published key the benchmark cannot serve as stated is refused: a
    query LoRA, group-limited routing, rope scaling, attention biases,
    another activation, scoring or top-k method, chosen weights left
    unnormalized, or MoE layers that are not every layer after the
    leading dense ones."""
    refuse = {"q_lora_rank": None, "rope_scaling": None, "n_group": 1,
              "topk_group": 1, "attention_bias": False, "hidden_act": "silu",
              "scoring_func": "sigmoid", "topk_method": "noaux_tc",
              "norm_topk_prob": True, "moe_layer_freq": 1}
    for k, want in refuse.items():
        if c.get(k, want) != want:
            raise ValueError(f"{k} = {c[k]!r}: not covered by the program "
                             f"and the reference (only {want!r})")
    out = {k: v for k, v in c.items() if k not in SOURCE_KEYS}
    out.update({SOURCE_KEYS[k]: c[k] for k in SOURCE_KEYS})
    return out


def program_config(c: Dict):
    """The program's ``ModelConfig`` for configuration file ``c``: the
    named preset with every size the file gives and the first
    ``held_experts`` experts held, checked field by field, so the file
    is what runs."""
    from repro.configs import get_config
    base = get_config(c["program_config"])
    n_lead = c["leading_dense"]
    kinds = lambda specs: [(s.kind, s.mlp) for s in specs]
    if kinds(base.leading) != [("mla", "dense")] * n_lead or \
            kinds(base.block_pattern) != [("mla", "moe")]:
        raise ValueError("the reference covers leading dense mla layers, "
                         "then mla + MoE layers, only")
    cfg = dataclasses.replace(
        base, **{k: c[k] for k in MODEL_KEYS},
        held_experts=tuple(range(c["held_experts"])),
        num_blocks=c["num_layers"] - n_lead, remainder=()).validate()
    for k in MODEL_KEYS:
        if getattr(cfg, k) != c[k]:
            raise ValueError(f"{k}: program has {getattr(cfg, k)!r}, "
                             f"configuration file {c[k]!r}")
    if cfg.padded_vocab != cfg.vocab_size or cfg.padded_num_experts:
        raise ValueError("padded vocabulary or experts: not covered by the "
                         "reference")
    return cfg


def setup(c: Dict, mix: Dict, seed: int):
    """Config, weights, engine, compiled steps, one warm request.
    Returns (params, engine)."""
    import jax
    from repro.serve.engine import ServingEngine
    marks = [("start", clock.since_start())]
    cfg = program_config(c)
    params = jax.block_until_ready(mla_weights.make(c, seed))
    marks.append(("weights", clock.since_start()))
    sv = c["serving"]
    eng = ServingEngine(cfg, params, batch_slots=sv["slots"],
                        cache_len=sv["cache_len"],
                        prefill_chunk=sv["prefill_chunk"],
                        trust=_trust(mix, seed), donate_cache=True)
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
    """``lm_serve.drive``'s backlog loop, with the stream's quantile sets
    of lengths drawn in blocks of ``mix["block"]`` requests: at one block
    per batch of slots, every seed fills the slots with the same lengths
    in another order, so the window's share of prompt positions (which
    yield no token) moves with the seed far less than at blocks of 256.
    Records the engine's counters and slot occupancy over the window as
    ``lm_serve.drive`` does."""
    d = Driver(eng)
    cnt0, occ0 = d.counters(), d.occupancy()
    t0, t1 = d.backlog(Backlog(mix, c["vocab_size"], seed,
                               block=mix["block"]),
                       mix["backlog"], seconds, trace)
    cnt1, occ1 = d.counters(), d.occupancy()
    return SimpleNamespace(
        driver=d, t0=t0, t1=t1, seconds=t1 - t0,
        records=[r for r in d.records.values() if r["due"] < t1],
        steps=[s for s in d.steps if s["t0"] >= t0],
        counters={k: cnt1[k] - cnt0[k] for k in cnt0},
        occupancy=(occ1[0] - occ0[0], occ1[1] - occ0[1]),
        delivered=sum(r["n"] for r in d.records.values()))


def scoped_ops(hlo_text: str, scope: str = SCOPE) -> set:
    """Names of the device ops (the instructions of the entry, loop and
    call computations, not those inside fusions) whose ``op_name`` holds
    the named scope ``scope``."""
    tools = str(Path(__file__).resolve().parents[1] / "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    from hlo_dtypes import parse
    comps = parse(hlo_text)
    scheduled = {n for n, cp in comps.items() if cp["entry"]}
    for cp in comps.values():
        for ins in cp["instrs"]:
            scheduled |= {callee for kind, callee in ins["calls"]
                          if kind in ("body", "condition", "branch")
                          or ins["op"] == "call"}
    return {ins["name"] for n in scheduled for ins in comps[n]["instrs"]
            if scope in ins["op_name"].split("/")}


def step_hlo(eng, width: int) -> str:
    """The compiled serve step's HLO text at chunk width ``width``."""
    import jax.numpy as jnp
    B = eng.batch
    batch = {"tokens": jnp.zeros((B, width), jnp.int32),
             **{k: jnp.zeros(B, jnp.int32)
                for k in ("start", "pos", "lengths", "adv")}}
    return eng._step_fn.lower(eng.params, eng.caches, batch).compile() \
        .as_text()


def run(cell: Dict, c: Dict, mix: Dict, seed: int, seconds: float,
        trace_dir: str = None, on_window=None,
        controls=()) -> SimpleNamespace:
    """Set-up, window, checks.  Returns the window's record with
    ``setup_s``, ``memory_peak_bytes``, ``checks``, the window's
    ``held_assignments`` and, traced, the ``mla_ops``.  ``controls``:
    control precisions whose readings go to ``info`` too."""
    import jax
    from mla_reference import Reference
    params, eng = setup(c, mix, seed)
    mla_ops = (scoped_ops(step_hlo(eng, c["serving"]["prefill_chunk"]))
               if trace_dir else set())
    trace = (TraceWindow(trace_dir, mix.get("trace_from", 0.4) * seconds,
                         mix.get("trace_seconds", 4.0))
             if trace_dir else None)
    held = lambda: float(eng.obs.metrics.value("serve.held_assignments"))
    setup_s = clock.since_start()
    if on_window is not None:
        on_window(True)
    held0 = held()
    win = drive(eng, c, mix, seed, seconds, trace)
    win.held_assignments = held() - held0
    if on_window is not None:
        on_window(False)
    win.setup_s = setup_s
    win.trace = trace
    win.mla_ops = mla_ops
    stats = jax.devices()[0].memory_stats() or {}
    win.memory_peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    chosen = sample(eng, win, mix, seed)        # before any stream is altered
    checks = trust_checks(eng, win, seed)
    win.attempted = len(win.records)
    win.failed = checks.get("honest_revoked", {}).get("value", 0)
    del eng
    win.driver.eng = None
    gc.collect()
    got = (lm_reference.readings(Reference(c, params), chosen, controls)
           if chosen else {})
    served = got.get("served", {})
    win.info = {f"{k}_logit_gap" if k != "mismatch" else "mismatch_share":
                v for k, v in served.items() if k in ("widest", "mismatch")}
    win.info.update({f"{name}_{k}_logit_gap": v for name in controls
                     for k, v in got.get(name, {}).items()
                     if k in ("widest", "mean")})
    win.checks = {"mean_logit_gap": {"value": served.get("mean"),
                                     "limit": mix["check"]["mean_logit_gap"],
                                     "le": True},
                  "sampled_tokens": {"value": served.get("tokens", 0),
                                     "limit": mix["check"]["min_tokens"],
                                     "le": False},
                  **checks}
    return win
