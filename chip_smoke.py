#!/usr/bin/env python3
"""Chip smoke test: drive the system's main path once on a TPU.

    python3 chip_smoke.py            # one chip
    python3 chip_smoke.py --chips 4  # only the multi-chip paths

One chip (one process, one device, even on a host with more):

- device: platform, kind and count, and the kernel backend, which must
  resolve to compiled Pallas;
- verified serving at full width: ``bmoe-paper`` at its published widths
  (random weights from ``--seed``) through ``ServingEngine`` with and
  without a ``TrustConfig``; every request finishes and finalizes, the
  verified streams equal the unverified ones, and a tampered stream is
  revoked;
- reference: one request's first tokens against greedy decoding by the
  cache-free ``forward_train`` over the growing sequence, both under
  ``jax.default_matmul_precision("highest")``, at a capacity where no
  token drops;
- B-MoE rounds: the paper's system (N=10 experts, K=3, 10 edges, MLP
  experts, FMNIST-shaped data, batch 256) in the optimistic framework;
  an honest run books no fraud proof, no slash and no rollback, and an
  attacked run convicts and rolls back the malicious executor.  The
  round step and the audit recompute run the compiled Pallas kernels.

Four chips (``--chips 4``): the mesh-executed optimistic rounds
(``mesh="on"``, 8 experts over 4 edge shards, attacked) against the
single-device ``mesh="off"`` oracle, and the LM-scale consensus vote
(``make_trust``) on a ``(1, replica=2, model=2)`` mesh under attack.

Exits non-zero, and prints no result, when JAX finds no TPU or when any
check fails.  The last line of stdout is one JSON object naming the
device.  All data and weights are generated from ``--seed``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(f"check failed: {what}")
    log(f"  ok: {what}")


def peak_hbm() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.3f} GiB"


# ------------------------------------------------------------ serving
def serving_mix(vocab: int, n: int, seed: int, *, min_prompt: int,
                max_prompt: int, max_new: int):
    """``n`` seeded ``serving_requests`` with prompts of
    [min_prompt, max_prompt] tokens, each generating ``max_new``."""
    from repro.data.synthetic import serving_requests
    out = []
    for r in serving_requests(vocab, 8 * n, max_prompt=max_prompt + 1,
                              seed=seed):
        if len(r["prompt"]) >= min_prompt:
            out.append({"id": len(out), "prompt": r["prompt"],
                        "max_new_tokens": max_new})
        if len(out) == n:
            return out
    raise SmokeFailure(f"seed {seed} gave < {n} prompts >= {min_prompt}")


def serve_phase(cfg, params, requests, *, slots: int, cache_len: int,
                prefill_chunk: int, seed: int) -> None:
    from repro.serve.engine import ServingEngine
    from repro.trust.protocol import TrustConfig

    def run(trust):
        eng = ServingEngine(cfg, params, batch_slots=slots,
                            cache_len=cache_len, prefill_chunk=prefill_chunk,
                            trust=trust)
        t0 = time.monotonic()
        n = eng.warmup()
        log(f"  warmup: {n} width buckets compiled in "
            f"{time.monotonic() - t0:.1f} s")
        eng.submit(requests)
        t0 = time.monotonic()
        done = eng.run()
        log(f"  served {len(requests)} requests in {eng.tick} ticks, "
            f"{eng.steps} macro-steps, {time.monotonic() - t0:.2f} s host "
            f"clock (trust={'on' if trust else 'off'})")
        return eng, done

    plain, done = run(None)
    want = {r["id"]: r["max_new_tokens"] for r in requests}
    check(sorted(done) == sorted(want)
          and all(len(done[i]) == want[i] for i in want),
          f"unverified engine finished all {len(want)} requests with their "
          f"max_new tokens")
    del plain
    trust = TrustConfig(audit_rate=0.5, num_verifiers=2, challenge_window=6,
                        seed=seed)
    veng, vdone = run(trust)
    check(not veng.pending_finalization
          and all(veng.records[i].finalized for i in want)
          and sorted(vdone) == sorted(want),
          "verified engine finalized every request")
    check(vdone == done, "verified streams equal the unverified streams")
    reports = veng.audit_all()
    check(reports and not any(r["revoked"] for r in reports),
          f"{len(reports)} honest session audits, none revoked")
    rid = requests[0]["id"]
    rec = veng.records[rid]
    rec.tokens = [t ^ 1 for t in rec.tokens]     # the executor alters it
    caught = [veng.audit_session(rid, v)["revoked"]
              for v in range(trust.num_verifiers)]
    check(any(caught) and rid not in veng.completed,
          f"tampered stream {rid} revoked and no longer final")


def reference_phase(cfg, params, prompt, *, n_tokens: int,
                    cache_len: int) -> None:
    """The engine's greedy tokens against a cache-free forward over the
    growing sequence.  The reference pads its sequence to one length:
    attention is causal and bucket positions count only earlier tokens,
    so rows after the read position never reach it — one compile."""
    from repro.models import transformer as tfm
    from repro.serve.engine import ServingEngine

    # capacity >= tokens per group: no assignment drops on either path
    cfg = dataclasses.replace(cfg, capacity_factor=float(
        math.ceil(cfg.num_experts / cfg.num_experts_per_tok)))
    P = len(prompt)
    with jax.default_matmul_precision("highest"):
        eng = ServingEngine(cfg, params, batch_slots=1, cache_len=cache_len,
                            prefill_chunk=16)
        eng.submit([{"id": 0, "prompt": prompt, "max_new_tokens": n_tokens}])
        got = eng.run()[0]
        del eng
        fwd = jax.jit(lambda p, t: tfm.forward_train(
            p, t, cfg, remat=False)[0][0])
        seq = np.zeros((1, P + n_tokens), np.int32)
        seq[0, :P] = prompt
        ref, gaps = [], []
        for i in range(n_tokens):
            logits = np.asarray(fwd(params, jnp.asarray(seq))[P + i - 1])
            top2 = np.sort(logits)[-2:]
            ref.append(int(logits.argmax()))
            gaps.append(float(top2[1] - top2[0]))
            seq[0, P + i] = ref[-1]
    log(f"  prompt {P} tokens; engine {got}; reference {ref}")
    log(f"  reference top-2 logit gaps {['%.3g' % g for g in gaps]}")
    part = next((i for i in range(n_tokens) if got[i] != ref[i]), None)
    if part is not None:
        log(f"  streams part at token {part}: top-2 gap there "
            f"{gaps[part]:.3g}")
    check(part is None, f"first {n_tokens} greedy tokens equal the "
          f"cache-free reference")


# ------------------------------------------------------------ B-MoE rounds
def _round_data(seed: int, batch: int, rounds: int):
    from repro.data.synthetic import FMNIST, make_image_dataset
    xtr, ytr, xte, _ = make_image_dataset(FMNIST, n_train=4 * batch,
                                          n_test=batch, seed=seed)
    xtr = xtr.reshape(len(xtr), -1)
    rng = np.random.default_rng(seed)
    idx = [rng.integers(0, len(xtr), batch) for _ in range(rounds)]
    return [(xtr[i], ytr[i]) for i in idx], xte.reshape(len(xte), -1)


def _step_hlo(s, x, y) -> str:
    """Compiled HLO of the system's jitted round step on one batch
    (the argument construction of ``BMoESystem.train_round``)."""
    atk = s.cfg.attack
    rkey = jax.random.fold_in(jax.random.PRNGKey(s.cfg.seed + 17), 0)
    gate_bias, active = s._controls()
    bank = s._resolve_bank(x, gate_bias)
    return s._train_step.lower(
        s.gate, bank, jnp.asarray(x), jnp.asarray(y),
        jnp.zeros(s.cfg.num_edges, jnp.float32), jax.random.fold_in(rkey, 1),
        atk.noise_std, jnp.asarray(atk.colluding), gate_bias, active,
        jnp.int32(0)).compile().as_text()


def rounds_phase(*, seed: int, batch: int, rounds: int) -> None:
    from repro.core.attacks import AttackConfig
    from repro.core.bmoe import BMoEConfig, BMoESystem
    from repro.trust.protocol import RoundPhase, TrustConfig

    data, _ = _round_data(seed, batch, rounds)

    def run(attack, name):
        s = BMoESystem(BMoEConfig(
            framework="optimistic", num_experts=10, num_edges=10, top_k=3,
            expert_kind="mlp", attack=attack, seed=seed,
            trust=TrustConfig(audit_rate=1.0, num_verifiers=2,
                              challenge_window=2, seed=seed)))
        walls = []
        for x, y in data:
            t0 = time.monotonic()
            s.train_round(x, y)
            walls.append(time.monotonic() - t0)
        s.flush_trust()
        log(f"  {name}: round 0 (with compiles) {walls[0]:.2f} s, later "
            f"rounds {np.mean(walls[1:]):.3f} s mean, host clock")
        return s

    honest = run(AttackConfig(), "honest")
    check("tpu_custom_call" in _step_hlo(honest, *data[0]),
          "compiled round step carries the Pallas moe_gemm "
          "(tpu_custom_call)")
    # the audit recompute at one audit bucket: 8 sampled chunks of the
    # task (plus its zero sentinel row) gathered by row index
    xd = jnp.asarray(np.concatenate([data[0][0], np.zeros_like(
        data[0][0][:1])]))
    idx = jnp.zeros((8, batch // 10), jnp.int32)
    gid = jnp.zeros((8,), jnp.int32)
    check("tpu_custom_call" in honest._batched_recompute_call.lower(
        honest.experts, xd, idx, gid).compile().as_text(),
        "compiled audit recompute carries the Pallas audit_mlp "
        "(tpu_custom_call)")
    p = honest.protocol
    check(p.stats["fraud_proofs"] == 0 and not p.stakes.events
          and p.stats["rolled_back"] == 0,
          f"honest run: {p.stats['committed']} rounds committed, "
          f"{p.stats['audited_leaves']} leaves audited, no fraud proof, "
          f"no slash, no rollback")
    check(all(r.phase is RoundPhase.FINALIZED for r in p.rounds.values()),
          "honest run: every round finalized")

    malicious = 1                      # executor of round 1 (rotation)
    attacked = run(AttackConfig(malicious_edges=(malicious,),
                                attack_prob=1.0, noise_std=5.0), "attacked")
    p = attacked.protocol
    convicted = [r for r in p.rounds.values()
                 if r.phase is RoundPhase.ROLLED_BACK]
    check(convicted and all(r.executor == malicious for r in convicted)
          and {ev.edge for ev in p.stakes.events} == {malicious},
          f"attacked run: executor {malicious} convicted in rounds "
          f"{[r.round_id for r in convicted]} and slashed, nobody else")
    check(p.stats["rolled_back"] == len(convicted) and all(
        r.phase in (RoundPhase.FINALIZED, RoundPhase.ROLLED_BACK,
                    RoundPhase.INVALIDATED) for r in p.rounds.values()),
        "attacked run: convicted rounds rolled back, the rest settled")


# ------------------------------------------------------------ four chips
def mesh_rounds_phase(*, seed: int, batch: int, rounds: int) -> None:
    """Mesh-executed optimistic rounds against the single-device oracle:
    the comparison of ``benchmarks/mesh_bench.py``."""
    from repro.core.attacks import AttackConfig
    from repro.core.bmoe import BMoEConfig, BMoESystem
    from repro.core.ledger import digest_tree
    from repro.core.reputation import ReputationConfig
    from repro.trust.commitments import MerkleTree
    from repro.trust.protocol import TrustConfig

    data, xte = _round_data(seed, batch, rounds)
    atk = AttackConfig(malicious_edges=(2,), attack_prob=1.0, noise_std=5.0)

    def run(mesh):
        s = BMoESystem(BMoEConfig(
            framework="optimistic", dispatch="sparse", mesh=mesh,
            mesh_shards=4 if mesh == "on" else None, num_experts=8,
            num_edges=8, top_k=2, capacity_factor=1.25, pow_difficulty=2,
            attack=atk, seed=seed,
            reputation=ReputationConfig(init=0.5, gain=0.01, slash=0.4,
                                        exclusion_threshold=0.2),
            trust=TrustConfig(audit_rate=1.0, num_verifiers=2,
                              challenge_window=2, seed=seed)))
        t0 = time.monotonic()
        for x, y in data:
            s.train_round(x, y)
        s.flush_trust()
        log(f"  mesh={mesh}: {rounds} rounds in "
            f"{time.monotonic() - t0:.2f} s host clock (with compiles)")
        return s

    a, b = run("off"), run("on")
    check(b.mesh_shards == 4, "mesh run holds 4 edge shards")
    ra, rb = a.protocol.rounds, b.protocol.rounds
    la, _, _ = a.infer(xte, commit=False)
    lb, _, _ = b.infer(xte, commit=False)
    com = rb[0].commitment
    identity = {
        "params": digest_tree(a.experts) == digest_tree(b.experts)
        and digest_tree(a.gate) == digest_tree(b.gate),
        "commit_roots": all(ra[r].commitment.root == rb[r].commitment.root
                            for r in ra),
        "verdicts": all(
            ra[r].phase is rb[r].phase
            and [(q.leaf_index, q.expert, q.claimed_digest,
                  q.recomputed_digest) for q in ra[r].proofs]
            == [(q.leaf_index, q.expert, q.claimed_digest,
                 q.recomputed_digest) for q in rb[r].proofs]
            for r in ra),
        "rollbacks": a.protocol.stats["rolled_back"]
        == b.protocol.stats["rolled_back"] >= 1,
        "shard_root_reduction": com.num_shards == 4
        and MerkleTree(com.shard_roots).root == com.root,
        "infer_logits": np.asarray(la).tobytes() == np.asarray(lb).tobytes(),
    }
    log(f"  mesh vs oracle: {identity}")
    check(all(identity.values()),
          "mesh rounds bit-identical to the single-device oracle")


def replica_vote_phase(*, seed: int) -> None:
    """``make_trust`` on a (1, replica=2, model=2) mesh: a malicious
    replica's noise is voted out inside the shard_map."""
    from repro.core.trusted_moe import LMAttack, make_trust
    from repro.launch.mesh import make_mesh
    from repro.models.config import RedundancyConfig

    mesh = make_mesh((1, 2, 2), ("data", "replica", "model"))
    y = jax.random.normal(jax.random.PRNGKey(seed), (4, 16, 8, 128))
    want = np.asarray(y)
    for mode in ("faithful", "digest"):
        trust = make_trust(mesh, RedundancyConfig(2, mode), True,
                           LMAttack(malicious_replicas=(1,), noise_std=4.0))
        with mesh:
            got = np.asarray(jax.jit(trust)(y))
        check(np.array_equal(got, want),
              f"{mode} vote repairs the attacked replica bit for bit "
              f"(max |diff| {float(np.abs(got - want).max()):.3g})")


# ------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip paths")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0].platform is "
              f"{dev.platform!r})", file=sys.stderr)
        return 1
    count = len(jax.devices())
    if count < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX reports {count}", file=sys.stderr)
        return 1

    from repro.kernels.backend import require_compiled
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1
    jax.monitoring.register_event_listener(on_event)

    t_all = time.monotonic()
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={count} kernel_backend={require_compiled()}")
    log(f"compile cache: {cache_dir}")

    if args.chips == 1:
        from repro.configs import get_config
        from repro.train.loop import init_model
        cfg = get_config("bmoe-paper")
        t0 = time.monotonic()
        params = init_model(cfg, seed=args.seed)
        n_params = sum(a.size for a in jax.tree_util.tree_leaves(params))
        jax.block_until_ready(params)
        log(f"bmoe-paper at published widths: {n_params} params (f32), "
            f"initialised in {time.monotonic() - t0:.1f} s")
        requests = serving_mix(cfg.vocab_size, 8, args.seed, min_prompt=64,
                               max_prompt=512, max_new=32)
        log("phase serving: 8 slots, cache 1024, prefill chunk 16, prompts "
            f"{sorted(len(r['prompt']) for r in requests)}, max_new 32")
        serve_phase(cfg, params, requests, slots=8, cache_len=1024,
                    prefill_chunk=16, seed=args.seed)
        gc.collect()
        log(f"  peak HBM {peak_hbm()}")
        log("phase reference: highest matmul precision")
        reference_phase(cfg, params, requests[0]["prompt"], n_tokens=8,
                        cache_len=1024)
        del params
        gc.collect()
        log(f"  peak HBM {peak_hbm()}")
        log("phase rounds: optimistic B-MoE, N=10 K=3 M=10, batch 256")
        rounds_phase(seed=args.seed, batch=256, rounds=4)
    else:
        log("phase mesh rounds: 8 experts on 4 edge shards vs the oracle")
        mesh_rounds_phase(seed=args.seed, batch=256, rounds=5)
        log("phase replica vote: make_trust on a (1, 2, 2) mesh")
        replica_vote_phase(seed=args.seed)
    log(f"peak HBM {peak_hbm()}; compile cache hits "
        f"{cache_events['hits']}, misses {cache_events['misses']}; total "
        f"{time.monotonic() - t_all:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
