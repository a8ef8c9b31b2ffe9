"""Continuous-batching serving engine with prefill/decode disaggregation.

The engine serves requests (prompt + max_new_tokens) from a fixed set of
batch slots whose composition changes **every decode step**: finished
requests are evicted and queued requests admitted each tick
(``scheduler.SlotScheduler``), so a short request never waits for a long
co-batched one to drain.
Prefill is disaggregated from decode inside ONE fused compiled step
(``train.step.make_serve_chunk_step``): each call runs C engine ticks
as a ``lax.scan`` in which prefilling slots consume up to C prompt
tokens while decoding slots keep generating autoregressively — so a
long prompt costs ceil(len/C) dispatches instead of len, and in-flight
decode never stalls behind a token-by-token prompt feed.  Shapes are
fixed per pow2 width bucket (per-slot positions, per-row write masks),
so occupancy changes never recompile and there is no per-token Python
dispatch inside a chunk.

Verified sessions (``trust=TrustConfig(...)``): the optimistic
commit-challenge-audit protocol from ``repro.trust`` applied to
streaming inference.  Every emitted token is digested into a session
leaf, and the engine appends **one Merkle root per batch tick** — a
single tree over all slots' leaves for that tick
(``trust.session.commit_tick``), with per-session inclusion paths
derived from it — instead of one append per stream.  Per-session leaf
digests and sealed roots are unchanged, so ``audit_session`` verdicts
are bit-identical to the per-stream scheme on the same trace; the tick
tree adds an inclusion check that catches post-hoc rewrites of a
session's leaf list.  Finished requests enter an asynchronous challenge
window (engine ticks); ``completed`` exposes only *finalized* requests,
and a mismatching audit revokes a request instead of finalizing it.
"""
from __future__ import annotations

import dataclasses
import heapq
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.models import transformer as tfm
from repro.models.builder import materialize
from repro.models.config import ModelConfig
from repro.obs import Observability, now
from repro.obs.metrics import MetricsRegistry
from repro.serve.scheduler import SlotScheduler, SlotState
from repro.storage import ExpertCache, ExpertStore, StorageNetwork
from repro.storage.kv import (KV_GENESIS, KVBlockStore, KVStorageConfig,
                              prefix_chain, prefix_cid)
from repro.train.step import (at_product_width, make_serve_chunk_step,
                              product_dtype)
from repro.trust.audit import VerifierPool
from repro.trust.commitments import MerkleTree, RoundCommitment, leaf_digest
from repro.trust.da import DataAvailabilityAuditor
from repro.trust.protocol import ChallengeWindow, TrustConfig
from repro.trust.session import (SessionLeafRef, TickCommitment, commit_tick,
                                 verify_session_inclusion)

__all__ = ["KVStorageConfig", "ServingEngine", "SessionRecord",
           "SlotState"]


class _KVRuntime:
    """The engine's KV-paging sidecar: a ``KVBlockStore`` over its own
    storage network, store and byte-budgeted cache.

    ``da_rate > 0`` adds data-availability challenges over the sealed
    KV chunks: the same corrupt-slash-repair / withhold-window-slash
    machinery that audits expert chunks (``repro.trust.da``)."""

    def __init__(self, kcfg: KVStorageConfig,
                 metrics: Optional[MetricsRegistry] = None):
        self.cfg = kcfg
        self.T = int(kcfg.block_tokens)
        if self.T < 1:
            raise ValueError(f"block_tokens {self.T} < 1")
        self.network = StorageNetwork(num_nodes=kcfg.num_nodes,
                                      replication=kcfg.replication,
                                      seed=kcfg.seed, metrics=metrics,
                                      namespace="kv.network")
        self.store = ExpertStore(self.network, chunk_bytes=kcfg.chunk_bytes,
                                 metrics=metrics, namespace="kv.store")
        self.cache = ExpertCache(self.store, kcfg.cache_bytes,
                                 metrics=metrics, namespace="kv.cache")
        self.kv = KVBlockStore(self.store, self.cache, metrics=metrics)
        self.da = (DataAvailabilityAuditor(
            self.network, len(self.network.nodes), window=kcfg.da_window,
            sample_rate=kcfg.da_rate, seed=kcfg.seed, metrics=metrics,
            namespace="kv.da") if kcfg.da_rate > 0 else None)
        self.like = None                # block-structure template (lazy)

    def report(self) -> Dict:
        out = {**dict(self.kv.stats),
               "cache": dict(self.cache.stats),
               "store": dict(self.store.stats)}
        if self.da is not None:
            out["da"] = dict(self.da.stats)
        return out


def _tick_leaf(request_id: int, tick: int, token: int) -> str:
    """Leaf digest of one committed engine tick.  The (1, 3) row layout
    matches ``RoundCommitment.leaf_chunk`` for a one-tick-per-leaf
    commitment, so session audits run through the same batched
    ``VerifierPool`` path as training audits."""
    return leaf_digest(np.array([[request_id, tick, token]], np.int64))


@dataclasses.dataclass
class SessionRecord:
    """Per-request commitment stream: one leaf per generated token, plus
    (in the batched-commitment engine) one inclusion reference per leaf
    into the batch tick tree it was committed under."""
    request_id: int
    leaves: List[str] = dataclasses.field(default_factory=list)
    ticks: List[int] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    refs: List[SessionLeafRef] = dataclasses.field(default_factory=list)
    root: str = ""
    finalized: bool = False
    revoked: bool = False
    audited: bool = False              # at least one spot-check pass ran

    def append(self, tick: int, token: int) -> None:
        self.leaves.append(_tick_leaf(self.request_id, tick, token))
        self.ticks.append(tick)
        self.tokens.append(token)

    def seal(self) -> str:
        self.root = MerkleTree(self.leaves).root
        return self.root

    def commitment(self) -> RoundCommitment:
        """The sealed session as a RoundCommitment: one (pseudo-)expert,
        one tick per leaf — what lets ``VerifierPool.audit_batched``
        audit a serving session and a training round through one code
        path.  ``claimed`` holds the *current* stream records; the
        sealed ``leaf_digests`` are what they are checked against."""
        t = len(self.leaves)
        claimed = np.array(
            [[[self.request_id, self.ticks[i], self.tokens[i]]
              for i in range(t)]], np.int64)
        return RoundCommitment(
            round_id=self.request_id, executor=-1, root=self.root,
            num_experts=1, chunks_per_expert=t, bounds=list(range(t + 1)),
            leaf_digests=list(self.leaves), claimed=claimed)


def latent_rows(pos: np.ndarray, C: int, cap: int, blk: int):
    """Latent rows one ``mla`` layer's attention core covers over a
    macro-step of ``C`` micro-steps from positions ``pos`` (B,): every
    slot reads rows ``0 .. min(pos + t, cap - 1)`` at micro-step ``t``.
    Returns (live, read): their sum, and the same in whole ``blk``-row
    blocks, the unit the core reads in."""
    rows = np.minimum(pos[None, :] + np.arange(C)[:, None], cap - 1) + 1
    return int(rows.sum()), int((-(-rows // blk) * blk).sum())


def _zero_slots(caches, sel):
    """``caches`` with the rows of the slots ``sel`` (B,) bool zeroed:
    batch is axis 1 of the stacked block caches (a leading layer axis),
    axis 0 of the leading and remainder layers' caches."""
    def zero_rows(axis):
        def f(a):
            m = sel.reshape((1,) * axis + (-1,) + (1,) * (a.ndim - axis - 1))
            return jnp.where(m, jnp.zeros((), a.dtype), a)
        return f

    new = {"blocks": jax.tree_util.tree_map(zero_rows(1), caches["blocks"])}
    for part in tfm.UNSTACKED:
        if part in caches:
            new[part] = jax.tree_util.tree_map(zero_rows(0), caches[part])
    return new


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, *, batch_slots: int = 4,
                 cache_len: int = 256, mesh=None, prefill_chunk: int = 16,
                 trust: Optional[TrustConfig] = None,
                 kv_storage: Optional[KVStorageConfig] = None,
                 obs: Optional[Observability] = None,
                 donate_cache: bool = False):
        if cfg.is_encoder_decoder:
            raise NotImplementedError("engine drives decoder-only archs")
        self.cfg = cfg
        self.obs = obs if obs is not None else Observability()
        # every matrix product's weight held at the width the product
        # reads it (bfloat16 for one bfloat16 pass on a TPU), rounded
        # once here rather than inside every call of the step
        platform = (mesh.devices.flat[0].platform if mesh is not None
                    else jax.default_backend())
        self.params, narrowed = at_product_width(
            params, product_dtype(cfg, platform))
        flat = jax.tree_util.tree_flatten_with_path(self.params)[0]
        self.obs.metrics.gauge("serve.product_weight_bytes").set(
            sum(a.nbytes for path, a in flat if tfm.is_product_weight(path)))
        self.obs.metrics.gauge("serve.product_weight_narrowed_bytes").set(
            sum(a.nbytes for path, a in flat if path in narrowed))
        self.batch = batch_slots
        self.cache_len = cache_len
        self.prefill_chunk = max(1, int(prefill_chunk))
        self.caches = materialize(
            tfm.cache_decl(cfg, batch_slots, cache_len),
            jax.random.PRNGKey(0))
        self.sched = SlotScheduler(batch_slots)
        # ---- KV paging through the chunked store: sealed prefix-CID
        # blocks, warm-prefix restore on admission, page-out/resume
        self.kvrt = None
        if kv_storage is not None:
            tfm.check_kv_pageable(cfg)
            if cache_len - 1 < kv_storage.block_tokens:
                raise ValueError(
                    f"block_tokens {kv_storage.block_tokens} cannot fit "
                    f"cache_len {cache_len} (need <= cache_len - 1)")
            self.kvrt = _KVRuntime(kv_storage, metrics=self.obs.metrics)
        # per-slot prefix-chain cursor: {"prev": cid, "sealed": nblocks}
        self._kv_chain: List[Optional[Dict]] = [None] * batch_slots
        # paged-out requests awaiting readmission: rid -> resume state
        self._kv_resume: Dict[int, Dict] = {}
        self._pending_kv_roots: List[str] = []   # sealed, not yet committed
        self._kv_macro_cids: List[str] = []      # sealed this macro-step
        # ONE compiled fused step: C engine ticks per call (C=1 pure
        # decode up to C=prefill_chunk while prompts are chunking), fixed
        # (B, C) shapes per pow2 width bucket (jax.jit's shape cache) —
        # occupancy changes never recompile, and there is no per-token
        # Python dispatch inside a chunk
        # ``donate_cache``: the step and the slot reset write the new
        # cache into the old one's buffer, so the device never holds two
        # (a long latent cache is most of the memory); the caller then
        # keeps no reference to ``self.caches`` across either.  Off by
        # default only while a caller still reuses the cache it passed
        # in (a fault test that returns the step's input); every engine
        # can donate once none does, and the option then goes
        self._step_fn = jax.jit(make_serve_chunk_step(cfg, mesh),
                                donate_argnums=(1,) if donate_cache else ())
        self._reset_fn = jax.jit(_zero_slots,
                                 donate_argnums=(0,) if donate_cache else ())
        # latent attention: how many layers read the latent cache, and the
        # block they read it in
        mla = lambda specs: sum(s.kind == "mla" for s in specs)
        self._mla_layers = (mla(cfg.leading) + mla(cfg.remainder)
                            + mla(cfg.block_pattern)
                            * cfg.resolved_num_blocks)
        if self._mla_layers:
            self._latent_blk = ops.mla_read_block(cache_len)
        self.tick = 0
        self.steps = 0                  # fused macro-step invocations
        self._done: Dict[int, List[int]] = {}
        # ---- verified-session state (optimistic trust layer)
        self.trust = trust
        self.records: Dict[int, SessionRecord] = {}
        self.session_log: List[Dict] = []       # commit/finalize/revoke events
        # the on-chain session commitment stream: ONE append per batch
        # tick (a Merkle root over every token emitted that tick)
        self.tick_commitments: List[TickCommitment] = []
        self._window = (ChallengeWindow(trust.challenge_window)
                        if trust is not None else None)
        # audit_rate is the pool-wide sampled fraction (same contract as
        # OptimisticProtocol): each verifier draws its stake-weighted
        # share, and session re-audits catch rubber-stampers too
        self._auditors = (VerifierPool(
            trust.num_verifiers,
            trust.audit_rate / max(trust.num_verifiers, 1),
            trust.lazy_verifier_prob, trust.seed,
            stakes=trust.verifier_stakes, reaudit_rate=trust.reaudit_rate,
            verifier_slash_fraction=trust.verifier_slash_fraction,
            metrics=self.obs.metrics, namespace="serve.verifiers")
            if trust is not None else None)
        self._finalized: set = set()
        # deadline-ordered auto-audit queue: a sealed session's audit is
        # parked off the critical path and drained (whole backlog at
        # once, mirroring OptimisticProtocol.pop_audit_jobs) when the
        # oldest challenge window is about to close — so a tampered
        # stream is caught *before* it can finalize
        self._audit_queue: List[Tuple[int, int]] = []   # (deadline, rid)
        # sessions neither finalized nor revoked: the only ones the
        # finality-deferral and chained-revocation scans must touch —
        # O(open), not O(all sessions ever served)
        self._open_sessions: set = set()

    # ------------------------------------------------------------- views
    @property
    def slots(self) -> List[SlotState]:
        return self.sched.slots

    @property
    def queue(self):
        return self.sched.queue

    @property
    def request_meta(self) -> Dict[int, Dict[str, float]]:
        """Per-request milestones: submitted/admitted/first-token/
        finished ticks, and on the obs clock (``repro.obs.now``) when
        the request was submitted, admitted and its first token reached
        the host (``-1`` until then)."""
        return self.sched.meta

    @property
    def verified(self) -> bool:
        return self.trust is not None

    @property
    def completed(self) -> Dict[int, List[int]]:
        """Finished — and, in verified mode, *finalized* — requests, in
        request-submission order (deterministic output)."""
        if not self.verified:
            return {rid: self._done[rid] for rid in self.sched.submit_order
                    if rid in self._done}
        return {rid: self._done[rid] for rid in self.sched.submit_order
                if rid in self._finalized}

    @property
    def pending_finalization(self) -> List[int]:
        """Finished requests still inside their challenge window."""
        if not self.verified:
            return []
        return [rid for rid in self.sched.submit_order
                if rid in self._done and rid not in self._finalized
                and not self.records[rid].revoked]

    def submit(self, requests: Iterable[dict]):
        requests = list(requests)
        self.sched.submit(requests, self.tick)
        t = now()
        for r in requests:
            self.sched.meta[r["id"]].update(submitted_s=t, admitted_s=-1.0,
                                            first_token_s=-1.0)

    def warmup(self) -> int:
        """Compile every fused-step width bucket up front (the pow2s up
        to ``prefill_chunk``) against zero-advance dummy batches —
        ``adv=0`` masks every cache write, so state is untouched — so no
        compile ever lands in a served request's latency.  Returns the
        number of buckets compiled."""
        w, n = 1, 0
        while True:
            batch = {"tokens": jnp.zeros((self.batch, w), jnp.int32),
                     "start": jnp.zeros(self.batch, jnp.int32),
                     "pos": jnp.zeros(self.batch, jnp.int32),
                     "lengths": jnp.zeros(self.batch, jnp.int32),
                     "adv": jnp.zeros(self.batch, jnp.int32)}
            out = self._step_fn(self.params, self.caches, batch)
            self.caches = out[1]
            jax.block_until_ready(out[0])
            n += 1
            if w * 2 > self.prefill_chunk:
                return n
            w *= 2

    # ------------------------------------------------------- slot intake
    def _admit(self) -> None:
        admitted = self.sched.admit(self.tick)
        if not admitted:
            return
        t = now()
        wait = self.obs.metrics.histogram("serve.queue_wait_s")
        for _, slot in admitted:
            meta = self.sched.meta[slot.request_id]
            if meta["admitted_s"] < 0:        # a readmission is not counted
                meta["admitted_s"] = t
                wait.observe(t - meta["submitted_s"])
        with self.obs.span("reset-slots", slots=len(admitted)):
            self._reset_slot_caches([i for i, _ in admitted])
        if self.kvrt is not None:
            for i, slot in admitted:
                self._kv_on_admit(i, slot)
        if self.verified:
            for _, slot in admitted:
                rid = slot.request_id
                # a paged-out-then-readmitted session keeps its record:
                # its commitment stream continues where it left off
                if rid not in self.records:
                    self.records[rid] = SessionRecord(request_id=rid)
                self._open_sessions.add(rid)

    def _reset_slot_caches(self, idxs: List[int]) -> None:
        """Zero the admitted slots' cache rows (KV + recurrent state)."""
        sel = np.zeros(self.batch, bool)
        sel[idxs] = True
        self.caches = self._reset_fn(self.caches, jnp.asarray(sel))

    # ------------------------------------------------------- KV paging
    def _kv_template(self):
        """Structure-only template for ``assemble_tree`` (leaf shapes
        come from the manifest, only the treedef must match)."""
        if self.kvrt.like is None:
            self.kvrt.like = tfm.slice_kv_block(self.caches, 0, 0, 1)
        return self.kvrt.like

    @staticmethod
    def _fed_tokens(s: SlotState, a: int, b: int) -> np.ndarray:
        """Token ids FED at cache positions [a, b): the prompt up to its
        length, then the generated continuation (cache row p holds the
        KV of the token fed at position p — a pure function of the
        token prefix, which is what makes prefix-CID addressing
        sound)."""
        L = len(s.prompt)
        out = np.empty(b - a, np.int64)
        for j, p in enumerate(range(a, b)):
            out[j] = int(s.prompt[p]) if p < L else s.generated[p - L]
        return out

    def _kv_on_admit(self, index: int, slot: SlotState) -> None:
        """Admission-side restore: a readmitted paged-out request gets
        its exact sealed state back; a fresh request whose leading
        prompt blocks are already sealed (another session shared the
        prefix) restores them instead of recomputing prefill.  At least
        one prompt token is always left unconsumed — the first
        generated token comes from feeding the LAST prompt token."""
        kv, T = self.kvrt.kv, self.kvrt.T
        rid = slot.request_id
        res = self._kv_resume.pop(rid, None)
        if res is not None:
            for cid, a, b in res["cids"]:
                block = kv.fetch(cid, self._kv_template())
                self.caches = tfm.restore_kv_block(self.caches, index,
                                                   a, block)
            slot.pos, slot.cursor = res["pos"], res["cursor"]
            slot.generated = list(res["generated"])
            self._kv_chain[index] = {"prev": res["prev"],
                                     "sealed": res["sealed"]}
            kv.stats["resumes"] += 1
            kv.stats["restored_tokens"] += slot.pos
            return
        chain = prefix_chain(slot.prompt, T)
        # restorable blocks must end strictly inside the prompt
        restorable = chain[:max(0, (len(slot.prompt) - 1) // T)]
        n = kv.warm_prefix(restorable) if restorable else 0
        for b in range(n):
            block = kv.fetch(chain[b], self._kv_template())
            self.caches = tfm.restore_kv_block(self.caches, index,
                                               b * T, block)
        slot.pos = slot.cursor = n * T
        self._kv_chain[index] = {"prev": chain[n - 1] if n else KV_GENESIS,
                                 "sealed": n}
        if n:
            kv.stats["restored_tokens"] += n * T

    def _kv_seal_upto(self, index: int, s: SlotState) -> None:
        """Seal every full block the slot's fed sequence has crossed.
        The compiled chunk already wrote these rows (cache rows are
        write-once), so slicing the post-chunk cache at any replay tick
        past the block boundary reads exactly what that tick held.  A
        CID another session already sealed dedups without slicing."""
        st, kv, T = self._kv_chain[index], self.kvrt.kv, self.kvrt.T
        while (st["sealed"] + 1) * T <= s.pos:
            b = st["sealed"]
            cid = prefix_cid(st["prev"],
                             self._fed_tokens(s, b * T, (b + 1) * T))
            if cid in kv:
                man = kv.seal(cid, None, 0)
            else:
                block = tfm.slice_kv_block(self.caches, index,
                                           b * T, (b + 1) * T)
                man = kv.seal(cid, block, T)
            st["prev"], st["sealed"] = cid, b + 1
            if self.verified:
                self._pending_kv_roots.append(man.root)
            self._kv_macro_cids.append(cid)

    def _kv_prefetch_queued(self) -> None:
        """Warm the cache with queued requests' sealed prefix blocks —
        issued right after the fused chunk dispatch, so the fetch
        overlaps co-batched decode the way ``GateEMA`` prefetch
        overlaps expert fetch.  Prefetch never evicts residents."""
        kv, T = self.kvrt.kv, self.kvrt.T
        for r in list(self.sched.queue)[:self.batch]:
            if r["id"] in self._kv_resume:
                continue                 # resume fetches exact blocks
            chain = prefix_chain(r["prompt"], T)
            run = []
            for cid in chain[:max(0, (len(r["prompt"]) - 1) // T)]:
                if cid not in kv:
                    break
                run.append(KVBlockStore.object_id(cid))
            if run:
                self.kvrt.cache.prefetch(run, 0,
                                         lambda oid: self._kv_template())

    def page_out(self, index: int) -> int:
        """Page a running slot's KV out of the compute cache: seal its
        full blocks plus the partial tail block to the chunked store,
        stash the resume cursor, and requeue the request at the queue
        FRONT.  Readmission (``_kv_on_admit``) restores the rows and
        the slot resumes decode bit-identically.  Returns the request
        id."""
        if self.kvrt is None:
            raise ValueError("engine was not started with kv_storage")
        s = self.sched.slots[index]
        if not s.active:
            raise ValueError(f"slot {index} is not active")
        kv, T = self.kvrt.kv, self.kvrt.T
        self._kv_seal_upto(index, s)     # normally already sealed
        st = self._kv_chain[index]
        nfull, prev = st["sealed"], st["prev"]
        entries = []
        chain_prev = KV_GENESIS
        for b in range(nfull):
            chain_prev = prefix_cid(chain_prev,
                                    self._fed_tokens(s, b * T, (b + 1) * T))
            entries.append((chain_prev, b * T, (b + 1) * T))
        if s.pos > nfull * T:
            # tail block: chained over its (shorter) token run — the
            # int64 encoding binds the count, so it can never collide
            # with the full block over the same prefix
            tail_cid = prefix_cid(prev,
                                  self._fed_tokens(s, nfull * T, s.pos))
            block = tfm.slice_kv_block(self.caches, index, nfull * T, s.pos)
            man = kv.seal(tail_cid, block, s.pos - nfull * T)
            if self.verified:
                self._pending_kv_roots.append(man.root)
            entries.append((tail_cid, nfull * T, s.pos))
        self._kv_resume[s.request_id] = {
            "pos": s.pos, "cursor": s.cursor,
            "generated": list(s.generated),
            "cids": entries, "prev": prev, "sealed": nfull}
        kv.stats["pageouts"] += 1
        rid = self.sched.preempt(index, self.tick)
        self._kv_chain[index] = None
        return rid

    # --------------------------------------------------------- emissions
    def _emit(self, slot: SlotState, token: int, host_s: float) -> None:
        """Hand ``token`` to ``slot``'s stream; ``host_s`` is when the
        chunk that made it reached the host (obs clock)."""
        slot.generated.append(token)
        m = self.obs.metrics
        if len(slot.generated) == 1:
            slot.first_token_tick = self.tick
            meta = self.sched.meta[slot.request_id]
            meta["first_token_tick"] = self.tick
            meta["first_token_s"] = host_s
            m.histogram("serve.ttft_s").observe(host_s - meta["submitted_s"])
        m.counter("serve.tokens").add(1)
        if self.verified:
            self.records[slot.request_id].append(self.tick, token)

    def _finish(self, index: int) -> None:
        slot = self.sched.slots[index]
        generated = slot.generated[:slot.to_generate]
        rid = self.sched.release(index, self.tick)
        self._done[rid] = generated
        if not self.verified:
            return
        rec = self.records[rid]
        root = rec.seal() if rec.leaves else ""
        self.session_log.append({"event": "commit", "request": rid,
                                 "root": root[:16], "tick": self.tick,
                                 "leaves": len(rec.leaves)})
        self._window.enter(rid, self.tick)
        if rec.leaves:
            heapq.heappush(self._audit_queue,
                           (self.tick + self.trust.challenge_window, rid))

    # ----------------------------------------------------- the macro-step
    def step(self):
        """One fused macro-step: admit from the queue, then run C engine
        ticks in ONE compiled call — prefilling slots chunk-consume
        their prompts while decoding slots keep generating (C=1 when no
        prompt is in flight, up to ``prefill_chunk`` while one is).
        Per engine tick, host-side: emit, batch-commit the tick's
        Merkle leaf set, evict finished slots.  In verified mode, ticks
        keep running after the queue drains until every challenge
        window has closed.

        Spans under ``step``: ``admit`` (``reset-slots``), ``prepare``
        (chunk width, batch arrays), ``prefill``/``decode`` (``launch``:
        the compiled call until it returns; ``wait``: until its tokens
        are on the host), ``kv-prefetch`` when on,
        ``replay`` (the per-tick host work: emit, ``commit``, finish,
        window expiry with its ``audit-drain``), ``kv-da`` when on."""
        with self.obs.span("step", metric="serve.tick_s", tick=self.tick):
            return self._step_inner()

    def _step_inner(self):
        with self.obs.span("admit", metric="serve.admit_s",
                           tick=self.tick):
            self._admit()
        if not self.sched.any_active:
            if self.verified and len(self._window):
                with self.obs.span("replay", metric="serve.replay_s",
                                   tick=self.tick):
                    self.tick += 1           # idle tick: windows still age
                    self._expire_windows()
                return bool(len(self._window))
            return False
        self.steps += 1
        with self.obs.span("prepare", tick=self.tick):
            C, need, adv, pos, batch = self._prepare()
        name, metric = (("prefill", "serve.prefill_s") if (need > 0).any()
                        else ("decode", "serve.decode_s"))
        with self.obs.span(name, metric=metric, tick=self.tick, width=C):
            with self.obs.span("launch"):
                outs, self.caches, *held = self._step_fn(
                    self.params, self.caches, batch)
            with self.obs.span("wait"):
                outs = np.asarray(outs)      # (C, B) greedy next tokens
            if held:
                # assignments routed to this device's experts, all MoE
                # layers and micro-steps of the call
                self.obs.metrics.counter("serve.held_assignments").add(
                    int(held[0]))
        if self._mla_layers:
            # the latent rows the attention core covers (live) and reads
            # (whole blocks), over micro-steps, slots and mla layers
            live, read = latent_rows(pos, C, self.cache_len,
                                     self._latent_blk)
            self.obs.metrics.counter("serve.latent_rows_live").add(
                self._mla_layers * live)
            self.obs.metrics.counter("serve.latent_rows_read").add(
                self._mla_layers * read)
        host_s = now()
        if self.kvrt is not None:
            # overlap with the chunk just dispatched: warm queued
            # requests' sealed prefix blocks into the cache
            with self.obs.span("kv-prefetch"):
                self._kv_macro_cids = []
                self._kv_prefetch_queued()
        with self.obs.span("replay", metric="serve.replay_s",
                           tick=self.tick, width=C):
            self._replay(C, need, adv, outs, host_s)
        if self.kvrt is not None and self.kvrt.da is not None \
                and self._kv_macro_cids:
            # DA challenges over the KV chunks sealed this macro-step:
            # replica nodes answer for sealed KV exactly like expert
            # chunks (corrupt -> slash + repair; withheld -> window)
            with self.obs.span("kv-da"):
                seen = sorted(set(self._kv_macro_cids))
                self.kvrt.da.challenge_round(self.tick,
                                             self.kvrt.kv.manifests(seen))
                self.kvrt.da.resolve(self.tick)
        return True

    def _prepare(self):
        """The macro-step's chunk width ``C`` and its inputs: per slot the
        prompt tokens it consumes (``need``) and the micro-steps it
        advances (``adv``), their positions at its first micro-step
        (``pos``), and the device batch."""
        m = self.obs.metrics
        m.histogram("serve.occupancy").observe(self.sched.occupancy())
        m.gauge("serve.queue_depth").set(self.sched.depth())
        slots = self.sched.slots

        # ---- chunk width C: the largest pow2 <= the busiest active
        # slot's remaining work (prompt left + tokens left to generate,
        # cache-bounded) — so no tick in the chunk is pure waste past
        # everyone's completion — capped by prefill_chunk and every
        # active slot's cache headroom.  The pow2 rounding bounds the
        # compile set to log2(prefill_chunk)+1 shape buckets.
        need = self.sched.prefill_lengths(self.prefill_chunk, self.cache_len)
        work = max((len(s.prompt) - s.cursor)
                   + max(s.to_generate - len(s.generated), 0)
                   for s in slots if s.active)
        headroom = min(self.cache_len - 1 - s.pos for s in slots if s.active)
        cmax = max(1, min(self.prefill_chunk, headroom, work))
        C = 1 << (cmax.bit_length() - 1)          # round DOWN to pow2
        need = np.minimum(need, C).astype(np.int32)

        tokens = np.zeros((self.batch, C), np.int32)
        start = np.zeros(self.batch, np.int32)
        pos = np.zeros(self.batch, np.int32)
        adv = np.zeros(self.batch, np.int32)
        for i, s in enumerate(slots):
            if not s.active:
                continue
            n = int(need[i])
            pos[i] = s.pos
            if n:
                tokens[i, :n] = s.prompt[s.cursor:s.cursor + n]
            if s.generated:
                start[i] = s.generated[-1]
            # a slot that finishes its prompt inside the chunk (or is
            # already decoding) generates for the rest of the scan; a
            # chunk/headroom-capped prefill slot stops at its cap
            adv[i] = C if s.cursor + n >= len(s.prompt) else n
        batch = {"tokens": jnp.asarray(tokens), "start": jnp.asarray(start),
                 "pos": jnp.asarray(pos), "lengths": jnp.asarray(need),
                 "adv": jnp.asarray(adv)}
        return C, need, adv, pos, batch

    def _replay(self, C: int, need: np.ndarray, adv: np.ndarray,
                outs: np.ndarray, host_s: float) -> None:
        """Replay the chunk host-side, one engine tick per micro-step:
        emit, seal KV blocks, commit, finish, expire windows."""
        slots = self.sched.slots
        for t in range(C):
            self.tick += 1
            emissions: List[Tuple[int, int, int]] = []  # (slot, rid, tok)
            for i, s in enumerate(slots):
                if not s.active:             # idle, or finished mid-chunk
                    continue
                n = int(need[i])
                if t < n:                    # consumed a prompt token
                    s.cursor += 1
                    s.pos += 1
                    if s.cursor == len(s.prompt):
                        tok = int(outs[t, i])   # first generated token
                        self._emit(s, tok, host_s)
                        emissions.append((i, s.request_id, tok))
                elif int(adv[i]) == C and s.cursor >= len(s.prompt):
                    tok = int(outs[t, i])    # autoregressive continuation
                    self._emit(s, tok, host_s)
                    emissions.append((i, s.request_id, tok))
                    s.pos += 1
            if self.kvrt is not None:
                # seal the blocks this tick completed (prefill AND
                # decode rows page through the same chain), BEFORE the
                # commit so their manifest roots ride this tick's
                # on-chain append
                for i, s in enumerate(slots):
                    if s.active:
                        self._kv_seal_upto(i, s)
            if self.verified and emissions:
                self._commit_tick(emissions)
            for i, s in enumerate(slots):
                if not s.active:
                    continue
                done = (not s.prefilling
                        and len(s.generated) >= s.to_generate)
                if done or s.pos >= self.cache_len - 1:
                    self._finish(i)
            if self.verified:
                self._expire_windows()

    def _commit_tick(self, emissions: List[Tuple[int, int, int]]) -> None:
        """One Merkle append for the whole batch tick: a tree over every
        token emitted this tick (slot order); each session stores its
        inclusion path into it.  KV-block manifest roots sealed since
        the last append ride along as the side-band ``kv_root`` (a
        prefill tick can seal without emitting, so pending roots carry
        forward); the token ``root`` is untouched — streams and
        verdicts stay bit-identical to paging-off."""
        with self.obs.span("commit", metric="serve.commit_s",
                           tick=self.tick, leaves=len(emissions)):
            entries = [(rid, self.records[rid].leaves[-1])
                       for _, rid, _ in emissions]
            tc, refs = commit_tick(self.tick, entries,
                                   kv_roots=self._pending_kv_roots)
            self._pending_kv_roots = []
            self.tick_commitments.append(tc)
            for rid, ref in refs.items():
                self.records[rid].refs.append(ref)
            m = self.obs.metrics
            m.counter("serve.commit.appends").add(1)
            m.counter("serve.commit.leaves").add(len(entries))

    def run(self, max_ticks: int = 10_000) -> Dict[int, List[int]]:
        ticks = 0
        while self.step() and ticks < max_ticks:
            ticks += 1
        return self.completed

    # ------------------------------------------------------- observability
    def obs_report(self) -> Dict:
        """Serving-side view over the metrics registry: tick/token
        throughput, wall-clock totals per phase, queue-wait and
        time-to-first-token percentiles (submission to admission, and
        to the first token on the host), slot occupancy, the
        batched-commitment append counters, plus the KV paging section
        when KV paging is on."""
        m = self.obs.metrics
        out = {
            "ticks": self.tick,
            "tokens": int(m.value("serve.tokens")),
            "tick_s": float(m.value("serve.tick_s")),
            "admit_s": float(m.value("serve.admit_s")),
            "prefill_s": float(m.value("serve.prefill_s")),
            "decode_s": float(m.value("serve.decode_s")),
            "commit_s": float(m.value("serve.commit_s")),
            "replay_s": float(m.value("serve.replay_s")),
            "audit_offpath_s": float(m.value("serve.audit_s")),
            "queue_wait": m.histogram("serve.queue_wait_s").snapshot(),
            "ttft": m.histogram("serve.ttft_s").snapshot(),
            "occupancy": m.histogram("serve.occupancy").snapshot(),
            "commit_appends": int(m.value("serve.commit.appends")),
            "commit_leaves": int(m.value("serve.commit.leaves")),
        }
        if self.kvrt is not None:
            out["kv"] = self.kvrt.report()
        return out

    def report(self) -> Dict:
        return self.obs_report()

    # ------------------------------------------------ audits (verified)
    def _audit_full(self, rid: int) -> None:
        """One spot-check pass per verifier (stopping early once a fraud
        revokes the session)."""
        for v in range(self._auditors.num_verifiers):
            self.audit_session(rid, v)
            if self.records[rid].revoked:
                break

    def _drain_session_audits(self) -> None:
        """Run queued session audits once the oldest deadline is due —
        and then the whole backlog, so audits burst off the critical
        path instead of blocking every tick."""
        if not self._audit_queue or self._audit_queue[0][0] > self.tick:
            return
        # burst drains off the critical path: booked to serve.audit_s and
        # excluded from the enclosing tick span's serve.tick_s
        drained = [rid for _, rid in self._audit_queue]
        with self.obs.span("audit-drain", metric="serve.audit_s",
                           off_path=True, tick=self.tick, drained=drained):
            while self._audit_queue:
                _, rid = heapq.heappop(self._audit_queue)
                rec = self.records[rid]
                if rec.revoked or not rec.root:
                    continue
                self._audit_full(rid)

    @staticmethod
    def _overlaps(a: SessionRecord, b: SessionRecord) -> bool:
        return (bool(a.ticks) and bool(b.ticks)
                and b.ticks[0] <= a.ticks[-1] and a.ticks[0] <= b.ticks[-1])

    def _expire_windows(self) -> None:
        self._drain_session_audits()
        for rid in self._window.expire(self.tick):
            rec = self.records[rid]
            if rec.revoked:
                continue
            # serving-side sequential finality: a stream cannot finalize
            # while a tick-overlapping co-batched stream is still being
            # produced (its later-confirmed fraud would void this one) or
            # is sealed but unchecked — spot-check the neighbour first,
            # which revokes this stream too if the neighbour was altered
            deferred = False
            for rid2 in list(self._open_sessions):
                dep = self.records[rid2]
                if rid2 == rid or dep.revoked \
                        or not self._overlaps(rec, dep):
                    continue
                if not dep.root:
                    if rid2 not in self._done:   # neighbour still streaming
                        self._window.hold(rid, self.tick + 1)
                        deferred = True
                        break
                    continue                     # empty session: no leaves
                if not dep.audited:
                    self._audit_full(rid2)
            if deferred or rec.revoked:
                continue
            rec.finalized = True
            self._finalized.add(rid)
            self._open_sessions.discard(rid)
            self.session_log.append({"event": "finalize", "request": rid,
                                     "tick": self.tick})

    def audit_session(self, request_id: int, verifier: int = 0) -> Dict:
        """Spot-check sampled leaves of a session commitment through the
        same batched auditor as training rounds: the sampled (tick,
        token) records are re-digested in one ``leaf_digest_batch`` pass
        and compared against the sealed leaves, then proven against both
        the sealed per-session root AND the batch tick roots the tokens
        were served under.  A mismatch (the served stream was altered
        after commitment) revokes the request: it will never finalize."""
        if not self.verified:
            raise ValueError("engine was not started with a TrustConfig")
        rec = self.records[request_id]
        if not rec.root:
            raise ValueError(f"request {request_id} not sealed yet")
        com = rec.commitment()

        def batch_recompute(experts, slices):
            # honest recompute of a session leaf = re-encoding the served
            # (tick, token) record; leaf i covers batch row i
            rows = [[request_id, rec.ticks[sl.start], rec.tokens[sl.start]]
                    for sl in slices]
            return np.asarray(rows, np.int64)[:, None, :]

        [report] = self._auditors.audit_batched(com, batch_recompute,
                                                verifiers=[verifier])

        def recompute(e: int, sl: slice):
            return np.array([[request_id, rec.ticks[sl.start],
                              rec.tokens[sl.start]]], np.int64)

        # second-layer lottery (reaudit_rate > 0): spot-check this
        # verifier's salted recompute attestations — a rubber-stamping
        # session auditor is slashed out of future lotteries just like a
        # training-round one
        self._auditors.reaudit(com, [report], recompute)
        sampled = report.sampled_leaves
        mismatches = [p.leaf_index for p in report.fraud_proofs]
        # Merkle-path check against the SEALED root: catches a consistent
        # post-seal rewrite of both the record and its leaf digest, which
        # the digest comparison alone (recompute vs current leaf list)
        # cannot see
        tree = MerkleTree(rec.leaves)
        if tree.root != rec.root:
            mismatches = sorted(set(mismatches) | {
                leaf for leaf in sampled
                if not MerkleTree.verify(rec.root, rec.leaves[leaf],
                                         tree.prove(leaf))})
        # inclusion check against the batch tick trees: every sampled
        # leaf must still be the one committed (one append per tick for
        # the whole batch) when its token was served
        if rec.refs and len(rec.refs) == len(rec.leaves):
            bad = verify_session_inclusion(rec.leaves, rec.refs, sampled)
            mismatches = sorted(set(mismatches) | set(bad))
        rec.audited = True
        if mismatches:
            self._revoke_session(request_id, mismatches)
        return {"request": request_id, "sampled": sampled,
                "mismatches": mismatches, "revoked": rec.revoked}

    def _revoke_session(self, request_id: int, mismatches: List[int]) -> None:
        """Revoke a session, then chain the revocation: every session
        whose ticks overlap the revoked stream's and whose window is
        still open is revoked with it — those tokens came out of the
        same batched decode calls as the fraudulent ones, so their
        provenance is void (the per-tick analogue of the training
        pipeline's INVALIDATED descendants; no separate fraud is booked
        for them).  Already-finalized sessions are immune: their windows
        closed clean before the fraud was confirmed."""
        rec = self.records[request_id]
        rec.revoked = True
        rec.finalized = False            # a revoked record is never final
        self._finalized.discard(request_id)
        self._open_sessions.discard(request_id)
        self._window.revoke(request_id)
        self.session_log.append({"event": "revoke", "request": request_id,
                                 "leaves": mismatches})
        for rid in list(self._open_sessions):
            dep = self.records[rid]
            if dep.revoked or dep.finalized or not self._overlaps(rec, dep):
                continue
            dep.revoked = True
            self._finalized.discard(rid)
            self._open_sessions.discard(rid)
            self._window.revoke(rid)
            self.session_log.append({"event": "revoke_dependent",
                                     "request": rid,
                                     "cause": request_id})

    def audit_all(self) -> List[Dict]:
        return [self.audit_session(rid, v)
                for rid in list(self.records)
                if self.records[rid].root
                for v in range(self._auditors.num_verifiers)]
