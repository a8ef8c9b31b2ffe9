"""The B-MoE system (paper §IV): task publisher + edge layer + blockchain
layer + storage layer, running the full Step 1-6 workflow for training
and the Step 1-3 (+6) workflow for inference.

Three frameworks are implemented behind one API:

- ``framework="traditional"``: the paper's baseline — edge i employs
  expert i; no redundancy, no consensus; malicious edges corrupt their
  own expert's results (and the gate must cope on its own, §III).
- ``framework="bmoe"``: every edge computes ALL activated experts
  (redundancy mechanism); the blockchain layer majority-votes the
  per-expert results, aggregates the trusted ones, and records the round
  in a PoW block; updated experts are hash-voted and stored by CID
  (Steps 4-5) during training.
- ``framework="optimistic"``: the commit-challenge-audit protocol from
  ``repro.trust`` — one rotating executor edge computes, commits a
  Merkle root over its per-expert output chunks on-chain, and the round
  is accepted optimistically; a verifier pool spot-checks sampled leaves
  (recompute against the stored expert by CID), confirmed fraud proofs
  slash the executor's stake, feed the reputation ledger, escalate the
  round to the full redundancy vote (the dispute court), and roll the
  round's parameter update back.  Expected verification recompute drops
  from O(M) to O(audit_rate) per round while keeping the same trust
  guarantee up to 1-(1-audit_rate)^k detection.

The numerics (expert compute, manipulation, majority vote, SGD) run as
one jitted step; the ledger/PoW/storage bookkeeping — and, for the
optimistic framework, the commit/audit/slash/rollback machinery — runs
per round in Python, mirroring the paper's on-chain/off-chain split.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import experts as ex
from repro.core.attacks import AttackConfig, round_attack_mask, poison_tree
from repro.core.consensus import ProofOfWork
from repro.core.ledger import Ledger, digest_array, digest_bytes, digest_tree
from repro.core.reputation import ReputationConfig, ReputationLedger, WorkloadBalancer
from repro.obs import Observability
from repro.storage import (ExpertCache, ExpertStore, GateEMA,
                           NetworkCostModel, StorageNetwork)
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.models.moe import capacity_positions
from repro.trust.audit import pack_audit_batch, pack_audit_batch_multi
from repro.trust.commitments import chunk_bounds
from repro.trust.da import DataAvailabilityAuditor
from repro.trust.protocol import (TERMINAL_PHASES, AuditJob,
                                  OptimisticProtocol, RoundPhase,
                                  TrustConfig)


@dataclasses.dataclass(frozen=True)
class BMoEConfig:
    num_experts: int = 10           # N (paper §V)
    num_edges: int = 10             # M
    top_k: int = 3                  # K
    expert_kind: str = "mlp"        # mlp (fmnist) | cnn (cifar)
    in_dim: int = 784
    in_ch: int = 1
    num_classes: int = 10
    lr: float = 0.01
    framework: str = "bmoe"         # bmoe | traditional | optimistic
    # execution model of the expert layer (paper §II: sparse gating
    # "lowers computational overhead"):
    # - "sparse" (default): top-k scatter-dispatch into per-expert
    #   capacity buckets + grouped GEMM (ops.moe_gemm route) + gather-
    #   combine — expert compute scales with top_k/num_experts;
    # - "dense": every expert over the full batch (the pre-sparse
    #   reference oracle; top-k gating only zeroes combine weights).
    dispatch: str = "sparse"
    capacity_factor: float = 1.25   # bucket slots per expert, as a
    #                                 multiple of the balanced share
    #                                 B*top_k/num_experts (overflow drops)
    # device-mesh execution (the distributed edge network made real):
    # "on" runs every round's jitted step under an edge mesh
    # (launch.mesh.make_edge_mesh) — the expert bank is sharded so each
    # simulated edge device owns an E/msize slice, sparse dispatch
    # crosses shards via all_to_all (wire bytes per device independent
    # of E), and the trust layer goes shard-local: each edge hashes only
    # its own buckets (root = Merkle reduction over shard roots) and
    # audit recompute runs on the owning shard.  Outputs, commitments,
    # audit verdicts, and rollback replays are BIT-IDENTICAL to the
    # "off" single-device oracle (tests/test_mesh_bmoe.py).
    mesh: str = "off"               # on | off
    mesh_shards: Optional[int] = None  # edge devices (None: widest fit)
    attack: AttackConfig = dataclasses.field(default_factory=AttackConfig)
    pow_difficulty: int = 8
    num_chain_nodes: int = 8
    bandwidth_bytes_per_s: float = 125e6   # 1 Gbps edge links
    # chunked storage / edge cache (repro.storage): every round uploads
    # the *changed* experts as a new chunk-manifest version (chunk-level
    # dedup against the previous version) and the edge resolves the
    # round's bank through a bounded LRU ExpertCache instead of keeping
    # the whole bank resident.  "off" keeps the bank in memory — the
    # pre-cache oracle (bit-identical outputs, pinned in
    # tests/test_expert_cache.py).
    edge_cache: str = "on"          # on | off
    edge_cache_bytes: Optional[int] = None  # cache byte budget (None: unbounded)
    chunk_bytes: int = 1 << 16      # storage chunk size
    prefetch_topk: int = 0          # EMA-prefetch this many hot experts
    num_storage_nodes: int = 4
    storage_replication: int = 2
    # data-availability challenges (repro.trust.da): per-chunk sampling
    # rate at which replica nodes are challenged to produce committed
    # chunks each optimistic round; a withheld chunk past the challenge
    # window slashes the storage node (da_slash ledger block)
    da_rate: float = 0.05
    seed: int = 0
    # paper §VI extensions (see repro.core.reputation)
    reputation: Optional[ReputationConfig] = None       # §VI-B/D
    workload_balance: bool = False                      # §VI-C
    balance_eta: float = 0.5
    # optimistic framework knobs (see repro.trust)
    trust: Optional[TrustConfig] = None


class BMoESystem:
    """One instantiation of Fig. 3. See module docstring."""

    # phase-seconds metrics behind the legacy ``_timers`` keys: every
    # wall-clock second the system books flows through a span into the
    # obs registry, and the old dict is a read-only view of it
    _TIMER_METRICS = {"compute": "bmoe.compute_s",
                      "consensus": "bmoe.consensus_s",
                      "chain": "bmoe.chain_s",
                      "audit": "bmoe.audit_s",
                      "audit_infer": "bmoe.audit_infer_s",
                      "storage": "bmoe.storage_s"}

    def __init__(self, cfg: BMoEConfig, obs: Optional[Observability] = None):
        self.cfg = cfg
        # the one observability bundle of the run: every layer below
        # (storage network/store/cache, trust protocols, DA auditor)
        # records into its registry, and spans opened here mark the
        # round phases on its tracer.  Default: tracing off, metrics on.
        self.obs = obs if obs is not None else Observability()
        key = jax.random.PRNGKey(cfg.seed)
        kg, ke = jax.random.split(key)
        gate_in = cfg.in_dim if cfg.expert_kind == "mlp" else 32 * 32 * cfg.in_ch
        from repro.models.builder import materialize
        self.gate = materialize(ex.gate_decl(gate_in, cfg.num_experts), kg)
        self.experts, self._apply_all = ex.make_expert_bank(
            cfg.expert_kind, cfg.num_experts, ke, in_dim=cfg.in_dim,
            in_ch=cfg.in_ch, out=cfg.num_classes)
        self._apply_grouped = ex.grouped_apply_fn(cfg.expert_kind)
        # mesh execution (see BMoEConfig.mesh): shard the expert bank
        # over the edge mesh's model axis so each simulated edge device
        # owns a contiguous E/msize expert slice; the jitted steps then
        # run the all_to_all dispatch path (_mesh_sparse_forward)
        self.device_mesh = None
        self.mesh_shards = 1
        self._bank_sharding = None
        if cfg.mesh == "on":
            if cfg.dispatch != "sparse":
                raise ValueError(
                    "mesh='on' runs the all_to_all sparse dispatch; dense "
                    "dispatch has no per-expert buckets to exchange — set "
                    "dispatch='sparse'")
            from jax.sharding import PartitionSpec
            from repro.launch.mesh import make_edge_mesh
            from repro.sharding import Sharder
            self.device_mesh = make_edge_mesh(cfg.num_experts,
                                              shards=cfg.mesh_shards)
            axes = dict(zip(self.device_mesh.axis_names,
                            self.device_mesh.devices.shape))
            self.mesh_shards = axes["model"]
            sharder = Sharder(self.device_mesh, rules={"experts": "model"})
            self._bank_sharding = sharder.named(PartitionSpec("model"))
            self.experts = jax.device_put(self.experts, self._bank_sharding)
        self.ledger = Ledger()
        self.storage = StorageNetwork(
            num_nodes=cfg.num_storage_nodes,
            replication=cfg.storage_replication, seed=cfg.seed,
            cost=NetworkCostModel(
                bandwidth_bytes_per_s=cfg.bandwidth_bytes_per_s),
            metrics=self.obs.metrics)
        # the storage layer proper: versioned per-expert chunk manifests
        # (version v = the bank state entering round v; only changed
        # experts re-upload, and unchanged chunks dedup away), plus the
        # edge-side cache the executor resolves activated experts through
        self.expert_store = ExpertStore(self.storage,
                                        chunk_bytes=cfg.chunk_bytes,
                                        metrics=self.obs.metrics)
        self.edge_cache = (ExpertCache(self.expert_store,
                                       cfg.edge_cache_bytes,
                                       metrics=self.obs.metrics)
                           if cfg.edge_cache == "on" else None)
        self.gate_ema = GateEMA(cfg.num_experts)
        self._expert_like = jax.tree_util.tree_map(
            lambda a: np.asarray(a[0]), self.experts)
        self._bank_version = -1
        self._resolved_bank = None      # device bank memo, keyed by the
        self._resolved_key = None       # resolved manifest cids
        self._publish_bank(None, 0)     # genesis bank: every expert, v0
        self.pow = ProofOfWork(cfg.num_chain_nodes,
                               difficulty_bits=cfg.pow_difficulty,
                               seed=cfg.seed)
        self.round = 0
        if cfg.framework == "optimistic" and cfg.reputation is None:
            # exclusion of slashed executors needs a reputation ledger
            self.reputation = ReputationLedger(cfg.num_edges,
                                               ReputationConfig())
        else:
            self.reputation = (ReputationLedger(cfg.num_edges, cfg.reputation)
                               if cfg.reputation else None)
        self.balancer = (WorkloadBalancer(cfg.num_experts, cfg.balance_eta)
                         if cfg.workload_balance else None)
        self.activation_counts = np.zeros(cfg.num_experts)
        self.activation_total = 0
        # manifest CIDs of the expert versions each open optimistic round
        # committed against — retained in the store while the round's
        # challenge window is open (the data-availability contract) and
        # released once it closes (superseded versions are then GC'd)
        self._audit_cids: Dict[int, List[str]] = {}
        # pipelined-scheduling state: per-pending-round snapshots (the
        # (gate, experts) the executor was handed, the task, and the keys
        # needed to replay the round honestly after a chained rollback)
        self._round_ctx: Dict[int, Dict] = {}
        # batch-inference pipeline (lazily created on the first optimistic
        # infer): its own round clock, shared stakes/court/reputation
        self._infer_protocol: Optional[OptimisticProtocol] = None
        self._infer_round = 0
        self._infer_ctx: Dict[int, Dict] = {}
        self._infer_audit_cids: Dict[int, List[str]] = {}
        self.infer_log: List[Dict] = []
        # "audit" (bmoe.audit_s) collects verifier recompute/hash/fetch
        # seconds drained under pipelined scheduling: work that
        # deployment runs on the verifier pool concurrently with later
        # rounds, i.e. OFF the round loop's critical path — the drain
        # span is opened ``off_path=True``, so every enclosing phase
        # metric (consensus) natively excludes it.  Synchronous
        # scheduling keeps audits on the critical path, inside
        # "consensus".
        # "audit_infer" keeps the inference pipeline's drains out of the
        # per-training-round latency decomposition
        # "storage": expert-version publication + edge-cache bank
        # resolution seconds (host wall-clock; the *modeled* transfer
        # time lives in storage_report(), on the network cost model)
        for name in self._TIMER_METRICS.values():
            self.obs.metrics.counter(name)
        self.obs.metrics.counter("bmoe.round_s")
        # verification-compute ledger, in units of (expert evaluations x
        # samples): base = the one canonical execution, verify = recompute
        # done purely to check it (redundant copies / audits), escalate =
        # dispute-court full votes.  The jitted simulation broadcasts
        # instead of physically recomputing, so cost is counted, not timed.
        self.verify_stats = {"base_evals": 0.0, "verify_evals": 0.0,
                             "escalate_evals": 0.0, "rounds": 0}
        self.trust_cfg: Optional[TrustConfig] = None
        self.protocol: Optional[OptimisticProtocol] = None
        self.da: Optional[DataAvailabilityAuditor] = None
        if cfg.framework == "optimistic":
            self.trust_cfg = cfg.trust or TrustConfig(seed=cfg.seed)
            self.protocol = OptimisticProtocol(self.trust_cfg, cfg.num_edges,
                                               self.reputation,
                                               metrics=self.obs.metrics,
                                               namespace="trust.train")
            if cfg.da_rate > 0:
                # storage nodes post their own bonds: a replica that
                # cannot produce a committed chunk inside the challenge
                # window is slashed (see repro.trust.da)
                self.da = DataAvailabilityAuditor(
                    self.storage, num_nodes=cfg.num_storage_nodes,
                    window=self.trust_cfg.challenge_window,
                    sample_rate=cfg.da_rate, seed=cfg.seed,
                    metrics=self.obs.metrics)
            self._apply_one = (ex.mlp_expert_apply if cfg.expert_kind == "mlp"
                               else ex.cnn_expert_apply)
            # one grouped jitted call recomputes every sampled (expert,
            # chunk) pair of a round: the mlp bank routes through the
            # audit kernel (Pallas on TPU, bit-identical gathered-vmap
            # ref on CPU); other expert kinds use the generic gather
            if cfg.expert_kind == "mlp":
                self._batched_recompute_call = jax.jit(
                    lambda bank, xd, idx, gid:
                        kops.audit_mlp(bank, xd[idx], gid))
            else:
                def _gather_apply(bank, xd, idx, gid):
                    p = jax.tree_util.tree_map(lambda a: a[gid], bank)
                    return jax.vmap(self._apply_one)(p, xd[idx])
                self._batched_recompute_call = jax.jit(_gather_apply)
        if self.mesh_shards > 1 and self.trust_cfg is not None:
            # shard-local commitments reduce shard subtree roots into the
            # flat round root; the reduction is bit-identical only when
            # each shard's subtree is a complete subtree of the flat
            # tree, i.e. leaves per shard is a power of two
            lps = (cfg.num_experts // self.mesh_shards) \
                * self.trust_cfg.chunks_per_expert
            if lps & (lps - 1):
                raise ValueError(
                    f"shard-local commitments need a power-of-two leaf "
                    f"count per edge: (num_experts/mesh_shards) * "
                    f"chunks_per_expert = ({cfg.num_experts}/"
                    f"{self.mesh_shards}) * "
                    f"{self.trust_cfg.chunks_per_expert} = {lps}; adjust "
                    f"mesh_shards or TrustConfig.chunks_per_expert")
        self._train_step = jax.jit(functools.partial(
            _train_step, cfg=cfg, apply_all=self._apply_all,
            apply_grouped=self._apply_grouped, mesh=self.device_mesh,
            mesh_shards=self.mesh_shards))
        self._infer_step = jax.jit(functools.partial(
            _infer_step, cfg=cfg, apply_all=self._apply_all,
            apply_grouped=self._apply_grouped, mesh=self.device_mesh,
            mesh_shards=self.mesh_shards))
        # host-side routing re-derivation for sparse commitments: the
        # committed routing indices are what let auditors re-build the
        # exact capacity buckets the executor filled
        self._routing_call = jax.jit(functools.partial(_route_for_commit,
                                                       cfg=cfg))

    # ------------------------------------------------------------ api
    def train_round(self, x, y, *, attack: Optional[AttackConfig] = None):
        """One full Step 1-6 round on one published task (batch)."""
        cfg = self.cfg
        atk = attack if attack is not None else cfg.attack
        with self.obs.span("round-setup", round=self.round):
            rkey = jax.random.fold_in(jax.random.PRNGKey(cfg.seed + 17),
                                      self.round)
            mask_e = round_attack_mask(atk, cfg.num_edges, rkey)
            executor = (self.protocol.pick_executor(self.round)
                        if cfg.framework == "optimistic" else 0)
            gate_bias, active = self._controls()
        # the round span carries the on-path round seconds (off-path
        # audit drains nested below are excluded natively); every phase
        # below is its child, so one traced round decomposes into
        # fetch -> dispatch -> [publish/consensus/chain] spans whose
        # metric sums are exactly the legacy latency_report components
        with self.obs.span("round", metric="bmoe.round_s",
                           round=self.round, kind="train",
                           framework=cfg.framework, executor=executor):
            # Step 2 (storage -> edge): the executor edge resolves this
            # round's bank through its cache — activated experts pinned
            # and refreshed at the committed version, misses fetched
            # chunk-by-chunk from the storage layer (bit-identical to the
            # resident bank: pinned in tests/test_expert_cache.py)
            with self.obs.span("fetch", metric="bmoe.storage_s",
                               round=self.round):
                bank = self._resolve_bank(x, gate_bias)
            prev = (self.gate, bank)

            with self.obs.span("dispatch", metric="bmoe.compute_s",
                               round=self.round):
                (self.gate, self.experts, metrics) = self._train_step(
                    self.gate, bank, x, y, mask_e,
                    jax.random.fold_in(rkey, 1), atk.noise_std,
                    jnp.asarray(atk.colluding), gate_bias, active,
                    jnp.int32(executor))
                metrics = jax.tree_util.tree_map(np.asarray, metrics)
            with self.obs.span("bookkeeping", round=self.round):
                self.gate_ema.update(metrics["activation"])
                batch = int(x.shape[0])
                payload = {
                    "round": self.round, "kind": "train",
                    "task": digest_array(np.asarray(x)[:8]),
                    "loss": float(metrics["loss"]),
                }
                # cost ledger in expert-evaluation units (one unit = one
                # expert evaluated on one row of what it actually
                # computes: the full batch under dense dispatch, its
                # capacity bucket under sparse — the optimistic
                # commitment covers exactly that buffer), so
                # base/verify/escalate are all measured with the same
                # yardstick
                self.verify_stats["rounds"] += 1
                if cfg.framework == "traditional":
                    self.verify_stats["base_evals"] += cfg.top_k * batch
                else:
                    self.verify_stats["base_evals"] += \
                        self._exec_evals(batch)
            if cfg.framework != "optimistic":
                # Step 5, chunked: publish the updated experts as new
                # manifest versions (only routed experts changed;
                # unchanged chunks dedup away).  The optimistic path
                # publishes after its commit/audit bookkeeping instead —
                # round r's audits must be able to retain the version-r
                # manifests first.
                with self.obs.span("publish", metric="bmoe.storage_s",
                                   round=self.round):
                    self._publish_bank(metrics["activation"],
                                       self.round + 1)
                payload["bank_root"] = self._bank_root()[:16]
            if cfg.framework == "bmoe":
                # the redundancy mechanism IS the verification: M-1 extra
                # copies of the same execution
                self.verify_stats["verify_evals"] += \
                    (cfg.num_edges - 1) * self._exec_evals(batch)
                # Step 4-5: edges vote on the updated experts' hashes;
                # the accepted bank's storage root is in the payload.
                with self.obs.span("consensus", metric="bmoe.consensus_s",
                                   round=self.round):
                    payload["trusted_supports"] = \
                        metrics["support"].tolist()
                    self._expert_hash_vote(atk, rkey, payload)
                # Step 6: block generation under PoW.
                with self.obs.span("chain", metric="bmoe.chain_s",
                                   round=self.round):
                    self._mine(payload)
            elif cfg.framework == "optimistic":
                # commit -> optimistic accept -> async audit -> maybe
                # rollback.  The pipelined audit drain inside opens an
                # off_path span, so its seconds land in bmoe.audit_s and
                # are excluded from this consensus span's metric — the
                # span algebra that replaced the old hand subtraction.
                with self.obs.span("consensus", metric="bmoe.consensus_s",
                                   round=self.round):
                    metrics = self._optimistic_round(
                        x, y, atk, mask_e, rkey, executor, prev, metrics,
                        payload, gate_bias, active)
                payload["loss"] = float(metrics["loss"])
                with self.obs.span("publish", metric="bmoe.storage_s",
                                   round=self.round):
                    if not payload.get("rolled_back"):
                        # a rolled-back round's honest replay already
                        # republished the voided versions (including
                        # this round's successor)
                        self._publish_bank(metrics["activation"],
                                           self.round + 1)
                payload["bank_root"] = self._bank_root()[:16]
                with self.obs.span("chain", metric="bmoe.chain_s",
                                   round=self.round):
                    self._mine(payload)
            with self.obs.span("bookkeeping", round=self.round):
                self._update_controllers(metrics)
                self.activation_counts += metrics["activation"]
                self.activation_total += batch * cfg.top_k
                self.round += 1
        return metrics

    def infer(self, x, *, attack: Optional[AttackConfig] = None,
              commit: bool = True):
        """Steps 1-3 (+6): forward only, no updates (paper: 4-5 skipped).

        Under ``framework="optimistic"`` batch inference runs through the
        same commit-challenge-audit pipeline as training rounds, at batch
        granularity: a rotating executor's claimed per-expert outputs are
        Merkle-committed, the logits are returned immediately (the
        optimistic view), and the audit drains off the critical path on a
        separate inference round clock (shared stake book/court — an
        inference conviction slashes and excludes the executor from BOTH
        rotations).  ``pending_inference()`` lists rounds still inside
        their window; ``infer_log`` records commits/revocations;
        ``flush_trust()`` settles everything.  A corrupted round is
        caught w.p. 1-(1-audit_rate)^k ~= 1 for full-tensor corruption.

        ``commit=False`` is a side-effect-free probe of the finalized
        (honest) view: no commitment, no audit round, no shared-state
        mutation — what ``evaluate`` uses, so measuring accuracy never
        perturbs the trust experiment.  The per-tick protocol for
        streaming inference lives in ``ServingEngine`` verified
        sessions.
        """
        cfg = self.cfg
        atk = attack if attack is not None else cfg.attack
        rkey = jax.random.fold_in(jax.random.PRNGKey(cfg.seed + 91),
                                  self.round + 1_000_000)
        gate_bias, active = self._controls()
        if cfg.framework != "optimistic" or not commit:
            # non-optimistic frameworks serve their (possibly attacked)
            # consensus view; the optimistic probe serves the finalized
            # honest view (corruption would be audited out anyway)
            mask_e = (round_attack_mask(atk, cfg.num_edges, rkey)
                      if cfg.framework != "optimistic"
                      else jnp.zeros(cfg.num_edges, jnp.float32))
            bank = self._resolve_bank(x, gate_bias)
            logits, activation, support = self._infer_step(
                self.gate, bank, x, mask_e,
                jax.random.fold_in(rkey, 1), atk.noise_std,
                jnp.asarray(atk.colluding), gate_bias, active, jnp.int32(0))
            return (np.asarray(logits), np.asarray(activation),
                    np.asarray(support))

        proto = self._ensure_infer_protocol()
        rid = self._infer_round
        self._infer_round += 1
        # each inference round draws its own attack lottery — without
        # folding in rid, back-to-back infer() calls would replay one
        # perfectly correlated mask and void the per-round-independent
        # detection bound
        rkey = jax.random.fold_in(rkey, rid)
        mask_e = round_attack_mask(atk, cfg.num_edges, rkey)
        executor = proto.pick_executor(rid)
        # trace-only spans (no phase metric: the legacy decomposition
        # never booked inference wall-clock outside the audit drains) —
        # a traced run still sees the full fetch/dispatch/commit shape
        with self.obs.span("infer-round", round=rid, kind="infer",
                           executor=executor):
            with self.obs.span("fetch", round=rid):
                bank = self._resolve_bank(x, gate_bias)
            version = self._bank_version
            with self.obs.span("dispatch", round=rid):
                logits, activation, support = self._infer_step(
                    self.gate, bank, x, mask_e, jax.random.fold_in(rkey, 1),
                    atk.noise_std, jnp.asarray(atk.colluding), gate_bias,
                    active, jnp.int32(executor))
            self.gate_ema.update(np.asarray(activation))
            xin = np.asarray(x if cfg.expert_kind == "cnn"
                             else np.asarray(x).reshape(len(x), -1))
            row_index, bounds = self._commitment_layout(
                self.gate, x, xin.shape[0], gate_bias)
            tc = self.trust_cfg
            with self.obs.span("commit", round=rid,
                               executor=executor) as csp:
                honest = self._eager_outputs(bank, xin, bounds, row_index)
                attacked = bool(np.asarray(mask_e)[executor] > 0)
                state = self._commit_round(proto, rid, executor, honest,
                                           attacked, atk, 1_000_000 + rid,
                                           digest_array(xin[:8]), row_index)
                csp.set(root=state.commitment.root[:16])
        # data-availability contract: the versions this inference round
        # committed against stay retained until its window closes
        manifests = self._retain_round_manifests(version)
        self._infer_audit_cids[rid] = manifests
        self._infer_ctx[rid] = {
            "prev": (self.gate, bank), "xin": xin, "honest": honest,
            "executor": executor, "mask_e": np.asarray(mask_e), "atk": atk,
            "active": active, "manifests": manifests,
        }
        recompute_fn = self._make_recompute(xin, manifests, row_index)
        batch_fn = (self._make_batched_recompute(bank, xin, manifests,
                                                 row_index)
                    if tc.audit_backend == "batched" else None)
        proto.schedule_audit(rid, recompute_fn, batch_fn)
        self.infer_log.append({"event": "commit", "round": rid,
                               "executor": executor,
                               "root": state.commitment.root[:16]})

        drain_now = None if tc.scheduling == "synchronous" else rid
        summary = self._drain_trust(proto, self._infer_ctx,
                                    self._infer_audit_cids, drain_now,
                                    "infer")
        self._record_infer_verdicts(summary)
        for frid in proto.advance(rid):
            self.infer_log.append({"event": "finalize", "round": frid})
        self._prune_closed_rounds(proto, self._infer_ctx,
                                  self._infer_audit_cids)
        return np.asarray(logits), np.asarray(activation), np.asarray(support)

    def evaluate(self, x, y, *, attack: Optional[AttackConfig] = None,
                 batch: int = 1000) -> float:
        correct = 0
        for i in range(0, len(x), batch):
            # commit=False: an accuracy probe must not mint inference
            # rounds, pay commitments, or slash anyone
            logits, _, _ = self.infer(x[i:i + batch], attack=attack,
                                      commit=False)
            correct += int((logits.argmax(-1) == np.asarray(y[i:i + batch])).sum())
        return correct / len(x)

    def _controls(self):
        cfg = self.cfg
        gate_bias = jnp.asarray(self.balancer.bias) if self.balancer \
            else jnp.zeros(cfg.num_experts, jnp.float32)
        if self.reputation is not None:
            active = jnp.asarray(
                (~self.reputation.excluded).astype(np.float32))
        else:
            active = jnp.ones(cfg.num_edges, jnp.float32)
        return gate_bias, active

    def _update_controllers(self, metrics):
        if self.balancer is not None:
            self.balancer.update(metrics["activation"])
        # optimistic rounds feed reputation through confirmed fraud proofs
        # (slashing), not per-round agreement flags
        if (self.reputation is not None and "flags" in metrics
                and self.cfg.framework != "optimistic"):
            self.reputation.update_from_flags(metrics["flags"])

    @property
    def activation_ratio(self) -> np.ndarray:
        return self.activation_counts / max(self.activation_total, 1)

    # -------------------------------------------------------- internals
    def _expert_hash_vote(self, atk: AttackConfig, rkey, payload):
        """Paper Step 5: each edge uploads the updated experts' hashes; the
        chain accepts the majority; poisoned uploads are rejected."""
        cfg = self.cfg
        honest_digest = digest_tree(self.experts)
        uploads = []
        for m in range(cfg.num_edges):
            if atk.poison_params and m in atk.malicious_edges:
                poisoned = poison_tree(self.experts,
                                       jax.random.fold_in(rkey, 100 + (0 if
                                       atk.colluding else m)),
                                       atk.noise_std)
                uploads.append(digest_tree(poisoned))
            else:
                uploads.append(honest_digest)
        counts: Dict[str, int] = {}
        for d in uploads:
            counts[d] = counts.get(d, 0) + 1
        winner = max(counts, key=counts.get)
        payload["expert_hash"] = winner[:16]
        payload["expert_hash_support"] = counts[winner]
        payload["expert_hash_accepted"] = counts[winner] * 2 > cfg.num_edges
        if winner != honest_digest and payload["expert_hash_accepted"]:
            # majority is malicious: chain is misled (paper §IV-B, >50%)
            payload["chain_misled"] = True
        # Step 5 storage happens per round through the versioned chunk
        # store (``_publish_bank``); the block's ``bank_root`` already
        # binds the accepted bank's per-expert manifest roots on-chain.

    def _mine(self, payload):
        tr = self.obs.trace
        if tr.enabled:
            # block -> trace correlation (see trust/README.md): every
            # block mined while tracing names the trace and the innermost
            # open span it was mined under.  Only when tracing — a
            # disabled run's payloads (and so its block hashes) stay
            # bit-identical to the pre-obs chain.
            payload["trace_id"] = tr.trace_id
            payload["span_id"] = tr.current_span_id()
        block = self.pow.mine(len(self.ledger.blocks), self.ledger.head.hash,
                              payload)
        self.ledger.append(block)

    def _exec_evals(self, batch: int) -> float:
        """Expert-evaluation cost of one canonical execution: every
        expert over the full batch (dense) or over its capacity bucket
        (sparse — the grouped GEMM's real row count, padding included)."""
        cfg = self.cfg
        rows = (sparse_capacity(cfg, batch) if cfg.dispatch == "sparse"
                else batch)
        return cfg.num_experts * rows

    # ----------------------------------------------------- storage layer
    @staticmethod
    def _object_id(e: int) -> str:
        return f"expert/{e}"

    def _activated_experts(self, x, gate_bias) -> List[int]:
        """The experts the gate routes this batch to — what the edge must
        hold current versions of before computing.  The rest of the bank
        is provably unchanged on-storage: an unrouted expert's combine
        weight is zero everywhere, so it receives zero gradient and its
        previous version still serves (pinned in
        tests/test_expert_cache.py)."""
        eid, _, _ = self._routing_call(self.gate, x, gate_bias)
        return [int(e) for e in np.unique(np.asarray(eid))]

    def _resolve_bank(self, x, gate_bias):
        """Edge-side bank resolution (paper: the edge layer "employs the
        activated experts downloaded from the storage layer"): activated
        experts are pinned and resolved at the current version through
        the bounded ``ExpertCache`` — a miss or a stale entry fetches the
        expert chunk-by-chunk (CID-verified) from the storage network.
        The assembled device bank is memoized on the resolved manifest
        CIDs, so repeated inference against an unchanged bank costs no
        transfer and no re-stack.  ``edge_cache="off"`` keeps the bank
        resident — the pre-cache oracle, bit-identical by construction
        (the chunk round-trip preserves every byte)."""
        if self.edge_cache is None:
            return self.experts
        cfg, cache = self.cfg, self.edge_cache
        version = self._bank_version
        ids = [self._object_id(e)
               for e in self._activated_experts(x, gate_bias)]
        cache.pin(ids)
        try:
            if cfg.prefetch_topk:
                hot = [self._object_id(e)
                       for e in self.gate_ema.ranking()[:cfg.prefetch_topk]]
                cache.prefetch(hot, version, lambda oid: self._expert_like)
            rows = [cache.get(self._object_id(e), version,
                              self._expert_like)
                    for e in range(cfg.num_experts)]
        finally:
            cache.unpin(ids)
        key = tuple(
            self.expert_store.manifest_cid(self._object_id(e), version)
            for e in range(cfg.num_experts))
        if key != self._resolved_key:
            # host-side stack first, ONE device put per leaf — straight
            # into the edge-shard layout under mesh execution
            put = (functools.partial(jax.device_put,
                                     device=self._bank_sharding)
                   if self._bank_sharding is not None else jnp.asarray)
            self._resolved_bank = jax.tree_util.tree_map(
                lambda *ls: put(np.stack(ls)), *rows)
            self._resolved_key = key
        return self._resolved_bank

    def _publish_bank(self, activation, version: int) -> None:
        """Step 5, chunked: upload a new manifest version for every
        expert the round routed to (``activation=None``: the whole bank —
        genesis).  Unchanged chunks of a changed expert dedup away inside
        ``put_version``; untouched experts keep serving from their
        previous version."""
        cfg = self.cfg
        changed = (list(range(cfg.num_experts)) if activation is None else
                   [int(e) for e in
                    np.nonzero(np.asarray(activation) > 0)[0]])
        if not changed:
            self._bank_version = max(self._bank_version, version)
            return
        if len(changed) > 2:
            # one device->host transfer for the whole bank, slice in host
            # memory (beats a per-expert gather dispatch per leaf)
            host = jax.tree_util.tree_map(np.asarray, self.experts)
            pick = lambda a, e: a[e]
        else:
            host = self.experts
            pick = lambda a, e: np.asarray(a[e])
        for e in changed:
            tree_e = jax.tree_util.tree_map(lambda a: pick(a, e), host)
            self.expert_store.put_version(self._object_id(e), tree_e,
                                          version)
        self._bank_version = max(self._bank_version, version)

    def _bank_root(self) -> str:
        """One digest binding the current bank's per-expert manifest
        roots — the storage commitment a round's block records."""
        roots = "".join(
            self.expert_store.manifest(self._object_id(e),
                                       self._bank_version).root
            for e in range(self.cfg.num_experts))
        return digest_bytes(roots.encode())

    def _fetch_expert_manifest(self, manifest_cid: str):
        """Auditor-side fetch: the exact expert version a round
        committed against, named by its retained manifest CID (NOT a
        version-number lookup — a chained-rollback replay republishes
        voided version tags, and an open round's auditors must keep
        fetching what was actually committed).  Every chunk is
        CID-verified (a corrupted replica is skipped — verified refetch
        from a healthy one) and reassembled chunk-for-chunk."""
        return self.expert_store.fetch_manifest(
            self.expert_store.manifest_by_cid(manifest_cid),
            self._expert_like)

    def _retain_round_manifests(self, version: int) -> List[str]:
        """Pin the manifests a round committed against for the length of
        its challenge window (the data-availability contract: auditors
        must be able to fetch them until the round is terminal)."""
        cids = []
        for e in range(self.cfg.num_experts):
            cid = self.expert_store.manifest_cid(self._object_id(e),
                                                 version)
            self.expert_store.retain(cid)
            cids.append(cid)
        return cids

    def _run_da(self, now: Optional[int],
                manifest_cids: Optional[List[str]] = None) -> None:
        """One data-availability beat: challenge replica nodes for
        sampled chunks of the given manifests, close past-due challenges
        (``now=None``: all), and mine one ``da_slash`` block per
        confirmed fault (withheld past the window, or a corrupted
        replica — the latter also repaired by verified refetch)."""
        if self.da is None:
            return
        n = len(self.da.faults)
        if manifest_cids:
            manifests = {}
            for cid in manifest_cids:
                man = self.expert_store.manifest_by_cid(cid)
                manifests[man.object_id] = man
            self.da.challenge_round(now, manifests)
        self.da.resolve(now)
        for f in self.da.faults[n:]:
            self._mine({"kind": "da_slash", "node": f.executor,
                        "object": f.object_id, "chunk": f.chunk_index,
                        "cid": f.cid[:16], "fault": f.kind,
                        "challenged_round": f.round_id})

    def storage_report(self) -> Dict:
        """Byte/transfer economy of the storage layer: network counters
        (with *modeled* transfer seconds on the deterministic cost
        model), chunk-dedup upload savings, edge-cache hit/miss/byte
        counters, DA challenge stats, and the host wall-clock spent on
        storage bookkeeping.  A thin view over ``obs_report()`` — every
        number is a live registry metric; keys unchanged from pre-obs."""
        return self.obs_report()["storage"]

    # ------------------------------------------- optimistic verification
    def _sparse_routing(self, gate, x, gate_bias):
        """Re-derive the round's routing from the snapshot state and
        build the ``(N, capacity)`` bucket->task-row index the executor
        publishes with a sparse commitment.  Empty slots point one past
        the batch (the zero sentinel row auditors append to the task),
        so a leaf recompute is a pure gather + grouped apply."""
        cfg = self.cfg
        eid, pos, keep = (np.asarray(a) for a in
                          self._routing_call(gate, x, gate_bias))
        batch = len(x)
        capacity = sparse_capacity(cfg, batch)
        row_index = np.full((cfg.num_experts, capacity), batch, np.int32)
        tok = np.repeat(np.arange(batch, dtype=np.int32), cfg.top_k)
        row_index[eid[keep], pos[keep]] = tok[keep]
        return row_index, capacity

    @staticmethod
    def _pad_task(xin, row_index):
        """The auditors' task view: under sparse dispatch, the batch plus
        one trailing zero row (what empty bucket slots recompute from)."""
        if row_index is None:
            return xin
        return np.concatenate([xin, np.zeros_like(xin[:1])], axis=0)

    def _eager_outputs(self, experts, xin, bounds, row_index=None):
        """The executor's commitment-building pass: every expert's output
        computed through the same recompute path the auditors use, so
        honest leaves recompute bit-identically.  For the mlp bank every
        (expert, chunk) leaf goes through ONE grouped ``audit_mlp`` call
        (the auditors' own kernel); other expert kinds fall back to the
        per-expert chunked apply.  With ``row_index`` (sparse dispatch)
        the chunks tile each expert's capacity bucket and the task rows
        come from the committed routing, so the pass computes — and the
        commitment covers — only the bucketed buffers."""
        cfg = self.cfg
        n_chunks = len(bounds) - 1
        xpad = self._pad_task(xin, row_index)
        if cfg.expert_kind == "mlp" and self.protocol is not None:
            slices = [slice(bounds[c], bounds[c + 1])
                      for c in range(n_chunks)]
            if self.mesh_shards > 1:
                # shard-local commitment building: each edge recomputes
                # (and will hash) only its own expert buckets — one
                # grouped call per edge over its local (E_l, capacity, C)
                # slice.  Per-sample arithmetic is identical to the
                # single-call path, so the assembled tensor (and every
                # leaf digest) is bitwise the oracle's.
                e_l = cfg.num_experts // self.mesh_shards
                xd = jnp.asarray(xpad)
                work = [(e, sl) for e in range(e_l) for sl in slices]
                parts = []
                for s in range(self.mesh_shards):
                    bank_s, xd_s = self._on_shard(s, jax.tree_util.tree_map(
                        lambda a: a[s * e_l:(s + 1) * e_l], experts), xd)
                    rmap = (None if row_index is None
                            else row_index[s * e_l:(s + 1) * e_l])
                    idx, gid, n = pack_audit_batch(
                        [e for e, _ in work], [sl for _, sl in work],
                        row_map=rmap)
                    out = np.asarray(self._batched_recompute_call(
                        bank_s, xd_s, jnp.asarray(idx),
                        jnp.asarray(gid)))[:n]
                    parts.extend(np.concatenate(
                        [out[e * n_chunks + c][:bounds[c + 1] - bounds[c]]
                         for c in range(n_chunks)], axis=0)
                        for e in range(e_l))
                return np.stack(parts)
            work = [(e, sl) for e in range(cfg.num_experts)
                    for sl in slices]            # (e, c) row-major = leaf order
            idx, gid, n = pack_audit_batch([e for e, _ in work],
                                           [sl for _, sl in work],
                                           row_map=row_index)
            out = np.asarray(self._batched_recompute_call(
                experts, jnp.asarray(xpad), jnp.asarray(idx),
                jnp.asarray(gid)))[:n]
            parts = [np.concatenate(
                [out[e * n_chunks + c][:bounds[c + 1] - bounds[c]]
                 for c in range(n_chunks)], axis=0)
                for e in range(cfg.num_experts)]
            return np.stack(parts)
        parts = []
        for e in range(cfg.num_experts):
            p_e = jax.tree_util.tree_map(lambda a: a[e], experts)
            chunks = [np.asarray(self._apply_one(
                p_e, jnp.asarray(xpad[bounds[c]:bounds[c + 1]]
                                 if row_index is None
                                 else xpad[row_index[e,
                                                     bounds[c]:bounds[c + 1]]])))
                for c in range(n_chunks)]
            parts.append(np.concatenate(chunks, axis=0))
        return np.stack(parts)

    def _make_recompute(self, xin, manifests: List[str], row_index=None):
        """Auditor-side recompute: fetch the sampled expert from the
        storage layer by the *manifest the round committed against*
        (``manifests[e]`` — the CID list retained at commit, whose roots
        are bound on-chain; every chunk is CID-verified, so a tampered
        replica is self-evident and skipped) and recompute the audited
        chunk on the published task.  Under sparse dispatch the audited
        chunk is a slice of the expert's capacity bucket and the
        committed ``row_index`` maps its slots back to task rows (empty
        slots gather the zero sentinel) — auditors re-derive the
        executor's buckets from the commitment, never from the gate.
        The round retains its manifests at commit time and releases them
        when it reaches a terminal phase (the data-availability
        contract; superseded versions are then garbage collected, while
        the compact fraud proofs remain in the round state)."""
        cache: Dict[int, object] = {}
        xpad = self._pad_task(xin, row_index)

        def recompute(e: int, sl: slice):
            if e not in cache:
                cache[e] = self._fetch_expert_manifest(manifests[e])
            rows = xpad[sl] if row_index is None else xpad[row_index[e, sl]]
            return np.asarray(self._apply_one(cache[e], jnp.asarray(rows)))

        return recompute

    def _make_batched_recompute(self, experts, xin, manifests: List[str],
                                row_index=None):
        """Batched auditor recompute (``BatchRecomputeFn``): the same
        fetch-by-manifest semantics as ``_make_recompute`` — one
        chunk-verified storage fetch per sampled expert — but every
        sampled chunk of the round is then recomputed in ONE jitted
        grouped call instead of a Python-loop dispatch per (expert,
        slice).

        The fetch per sampled expert is preserved — every chunk is
        hash-verified against the committed manifest, so a fetched tree
        is guaranteed byte-identical to the expert version the round
        committed against (a tampered replica is skipped; a withheld
        chunk raises ``ChunkUnavailableError`` — the DA-challengeable
        fault).  That guarantee is what lets the grouped call read the
        already-device-resident bank and task directly: only the
        per-sample row indices and expert ids cross the host boundary,
        the expert and row gathers fuse into the kernel, the bank shape
        is constant, and the only jit-retrace axis is the sample count,
        bucketed to a multiple of 4.  Padding rows never reach the leaf
        hashes.

        The task transfer is deferred to the first call: under pipelined
        scheduling the host drains through the cross-round merged path
        (``_audit_jobs_merged``) and this closure is only the fallback
        for per-round drains, so building it must cost nothing."""
        fetched: set = set()
        xd_cache: List = []

        def fetch(e: int):
            if e not in fetched:
                self._fetch_expert_manifest(manifests[e])  # chunk-verified
                fetched.add(e)

        def batch_recompute(expert_ids, slices):
            for e in sorted({int(e) for e in expert_ids}):
                fetch(e)
            if not xd_cache:
                xd_cache.append(jnp.asarray(self._pad_task(xin, row_index)))
            if self.mesh_shards > 1:
                return self._sharded_batch_recompute(experts, xd_cache[0],
                                                     expert_ids, slices,
                                                     row_index)
            idx, gid, n = pack_audit_batch(expert_ids, slices,
                                           row_map=row_index)
            out = self._batched_recompute_call(experts, xd_cache[0],
                                               jnp.asarray(idx),
                                               jnp.asarray(gid))
            return np.asarray(out[:n])

        return batch_recompute

    def _on_shard(self, s: int, *trees):
        """Commit ``trees`` to the device of edge shard ``s``.  A Pallas
        kernel cannot be partitioned over the mesh, so each shard's
        recompute runs as a one-device program on the device that holds
        its expert slice."""
        dev = self.device_mesh.devices.reshape(-1, self.mesh_shards)[0, s]
        return jax.device_put(trees, dev)

    def _shard_groups(self, expert_ids):
        """Sample indices grouped by the edge shard owning each sampled
        expert — mesh execution routes every audit recompute to the
        shard that holds the expert slice."""
        e_l = self.cfg.num_experts // self.mesh_shards
        groups: Dict[int, List[int]] = {}
        for i, e in enumerate(expert_ids):
            groups.setdefault(int(e) // e_l, []).append(i)
        return e_l, groups

    def _book_audit_rows(self, shard: int, slices, sel) -> None:
        """Per-shard real recompute rows (padding excluded) — the bench
        gate that shard-local audits cost each edge ~1/msize of the
        round's audited rows (benchmarks/mesh_bench.py)."""
        rows = int(sum(slices[i].stop - slices[i].start for i in sel))
        self.obs.metrics.counter("bmoe.mesh.audit_rows",
                                 shard=str(shard)).add(rows)

    def _sharded_batch_recompute(self, experts, xd, expert_ids, slices,
                                 row_index):
        """Shard-local audit recompute: each sampled leaf runs as part of
        the owning edge's grouped call over its local bank slice (local
        expert ids, shard-sliced routing).  Per-sample arithmetic is
        independent of the grouping, so the reassembled ``(S, Cmax, C)``
        tensor is bitwise the single-call path's — verdicts, fraud
        proofs, and attestations are unchanged."""
        e_l, groups = self._shard_groups(expert_ids)
        cmax = max(sl.stop - sl.start for sl in slices)
        out = None
        for s, sel in sorted(groups.items()):
            bank_s, xd_s = self._on_shard(s, jax.tree_util.tree_map(
                lambda a: a[s * e_l:(s + 1) * e_l], experts), xd)
            rmap = (None if row_index is None
                    else row_index[s * e_l:(s + 1) * e_l])
            idx, gid, n = pack_audit_batch(
                [int(expert_ids[i]) - s * e_l for i in sel],
                [slices[i] for i in sel], row_map=rmap)
            part = np.asarray(self._batched_recompute_call(
                bank_s, xd_s, jnp.asarray(idx), jnp.asarray(gid)))[:n]
            if out is None:
                out = np.zeros((len(expert_ids), cmax) + part.shape[2:],
                               part.dtype)
            w = min(part.shape[1], cmax)
            for j, i in enumerate(sel):
                out[i, :w] = part[j, :w]
            self._book_audit_rows(s, slices, sel)
        return out

    def _commit_round(self, protocol, rid, executor, honest, attacked, atk,
                      seed_salt, task_digest, row_index=None):
        """Build the executor's claimed tensor (corrupted iff it attacks)
        and publish the round commitment — over the dense ``(N, B, C)``
        outputs, or (sparse dispatch) the capacity-bucketed buffers plus
        the routing indices auditors re-derive the buckets from."""
        claimed = honest
        if attacked:
            rng = np.random.default_rng(self.cfg.seed * 7919 + seed_salt)
            claimed = honest + atk.noise_std * rng.standard_normal(
                honest.shape).astype(honest.dtype)
        return protocol.commit(rid, executor, claimed,
                               task_digest=task_digest, row_index=row_index,
                               num_shards=self.mesh_shards)

    def _commitment_layout(self, gate, x, batch: int, gate_bias):
        """(row_index, bounds) of the round's commitment: bucket-chunk
        leaves under sparse dispatch, batch-chunk leaves under dense."""
        tc = self.trust_cfg
        if self.cfg.dispatch == "sparse":
            row_index, capacity = self._sparse_routing(gate, x, gate_bias)
            return row_index, chunk_bounds(capacity, tc.chunks_per_expert)
        return None, chunk_bounds(batch, tc.chunks_per_expert)

    def _court_publish(self, ctx, claimed, seed_salt):
        """The dispute court's input: every edge's copy of every expert's
        result — the paper's full redundancy matrix, reconstructed from
        the round snapshot and its attack pattern."""
        cfg = self.cfg
        honest, atk = ctx["honest"], ctx["atk"]
        pub = np.broadcast_to(
            honest[:, None],
            (cfg.num_experts, cfg.num_edges) + honest.shape[1:]).copy()
        att = np.asarray(ctx["mask_e"]) > 0
        if atk.colluding:
            pub[:, att] = claimed[:, None]     # coalition backs the executor
        else:
            rng = np.random.default_rng(cfg.seed * 104729 + seed_salt)
            for m in np.nonzero(att)[0]:
                pub[:, m] = honest + atk.noise_std * rng.standard_normal(
                    honest.shape).astype(honest.dtype)
        pub[:, ctx["executor"]] = claimed
        return pub

    def _audit_jobs_merged(self, protocol, ctx_store,
                           jobs: List[AuditJob]):
        """Audit a whole drained backlog through ONE grouped kernel call:
        the per-round expert-bank snapshots stack to ``(R*N, ...)``, the
        per-round tasks concatenate row-wise, and
        ``VerifierPool.audit_rounds`` fuses every sampled leaf of every
        drained round into a single recompute + one hash pass.  The
        fetch-by-manifest data-availability contract is kept per
        (round, sampled expert) — each fetch resolves the version that
        round committed against."""
        cfg = self.cfg
        ctxs = [ctx_store[j.round_id] for j in jobs]
        coms = [protocol.rounds[j.round_id].commitment for j in jobs]
        banks = [c["prev"][1] for c in ctxs]
        xins = [c["xin"] for c in ctxs]
        # pad multi-round drains to a FIXED (window+1)-slot layout —
        # constant stacked shapes, so the grouped kernel compiles once
        # per batch size instead of once per backlog size (padding slots
        # repeat round 0's bank and contribute zero task rows; no sample
        # ever indexes them).  Single-round drains keep the unpadded
        # per-round layout the synchronous scheduler always uses.
        row_maps = [c.row_index for c in coms]
        slots = (self.trust_cfg.challenge_window + 1 if len(jobs) > 1
                 else 1)
        slots = max(slots, len(jobs))
        # +1: every round's slot ends with at least one zero row — the
        # sentinel empty bucket slots of a sparse commitment gather from
        bmax = max(len(x) for x in xins) + 1
        row_off = np.arange(slots + 1) * bmax
        pad_banks = banks + [banks[0]] * (slots - len(banks))
        stacked_bank = jax.tree_util.tree_map(
            lambda *ls: jnp.concatenate([jnp.asarray(a) for a in ls], 0),
            *pad_banks)
        xpad = np.zeros((slots * bmax,) + xins[0].shape[1:],
                        xins[0].dtype)
        for k, x in enumerate(xins):
            xpad[k * bmax:k * bmax + len(x)] = x
        xcat = jnp.asarray(xpad)
        fetched: set = set()

        def fetch(k: int, e: int):
            if (k, e) in fetched:
                return
            self._fetch_expert_manifest(ctxs[k]["manifests"][e])
            fetched.add((k, e))

        def multi_fn(slot_ids, experts, slices):
            for k, e in sorted({(int(k), int(e))
                                for k, e in zip(slot_ids, experts)}):
                fetch(k, e)
            if self.mesh_shards > 1:
                return sharded_multi(slot_ids, experts, slices)
            # merged drains carry more (and more variable) samples than a
            # per-round audit: bucket to the next power of two so the
            # grouped call settles on O(1) compiled shapes
            bucket = 8
            while bucket < len(experts):
                bucket *= 2
            idx, gid, n = pack_audit_batch_multi(slot_ids, experts, slices,
                                                 row_off, cfg.num_experts,
                                                 bucket=bucket,
                                                 row_maps=row_maps)
            out = self._batched_recompute_call(stacked_bank, xcat,
                                               jnp.asarray(idx),
                                               jnp.asarray(gid))
            return np.asarray(out[:n])

        def sharded_multi(slot_ids, experts, slices):
            # the merged drain under mesh execution: every sampled leaf
            # still recomputes on the edge shard owning its expert — the
            # stacked (slots*N) bank restacks per shard to (slots*E_l)
            # with local expert ids and shard-sliced routing, and the
            # outputs reassemble into the one (S, Cmax, C) tensor
            # audit_rounds hashes (bitwise the unsharded call's rows)
            e_l, groups = self._shard_groups(experts)
            cmax = max(sl.stop - sl.start for sl in slices)
            out = None
            for s, sel in sorted(groups.items()):
                bank_s, xcat_s = self._on_shard(s, jax.tree_util.tree_map(
                    lambda a: a.reshape((slots, cfg.num_experts)
                                        + a.shape[1:])
                    [:, s * e_l:(s + 1) * e_l]
                    .reshape((slots * e_l,) + a.shape[1:]),
                    stacked_bank), xcat)
                rmaps_s = [None if rm is None
                           else rm[s * e_l:(s + 1) * e_l]
                           for rm in row_maps]
                bucket = 8
                while bucket < len(sel):
                    bucket *= 2
                idx, gid, n = pack_audit_batch_multi(
                    [slot_ids[i] for i in sel],
                    [int(experts[i]) - s * e_l for i in sel],
                    [slices[i] for i in sel], row_off, e_l,
                    bucket=bucket, row_maps=rmaps_s)
                part = np.asarray(self._batched_recompute_call(
                    bank_s, xcat_s, jnp.asarray(idx), jnp.asarray(gid)))[:n]
                if out is None:
                    out = np.zeros((len(experts), cmax) + part.shape[2:],
                                   part.dtype)
                w = min(part.shape[1], cmax)
                for j, i in enumerate(sel):
                    out[i, :w] = part[j, :w]
                self._book_audit_rows(s, slices, sel)
            return out

        return protocol.verifiers.audit_rounds(coms, multi_fn)

    def _drain_trust(self, protocol, ctx_store, cid_store, now,
                     domain: str) -> Dict:
        """Drain the deferred-audit backlog: run every queued audit (one
        merged grouped call under the batched backend), court-resolve the
        challenged rounds in round order, and — for the training domain —
        roll back the whole optimistic chain built on a convicted round
        (restore the pre-fraud snapshot, re-execute every voided round
        honestly).  Emits one rollback block per conviction."""
        cfg, tc = self.cfg, self.trust_cfg
        jobs = protocol.pop_audit_jobs(now)
        summary: Dict = {"drained": [j.round_id for j in jobs],
                         "audited_leaves": 0, "fraud_proofs": 0,
                         "convicted": [], "slashed": [],
                         "replayed_metrics": None}
        if not jobs:
            return summary
        # verifier-pool work: concurrent with later rounds in deployment,
        # so off the critical path under pipelined scheduling — the
        # off_path span's seconds land in its own audit metric and are
        # natively excluded from every enclosing phase metric (the
        # consensus span of the committing round).  Courts + chain
        # replay below stay on the critical path — state must be
        # settled.  Synchronous scheduling keeps the drain on-path (no
        # metric: its time belongs to consensus, as before).
        off = tc.scheduling == "pipelined"
        metric = (("bmoe.audit_s" if domain == "train"
                   else "bmoe.audit_infer_s") if off else None)
        with self.obs.span("audit-drain", metric=metric, off_path=off,
                           domain=domain,
                           drained=[j.round_id for j in jobs]):
            if tc.audit_backend == "batched":
                reports_by_rid = self._audit_jobs_merged(protocol,
                                                         ctx_store, jobs)
            else:
                reports_by_rid = {
                    j.round_id: protocol.verifiers.audit(
                        protocol.rounds[j.round_id].commitment,
                        j.recompute_fn)
                    for j in jobs}
            for job in jobs:
                reports = reports_by_rid[job.round_id]
                protocol.apply_reports(job.round_id, reports,
                                       job.recompute_fn)
                audited = sum(r.recomputed_leaves for r in reports)
                com = protocol.rounds[job.round_id].commitment
                summary["audited_leaves"] += audited
                # rows_per_expert is the capacity bucket under sparse
                # dispatch: audit recompute shrinks with execution
                # compute
                self.verify_stats["verify_evals"] += \
                    audited * com.rows_per_expert \
                    / max(com.chunks_per_expert, 1)

        # courts fire in round order, so an early conviction invalidates
        # ACCEPTED descendants before their (clean) audits can finalize
        # them, while CHALLENGED descendants still get their own verdict
        n_rollbacks = len(protocol.rollbacks)
        # the stake book is shared across the train/infer protocols and
        # their round-id namespaces overlap — attribute slashes by the
        # events this drain books, never by round-id lookup
        n_events = len(protocol.stakes.events)
        challenged = sorted(
            j.round_id for j in jobs
            if protocol.rounds[j.round_id].phase is RoundPhase.CHALLENGED)
        for rid in challenged:
            state = protocol.rounds[rid]
            if state.phase is not RoundPhase.CHALLENGED:
                continue
            ctx = ctx_store[rid]
            with self.obs.span("court", metric="bmoe.court_s", domain=domain,
                               round=rid, executor=state.executor) as csp:
                pub = self._court_publish(ctx, state.commitment.claimed,
                                          rid)
                verdict = protocol.court.escalate(
                    rid, pub, state.executor,
                    active=np.asarray(ctx["active"]))
                state = protocol.resolve(rid, verdict)
                csp.set(verdict=state.phase.value)
            summary["fraud_proofs"] += len(state.proofs)
            self.verify_stats["escalate_evals"] += \
                cfg.num_edges * cfg.num_experts \
                * state.commitment.rows_per_expert
            for cid in cid_store.pop(rid, []):
                self.expert_store.release(cid)
            if state.phase is RoundPhase.ROLLED_BACK:
                summary["convicted"].append(rid)

        summary["slashed"] = sorted(
            {ev.edge for ev in protocol.stakes.events[n_events:]})
        if summary["convicted"] and domain == "train":
            with self.obs.span("rollback-replay", metric="bmoe.replay_s",
                               convicted=summary["convicted"]):
                summary["replayed_metrics"] = self._replay_chain(
                    min(summary["convicted"]))
        for rec in protocol.rollbacks[n_rollbacks:]:
            with self.obs.span("rollback-block", round=rec.round_id):
                self._mine({"kind": "rollback", "domain": domain,
                            "rollback_of": rec.round_id,
                            "executor": rec.executor,
                            "chain": [rec.round_id] + rec.invalidated,
                            "invalidated": rec.invalidated,
                            "slashed": [rec.executor],
                            "at_round": self.round})
        return summary

    def _replay_chain(self, first: int):
        """Chained rollback: restore the (gate, experts) snapshot the
        convicted round started from and re-execute every voided round —
        the convicted one plus its INVALIDATED descendants — honestly and
        in order, exactly one slash having been booked per conviction.
        Returns the replayed metrics of the newest round (the host's
        current round, when it is part of the chain)."""
        cfg = self.cfg
        chain = [rid for rid in sorted(self._round_ctx)
                 if rid >= first and self.protocol.rounds[rid].phase in
                 (RoundPhase.ROLLED_BACK, RoundPhase.INVALIDATED)]
        self.gate, self.experts = self._round_ctx[first]["prev"]
        metrics = None
        for rid in chain:
            ctx = self._round_ctx[rid]
            (self.gate, self.experts, metrics) = self._train_step(
                self.gate, self.experts, ctx["x"], ctx["y"],
                jnp.zeros_like(jnp.asarray(ctx["mask_e"])),
                jax.random.fold_in(ctx["rkey"], 1), ctx["atk"].noise_std,
                jnp.asarray(ctx["atk"].colluding), ctx["gate_bias"],
                ctx["active"], jnp.int32(ctx["executor"]))
            metrics = jax.tree_util.tree_map(np.asarray, metrics)
            self.verify_stats["base_evals"] += \
                self._exec_evals(len(ctx["xin"]))
            # the voided versions were built on revoked state: republish
            # each replayed round's honest successor version in place
            # (put_version replaces the same (object, version) tag).
            # Full-bank republish, not just the replay's routed experts:
            # the voided lineage may have routed (and published)
            # DIFFERENT experts at this version tag, and every one of
            # those must be overwritten — chunk dedup keeps the upload at
            # the actually-changed bytes.
            self._publish_bank(None, rid + 1)
        return metrics if chain and chain[-1] == self.round else None

    def _prune_closed_rounds(self, protocol, ctx_store, cid_store):
        """Release snapshots and retained version manifests of rounds
        that hit a terminal phase — a superseded version nobody retains
        is garbage collected from the storage network (the compact fraud
        proofs stay in the round state)."""
        for rid in list(ctx_store):
            if protocol.rounds[rid].phase in TERMINAL_PHASES:
                del ctx_store[rid]
                for cid in cid_store.pop(rid, []):
                    self.expert_store.release(cid)

    def _optimistic_round(self, x, y, atk, mask_e, rkey, executor, prev,
                          metrics, payload, gate_bias, active):
        """Commit -> optimistic accept -> async audit -> (challenge ->
        court -> slash + chained rollback) for one training round.

        Under ``scheduling="pipelined"`` (default) the round's audit is
        only *queued* here: the system proceeds to the next rounds on the
        optimistically-accepted state and the backlog drains in one
        grouped burst when the oldest window is about to close.  Fraud
        confirmed after descendants committed rolls the whole chain back
        (``_replay_chain``).  ``scheduling="synchronous"`` keeps the
        audit on the critical path — the pre-pipeline reference
        behavior.  Returns the round's final metrics (the honest
        re-execution's, if rolled back)."""
        cfg, tc = self.cfg, self.trust_cfg
        with self.obs.span("commitment", metric="bmoe.commitment_s",
                           round=self.round):
            xin = np.asarray(x if cfg.expert_kind == "cnn"
                             else np.asarray(x).reshape(len(x), -1))
            batch = xin.shape[0]
            row_index, bounds = self._commitment_layout(prev[0], x, batch,
                                                        gate_bias)
            honest = self._eager_outputs(prev[1], xin, bounds, row_index)
            attacked = bool(np.asarray(mask_e)[executor] > 0)
            state = self._commit_round(self.protocol, self.round, executor,
                                       honest, attacked, atk, self.round,
                                       payload["task"], row_index)
            payload["commit_root"] = state.commitment.root[:16]
            if state.commitment.routing_digest:
                payload["routing"] = state.commitment.routing_digest[:16]
            payload["executor"] = executor
        # data-availability contract: retain the expert versions this
        # round committed against until its window closes, and challenge
        # replica nodes for sampled chunks of exactly those manifests
        with self.obs.span("da", metric="bmoe.da_s", round=self.round):
            manifests = self._retain_round_manifests(self.round)
            self._audit_cids[self.round] = manifests
            self._round_ctx[self.round] = {
                "prev": prev, "x": x, "y": y, "xin": xin, "honest": honest,
                "rkey": rkey, "executor": executor,
                "mask_e": np.asarray(mask_e), "atk": atk,
                "gate_bias": gate_bias, "active": active,
                "manifests": manifests,
            }
            self._run_da(self.round, manifests)
        with self.obs.span("schedule-audit", round=self.round):
            recompute_fn = self._make_recompute(xin, manifests, row_index)
            batch_fn = (self._make_batched_recompute(prev[1], xin,
                                                     manifests, row_index)
                        if tc.audit_backend == "batched" else None)
            self.protocol.schedule_audit(self.round, recompute_fn, batch_fn)

        # synchronous: the audit lands in the commit round itself (the
        # reference oracle); pipelined: drain only once a window forces it
        drain_now = None if tc.scheduling == "synchronous" else self.round
        summary = self._drain_trust(self.protocol, self._round_ctx,
                                    self._audit_cids, drain_now, "train")
        payload["audited_leaves"] = summary["audited_leaves"]
        if summary["drained"]:
            payload["drained_rounds"] = summary["drained"]
        if summary["fraud_proofs"]:
            payload["fraud_proofs"] = summary["fraud_proofs"]
            payload["slashed"] = summary["slashed"]
        if summary["replayed_metrics"] is not None:
            payload["rolled_back"] = True
            metrics = summary["replayed_metrics"]

        # close windows in deadline order (sequential finality: never past
        # an unresolved dispute) and release closed rounds' evidence
        with self.obs.span("settle", round=self.round):
            finalized = self.protocol.advance(self.round)
            if finalized:
                payload["finalized_rounds"] = finalized
            self._prune_closed_rounds(self.protocol, self._round_ctx,
                                      self._audit_cids)

        metrics = dict(metrics)
        metrics["rolled_back"] = np.float32(
            1.0 if payload.get("rolled_back") else 0.0)
        return metrics

    # ------------------------------------------------- pipeline flushing
    def flush_trust(self) -> Dict:
        """Close out the optimistic pipeline: run every still-queued audit
        (training and inference domains), court-resolve what they raise,
        and advance both clocks past the last open window so every
        committed round reaches a terminal phase.  Call at the end of a
        run (or before comparing two runs) — it is the pipelined
        equivalent of the synchronous scheduler's per-round settlement."""
        out: Dict = {}
        if self.protocol is None:
            return out
        summary = self._drain_trust(self.protocol, self._round_ctx,
                                    self._audit_cids, None, "train")
        if summary["convicted"]:
            out["rolled_back"] = summary["convicted"]
        horizon = self.protocol.clock + self.trust_cfg.challenge_window
        out["finalized"] = self.protocol.advance(horizon)
        self._prune_closed_rounds(self.protocol, self._round_ctx,
                                  self._audit_cids)
        self._run_da(None)               # close every open DA challenge
        if self._infer_protocol is not None:
            isummary = self._drain_trust(self._infer_protocol,
                                         self._infer_ctx,
                                         self._infer_audit_cids, None,
                                         "infer")
            self._record_infer_verdicts(isummary)
            ihorizon = (self._infer_protocol.clock
                        + self.trust_cfg.challenge_window)
            out["infer_finalized"] = self._infer_protocol.advance(ihorizon)
            for frid in out["infer_finalized"]:
                self.infer_log.append({"event": "finalize", "round": frid})
            self._prune_closed_rounds(self._infer_protocol, self._infer_ctx,
                                      self._infer_audit_cids)
        return out

    # -------------------------------------------- optimistic inference
    def _ensure_infer_protocol(self) -> OptimisticProtocol:
        if self._infer_protocol is None:
            # its own round clock/window, but the SAME stake book, court
            # and reputation ledger: one edge deposit backs both
            # workloads, and an inference conviction bars the executor
            # from the training rotation too
            # chained=False: inference batches run against frozen weights,
            # so rounds are independent — a conviction revokes only its
            # own round, never later in-flight batches
            self._infer_protocol = OptimisticProtocol(
                self.trust_cfg, self.cfg.num_edges, self.reputation,
                stakes=self.protocol.stakes, court=self.protocol.court,
                chained=False, metrics=self.obs.metrics,
                namespace="trust.infer")
        return self._infer_protocol

    def _record_infer_verdicts(self, summary: Dict) -> None:
        for rid in summary["convicted"]:
            self.infer_log.append({"event": "revoke", "round": rid,
                                   "executor":
                                       self._infer_protocol.rounds[rid]
                                       .executor})

    def pending_inference(self) -> List[int]:
        """Inference rounds still inside their challenge window."""
        return ([] if self._infer_protocol is None
                else self._infer_protocol.pending())

    # ------------------------------------------------- unified reporting
    @property
    def _timers(self) -> Dict[str, float]:
        """The legacy phase-timer dict, as a read-only view over the obs
        registry (same keys and values as the pre-obs ad-hoc dict).
        Writes happen only through spans — one measurement substrate."""
        m = self.obs.metrics
        return {k: float(m.value(n))
                for k, n in self._TIMER_METRICS.items()}

    def obs_report(self, expert_bytes: Optional[int] = None,
                   result_bytes: Optional[int] = None,
                   rounds: Optional[int] = None) -> Dict:
        """The unified observability entry point: one dict with every
        layer's numbers, all read from the single metrics registry.

        Sections: ``metrics`` (the flat registry snapshot),
        ``timers`` (legacy phase-seconds keys), ``storage`` (the exact
        ``storage_report()`` shape), ``verification`` (the exact
        ``verification_report()`` shape), and — when the byte/round
        arguments are given — ``latency`` (the exact ``latency_report()``
        shape).  The legacy report methods are thin views over this."""
        out: Dict = {
            "metrics": self.obs.metrics.snapshot(),
            "timers": dict(self._timers),
            "storage": {"network": dict(self.storage.stats),
                        "store": dict(self.expert_store.stats),
                        "cache": (dict(self.edge_cache.stats)
                                  if self.edge_cache else None),
                        "da": dict(self.da.stats) if self.da else None,
                        "wall_s": self._timers["storage"]},
            "verification": self.verification_report(),
        }
        if rounds is not None:
            out["latency"] = self._latency_section(
                expert_bytes or 0, result_bytes or 0, rounds)
        return out

    # ----------------------------------------------------- latency model
    def latency_report(self, expert_bytes: int, result_bytes: int,
                       rounds: int) -> Dict[str, float]:
        """Per-round latency decomposition (paper Fig. 4b is relative):
        measured compute/consensus/chain wall-clock + modeled comms.
        A thin view over ``obs_report()`` — keys unchanged from pre-obs."""
        return self.obs_report(expert_bytes, result_bytes,
                               rounds)["latency"]

    def _latency_section(self, expert_bytes: int, result_bytes: int,
                         rounds: int) -> Dict[str, float]:
        cfg = self.cfg
        bw = cfg.bandwidth_bytes_per_s
        if cfg.framework == "bmoe":
            # every edge downloads all K activated experts + uploads K results
            t_comm = (cfg.num_edges * cfg.top_k * expert_bytes
                      + cfg.num_edges * cfg.top_k * result_bytes) / bw
        elif cfg.framework == "optimistic":
            tc = self.trust_cfg
            # executor: K expert downloads + K result uploads + 32B root;
            # auditors: expected audit_rate of the N experts re-fetched
            # plus the sampled result chunks (audit_rate is the pool-wide
            # sampled fraction — already split across verifiers)
            audit_bytes = tc.audit_rate * (
                cfg.num_experts * expert_bytes + result_bytes)
            t_comm = (cfg.top_k * expert_bytes + cfg.top_k * result_bytes
                      + 32 + audit_bytes) / bw
        else:
            t_comm = cfg.top_k * result_bytes / bw
        r = max(rounds, 1)
        timers = self._timers
        return {
            "compute_s": timers["compute"] / r,
            "comm_s": t_comm,
            "consensus_s": timers["consensus"] / r,
            "chain_s": timers["chain"] / r,
            # verifier-pool audit seconds drained off the critical path
            # (pipelined scheduling only; synchronous audits sit inside
            # consensus_s) — reported separately, excluded from total_s
            "audit_offpath_s": timers["audit"] / r,
            # host wall-clock of the storage simulation (chunk hashing,
            # cache resolution) — reported separately, excluded from
            # total_s: the *transfer* time it simulates is already the
            # modeled comm_s term (see storage_report() for the cost-
            # model view)
            "storage_s": timers["storage"] / r,
            "total_s": timers["compute"] / r + t_comm
                       + timers["consensus"] / r
                       + timers["chain"] / r,
        }

    def verification_report(self) -> Dict[str, float]:
        """Per-round verification compute, in expert-evaluations x samples
        (the simulation broadcasts copies instead of physically paying for
        them, so redundancy/audit cost is counted, not wall-clocked)."""
        r = max(self.verify_stats["rounds"], 1)
        verify = self.verify_stats["verify_evals"]
        escalate = self.verify_stats["escalate_evals"]
        return {
            "base_evals_per_round": self.verify_stats["base_evals"] / r,
            "verify_evals_per_round": verify / r,
            "escalate_evals_per_round": escalate / r,
            "total_verification_per_round": (verify + escalate) / r,
        }


# ---------------------------------------------------------------- steps
def _flatten_for_gate(x):
    return x.reshape(x.shape[0], -1)


def sparse_capacity(cfg, batch: int) -> int:
    """Bucket slots per expert under sparse dispatch: the balanced share
    ``batch*top_k/num_experts`` scaled by ``capacity_factor``, rounded up
    to a multiple of 8 (GEMM-tile friendly) and capped at ``batch`` (an
    expert can receive at most one slot per token: top-k indices are
    distinct per token)."""
    cap = int(np.ceil(cfg.capacity_factor * batch * cfg.top_k
                      / cfg.num_experts))
    cap = min(-(-cap // 8) * 8, batch)
    return max(cap, 1)


def _sparse_dispatch(xin, topi, cfg, capacity):
    """Scatter the top-k assignments into per-expert capacity buckets.

    Returns (buf (N, capacity, *xin.shape[1:]), eid (B*k,), pos (B*k,),
    keep (B*k,)): slot ``pos[j]`` of expert ``eid[j]``'s bucket holds
    token ``j // k``'s input (overflowing assignments are dropped — the
    bucket row stays zero and the combine masks the slot out)."""
    B = xin.shape[0]
    eid = topi.reshape(-1)                              # (B*k,) row-major
    pos, keep, _ = capacity_positions(eid[None], cfg.num_experts, capacity)
    pos, keep = pos[0], keep[0]
    posc = jnp.where(keep, pos, capacity - 1)           # clamp drops
    kshape = (B * cfg.top_k,) + (1,) * (xin.ndim - 1)
    gath = jnp.repeat(xin, cfg.top_k, axis=0) \
        * keep.reshape(kshape).astype(xin.dtype)
    buf = jnp.zeros((cfg.num_experts, capacity) + xin.shape[1:],
                    xin.dtype).at[eid, posc].add(gath)
    return buf, eid, posc, keep


def _route_for_commit(gate, x, gate_bias, *, cfg):
    """The routing the executor publishes with a sparse commitment:
    exactly the gate + top-k + capacity-bucket assignment the forward
    uses, re-derived from the round's snapshot state."""
    flat = _flatten_for_gate(x)
    logits = ex.gate_apply(gate, flat) + gate_bias[None, :]
    _, topi = ex.sparse_gate_weights(logits, cfg.top_k)
    capacity = sparse_capacity(cfg, flat.shape[0])
    eid = topi.reshape(-1)
    pos, keep, _ = capacity_positions(eid[None], cfg.num_experts, capacity)
    return eid, pos[0], keep[0]


def _trust_outputs(outs, mask_e, key, noise_std, colluding, cfg, active,
                   executor, shard=None):
    """Framework-specific corruption + consensus over the per-expert
    output buffer ``outs`` (N, R, ...) — R is the full batch under dense
    dispatch, the capacity bucket under sparse (the vote and the attack
    surface shrink with the compute).

    ``shard=(sid, E_l)`` marks mesh execution: ``outs`` is edge ``sid``'s
    local expert slice ``(E_l, R, ...)``.  Corruption noise is then drawn
    at the full ``(N, R, ...)`` shape and sliced to the local experts —
    the counter-based PRNG makes every edge's corrupted bytes bitwise
    the single-device oracle's — and the consensus vote runs over the
    local experts only (the vote is per-expert independent, so local
    verdicts concatenate to exactly the global ones)."""
    n_local = outs.shape[0]
    full_shape = (cfg.num_experts,) + outs.shape[1:]

    def local(a):
        # barrier first: fusing the threefry/erfinv noise computation
        # into the corruption mul-add chain lets XLA contract the ops
        # shape-dependently (observed: last-ulp drift between the
        # (E_l, ...) mesh slice and the (N, ...) oracle); materializing
        # the full-shape draw makes the remaining slice + elementwise
        # chain bit-stable.  The draw is never differentiated (constant
        # w.r.t. params), so the missing optimization_barrier vjp rule
        # is moot.
        a = jax.lax.optimization_barrier(a)
        if shard is None:
            return a
        return jax.lax.dynamic_slice_in_dim(a, shard[0] * shard[1],
                                            shard[1], axis=0)

    if cfg.framework == "optimistic":
        # single-executor optimistic path: the round's result is whatever
        # the rotating executor published (corrupted iff it attacks);
        # verification happens off the jitted path (commit/audit/court)
        exec_flag = mask_e[executor]
        noise = local(jax.random.normal(key, full_shape, outs.dtype))
        trusted = outs + noise_std * noise * exec_flag
        support = jnp.full((n_local,), 1.0)
        flags = jnp.ones((n_local, cfg.num_edges), jnp.int32)
    elif cfg.framework == "traditional":
        # edge i employs expert i: manipulation hits expert i directly
        # (the sliced form below is manipulate_single restricted to the
        # local experts — same noise draw, same mask rows)
        mask_n = mask_e[:cfg.num_experts]
        noise = local(jax.random.normal(key, full_shape, outs.dtype))
        m = local(mask_n).reshape((n_local,) + (1,) * (outs.ndim - 1))
        trusted = outs + noise_std * noise * m
        support = jnp.full((n_local,), 1.0)
        flags = jnp.ones((n_local, cfg.num_edges), jnp.int32)
    else:
        # redundancy: every edge publishes every expert's result.  Each
        # edge's manipulated copy draws from its own folded key (the
        # colluding coalition folds a shared id, publishing identical
        # results), so only the (N, M, ...) publication tensor the vote
        # needs is materialized — not separate colluding + independent
        # noise tensors plus a full-size select.  The draw is vmapped
        # bare (optimization_barrier has no batching rule) and the
        # stacked tensor barriered before the slice + corruption
        # arithmetic — see ``local`` on why the barrier matters.
        def edge_noise(m):
            fid = jnp.where(colluding, 0, m)
            return jax.random.normal(jax.random.fold_in(key, fid),
                                     full_shape, outs.dtype)

        noise = jax.vmap(edge_noise)(jnp.arange(cfg.num_edges))
        noise = jax.lax.optimization_barrier(noise)      # (M, N, ...)
        if shard is not None:
            noise = jax.lax.dynamic_slice_in_dim(
                noise, shard[0] * shard[1], shard[1], axis=1)
        mshape = (1, cfg.num_edges) + (1,) * (outs.ndim - 1)
        pub = outs[:, None] + noise_std * jnp.moveaxis(noise, 0, 1) \
            * mask_e.reshape(mshape)                     # (N|E_l, M, ...)
        # Step 3: distributed consensus = majority vote over the M copies
        # (reputation-excluded edges barred from electorate, §VI-D)
        act = active if active is not None else jnp.ones(cfg.num_edges)
        trusted, support, flags = kref.redundancy_vote_masked_ref(pub, act)
    return trusted, support, flags


@jax.custom_vjp
def _grad_barrier(x):
    """Identity whose cotangent passes through an optimization barrier.

    Without it XLA fuses the ownership-mask reduction from the return
    all_to_all's transpose with the bias-gradient capacity reduce inside
    the expert vjp, summing the per-slot cotangents over (msize, cap)
    jointly — a different float association order than the oracle's
    plain cap reduce (observed: last-ulp drift on the experts' output
    bias after one SGD step, every other gradient bitwise equal).
    Materializing the cotangent here restores the oracle's reduction
    shape, and with it bit-identical parameter updates."""
    return x


def _grad_barrier_fwd(x):
    return x, None


def _grad_barrier_bwd(_, g):
    return (jax.lax.optimization_barrier(g),)


_grad_barrier.defvjp(_grad_barrier_fwd, _grad_barrier_bwd)


def _mesh_sparse_forward(experts, xin, topi, weights, capacity, mask_e, key,
                         noise_std, colluding, cfg, apply_grouped, active,
                         executor, mesh, msize):
    """Sparse dispatch across the edge mesh (BMoEConfig.mesh="on").

    Routing runs globally on the replicated gate — the identical ops the
    single-device oracle runs.  Each edge shard then scatters only its
    own token slice into a full-shape send buffer at the GLOBAL bucket
    positions, and the buffers cross the mesh via all_to_all; summing
    the per-shard partials is exact (every bucket slot has at most one
    nonzero contributor — its unique token — and 0+x is exact), so the
    local ``(E_l, capacity, C)`` buffers each edge computes its experts
    on are bitwise the oracle's bucket slices.  Per-device dispatch wire
    bytes are ~num_experts*capacity*C ~ capacity_factor*B*top_k*C —
    independent of the expert count (gated in benchmarks/mesh_bench.py).

    Trust corruption draws noise at the full ``(N, ...)`` shape and
    slices the local experts (see ``_trust_outputs``), so each edge's
    attacked bytes are bitwise the oracle's too.  The return all_to_all
    hands every token's combine rows back to the shard owning the token
    via the ``slot_src`` ownership map (derived from the replicated
    routing, so it needs no communication).

    Every non-bank input enters the shard_map REPLICATED and is sliced
    inside the body: the transpose then psums per-shard cotangents that
    are exact zeros outside each shard's slice, keeping the backward
    pass — and hence every parameter update — bit-identical to the
    oracle as well.  (The scalar *loss* is the one quantity allowed to
    differ in final ulps: its mean over the sharded output reduces in a
    different order.)"""
    N, k = cfg.num_experts, cfg.top_k
    E_l = N // msize
    B = xin.shape[0]
    B_l = -(-B // msize)
    B_pad = B_l * msize
    tail = xin.shape[1:]

    eid = topi.reshape(-1)                              # (B*k,) row-major
    pos, keep, _ = capacity_positions(eid[None], N, capacity)
    pos, keep = pos[0], keep[0]
    posc = jnp.where(keep, pos, capacity - 1)
    dropped = (B * k) - keep.sum().astype(jnp.float32)
    wk = jnp.take_along_axis(weights, topi, axis=1).reshape(-1)
    wk = wk * keep.astype(wk.dtype)

    # which token shard owns each filled bucket slot (-1: empty slot) —
    # token b lives on shard b // B_l, matching the slices below
    towner = jnp.repeat(jnp.arange(B, dtype=jnp.int32) // B_l, k)
    slot_src = jnp.full((N, capacity), -1, jnp.int32).at[eid, posc].max(
        jnp.where(keep, towner, -1), mode="drop")

    def padtok(a, fill):                                # (B*k,) -> (B_pad*k,)
        if B_pad == B:
            return a
        pad = jnp.full(((B_pad - B) * k,) + a.shape[1:], fill, a.dtype)
        return jnp.concatenate([a, pad], axis=0)

    xin_p = xin if B_pad == B else jnp.concatenate(
        [xin, jnp.zeros((B_pad - B,) + tail, xin.dtype)], axis=0)
    eid_p = padtok(eid, N)          # sentinel expert: dropped by the scatter
    posc_p = padtok(posc, capacity - 1)
    keep_p = padtok(keep, False)
    wk_p = padtok(wk, 0)

    def body(xr, eidr, posr, keepr, wkr, bank_l, src, mask_er, keyr,
             stdr, collr, activer, execr):
        sid = jax.lax.axis_index("model")
        lo = sid * B_l * k
        eidl = jax.lax.dynamic_slice_in_dim(eidr, lo, B_l * k)
        posl = jax.lax.dynamic_slice_in_dim(posr, lo, B_l * k)
        keepl = jax.lax.dynamic_slice_in_dim(keepr, lo, B_l * k)
        wkl = jax.lax.dynamic_slice_in_dim(wkr, lo, B_l * k)
        xl = jax.lax.dynamic_slice_in_dim(xr, sid * B_l, B_l)

        # scatter own tokens into the full-shape buffer at their GLOBAL
        # bucket positions, exchange, and sum the per-shard partials
        kshape = (B_l * k,) + (1,) * len(tail)
        gath = jnp.repeat(xl, k, axis=0) \
            * keepl.reshape(kshape).astype(xl.dtype)
        send = jnp.zeros((N, capacity) + tail, xl.dtype).at[
            eidl, posl].add(gath, mode="drop")
        recv = jax.lax.all_to_all(send.reshape((msize, E_l, capacity)
                                               + tail),
                                  "model", split_axis=0, concat_axis=0,
                                  tiled=False)
        buf_l = recv.sum(axis=0)                    # (E_l, capacity, *tail)

        outs_l = apply_grouped(bank_l, buf_l)       # (E_l, capacity, C)
        outs_l = _grad_barrier(outs_l)
        trusted_l, support_l, flags_l = _trust_outputs(
            outs_l, mask_er, keyr, stdr, collr, cfg, activer, execr,
            shard=(sid, E_l))

        # return exchange: each trusted row goes back to the shard that
        # owns its token (ownership-masked so the sum at the receiver
        # again has at most one nonzero contributor per slot)
        src_l = jax.lax.dynamic_slice_in_dim(src, sid * E_l, E_l, axis=0)
        own = src_l[None] == jnp.arange(msize, dtype=jnp.int32)[:, None,
                                                                None]
        back = jnp.where(
            own.reshape((msize, E_l, capacity)
                        + (1,) * (trusted_l.ndim - 2)),
            trusted_l[None], jnp.zeros((), trusted_l.dtype))
        ret = jax.lax.all_to_all(back, "model", split_axis=0,
                                 concat_axis=0, tiled=False)
        ret = ret.reshape((N, capacity) + trusted_l.shape[2:])

        yk = ret.at[eidl, posl].get(mode="fill", fill_value=0) \
            * wkl[:, None]
        y_l = yk.reshape((B_l, k) + yk.shape[1:]).sum(axis=1)
        return y_l, support_l, flags_l

    rep = P()
    bank_specs = jax.tree_util.tree_map(lambda _: P("model"), experts)
    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(rep, rep, rep, rep, rep, bank_specs, rep, rep, rep,
                  rep, rep, rep, rep),
        out_specs=(P("model"), P("model"), P("model")), check_vma=False)
    act = active if active is not None else jnp.ones(cfg.num_edges)
    y, support, flags = mapped(
        xin_p, eid_p, posc_p, keep_p, wk_p, experts, slot_src, mask_e,
        key, jnp.asarray(noise_std, jnp.float32), jnp.asarray(colluding),
        act, jnp.asarray(executor, jnp.int32))
    return y[:B], support, flags, dropped


def _moe_forward(gate, experts, x, mask_e, key, noise_std, colluding, cfg,
                 apply_all, apply_grouped, gate_bias=None, active=None,
                 executor=0, mesh=None, mesh_shards=1):
    """Shared forward: returns (trusted_out (B,C), weights (B,N),
    activation (N,), support (N,), flags (N,M), logits (B,N),
    dropped ()).  With ``mesh`` the sparse path runs sharded over the
    edge mesh (``_mesh_sparse_forward``) — bit-identical outputs."""
    flat = _flatten_for_gate(x)
    xin = x if cfg.expert_kind == "cnn" else flat
    logits = ex.gate_apply(gate, flat)
    if gate_bias is not None:  # §VI-C workload-balance bias (loss-free)
        logits = logits + jax.lax.stop_gradient(gate_bias)[None, :]
    weights, topi = ex.sparse_gate_weights(logits, cfg.top_k)
    B = xin.shape[0]

    if cfg.dispatch == "sparse":
        capacity = sparse_capacity(cfg, B)
        if mesh is not None:
            y, support, flags, dropped = _mesh_sparse_forward(
                experts, xin, topi, weights, capacity, mask_e, key,
                noise_std, colluding, cfg, apply_grouped, active,
                executor, mesh, mesh_shards)
        else:
            # top-k scatter-dispatch: only routed tokens reach an expert
            buf, eid, posc, keep = _sparse_dispatch(xin, topi, cfg,
                                                    capacity)
            outs = apply_grouped(experts, buf)          # (N, cap, C)
            dropped = (B * cfg.top_k) - keep.sum().astype(jnp.float32)
            trusted, support, flags = _trust_outputs(
                outs, mask_e, key, noise_std, colluding, cfg, active,
                executor)
            # aggregate with gate weights (paper: weighted sum over top-K)
            yk = trusted[eid, posc]                     # (B*k, C)
            wk = jnp.take_along_axis(weights, topi, axis=1).reshape(-1)
            wk = wk * keep.astype(wk.dtype)             # drops contribute 0
            y = (yk * wk[:, None]).reshape(B, cfg.top_k, -1).sum(axis=1)
    else:
        if mesh is not None:
            raise ValueError("mesh execution requires dispatch='sparse'")
        outs = apply_all(experts, xin)                  # (N, B, C)
        dropped = jnp.zeros((), jnp.float32)
        trusted, support, flags = _trust_outputs(outs, mask_e, key,
                                                 noise_std, colluding,
                                                 cfg, active, executor)
        y = jnp.einsum("bn,nbc->bc", weights, trusted)
    activation = (weights > 0).sum(axis=0).astype(jnp.float32)
    return y, weights, activation, support, flags, logits, dropped


def _train_step(gate, experts, x, y, mask_e, key, noise_std, colluding,
                gate_bias, active, executor, *, cfg, apply_all,
                apply_grouped, mesh=None, mesh_shards=1):
    def loss_fn(params):
        gate_p, experts_p = params
        out, w, activation, support, flags, _, dropped = _moe_forward(
            gate_p, experts_p, x, mask_e, key, noise_std, colluding, cfg,
            apply_all, apply_grouped, gate_bias, active, executor,
            mesh, mesh_shards)
        logp = jax.nn.log_softmax(out, axis=-1)
        loss = -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()
        return loss, (activation, support, flags, dropped)

    (loss, (activation, support, flags, dropped)), grads = \
        jax.value_and_grad(loss_fn, has_aux=True)((gate, experts))
    grads_e = grads[1]
    if mesh is not None:
        # keep bank grads (and therefore the updated bank) on the edge
        # mesh: without the constraint XLA materializes the replicated
        # grad as zero-padded shards + an all-reduce that scales with
        # the bank size, re-coupling wire bytes to the expert count.
        # Each element has exactly one contributing shard, so the
        # shard-local update is bitwise the same bank.
        bank_spec = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("model"))
        grads_e = jax.tree_util.tree_map(
            lambda g: jax.lax.with_sharding_constraint(g, bank_spec),
            grads_e)
    new_gate = jax.tree_util.tree_map(lambda p, g: p - cfg.lr * g, gate,
                                      grads[0])
    new_experts = jax.tree_util.tree_map(lambda p, g: p - cfg.lr * g,
                                         experts, grads_e)
    metrics = {"loss": loss, "activation": activation, "support": support,
               "flags": flags, "dropped": dropped}
    return new_gate, new_experts, metrics


def _infer_step(gate, experts, x, mask_e, key, noise_std, colluding,
                gate_bias, active, executor, *, cfg, apply_all,
                apply_grouped, mesh=None, mesh_shards=1):
    out, w, activation, support, flags, _, _ = _moe_forward(
        gate, experts, x, mask_e, key, noise_std, colluding, cfg, apply_all,
        apply_grouped, gate_bias, active, executor, mesh, mesh_shards)
    return out, activation, support
