"""Mesh-executed B-MoE rounds (BMoEConfig.mesh="on").

Acceptance pins for the mesh tentpole: with >= 4 simulated edge devices
(forced host devices in a subprocess), the full round loop — sparse
all_to_all dispatch, shard-local trust corruption/vote, shard-local
commitments, owning-shard audit recompute, fraud proofs, slashing, and
chained rollback — is BIT-IDENTICAL to the single-device oracle
(``mesh="off"``): same parameter digests every round, same commitment
and bank roots, same audit verdicts, same post-rollback state.  The
scalar loss is the one quantity compared with tolerance only (its mean
reduces over a sharded output in a different order), which is also why
block hashes — whose payloads embed the float loss — are never
compared.

Host-side tests cover the shard-local commitment algebra: per-edge
Merkle subtrees reduce to exactly the flat single-device root whenever
leaves-per-shard is a power of two (each shard subtree is then a
complete subtree of the flat tree), so every authentication path and
fraud proof is unchanged.
"""
import numpy as np
import pytest

from conftest import run_with_devices
from repro.trust.commitments import MerkleTree, commit_outputs


# ------------------------------------------------ shard-local commitments
def test_sharded_commitment_root_equals_flat_root():
    rng = np.random.default_rng(0)
    outs = rng.standard_normal((8, 16, 10), dtype=np.float32)
    flat = commit_outputs(outs, round_id=0, executor=1, chunks_per_expert=4)
    for shards in (2, 4, 8):
        com = commit_outputs(outs, round_id=0, executor=1,
                             chunks_per_expert=4, num_shards=shards)
        assert com.num_shards == shards
        assert len(com.shard_roots) == shards
        assert com.root == flat.root
        assert com.leaf_digests == flat.leaf_digests
        # the published shard roots ARE level log2(leaves/shard) of the
        # flat tree: reducing them reproduces the round root
        assert MerkleTree(com.shard_roots).root == com.root
        # ... and every fraud proof is byte-identical
        tree_f, tree_s = flat.tree(), com.tree()
        for leaf in (0, 7, 31):
            assert tree_s.prove(leaf) == tree_f.prove(leaf)


def test_sharded_commitment_single_leaf_shards():
    """leaves-per-shard == 1 (E_l == chunks == 1 ... or any product of
    one): the shard root IS the leaf digest; reduction still matches."""
    rng = np.random.default_rng(1)
    outs = rng.standard_normal((4, 3, 5), dtype=np.float32)
    flat = commit_outputs(outs, round_id=0, executor=0, chunks_per_expert=1)
    com = commit_outputs(outs, round_id=0, executor=0, chunks_per_expert=1,
                         num_shards=4)
    assert com.shard_roots == flat.leaf_digests
    assert com.root == flat.root


def test_sharded_commitment_rejects_non_pow2_leaves_per_shard():
    outs = np.zeros((6, 8, 4), np.float32)
    with pytest.raises(ValueError, match="power of two"):
        commit_outputs(outs, round_id=0, executor=0, chunks_per_expert=3,
                       num_shards=2)                     # 3*3 = 9 leaves
    with pytest.raises(ValueError, match="divide"):
        commit_outputs(outs, round_id=0, executor=0, num_shards=4)


def test_mesh_config_validation():
    from repro.core.bmoe import BMoEConfig, BMoESystem
    from repro.trust.protocol import TrustConfig
    with pytest.raises(ValueError, match="sparse"):
        BMoESystem(BMoEConfig(framework="optimistic", dispatch="dense",
                              mesh="on"))
    # on one device the edge mesh degenerates to a single shard and the
    # system must still construct (the subprocess tests cover >= 4)
    s = BMoESystem(BMoEConfig(framework="optimistic", dispatch="sparse",
                              mesh="on", num_experts=8, top_k=2,
                              pow_difficulty=2,
                              trust=TrustConfig(audit_rate=0.5,
                                                num_verifiers=1,
                                                challenge_window=1)))
    assert s.mesh_shards == 1


def test_mesh_rejects_non_pow2_shard_leaves(repo_src):
    """num_experts/shards * chunks_per_expert must be a power of two for
    the root-of-roots reduction to stay bit-identical — reject at system
    construction, before any round commits.  (Needs >1 shard: a single
    shard commits the flat tree, where any leaf count is legal.)"""
    out = run_with_devices("""
        import pytest
        from repro.core.bmoe import BMoEConfig, BMoESystem
        from repro.trust.protocol import TrustConfig
        with pytest.raises(ValueError, match="power-of-two"):
            BMoESystem(BMoEConfig(framework="optimistic", dispatch="sparse",
                                  mesh="on", num_experts=6, top_k=2,
                                  mesh_shards=2, pow_difficulty=2,
                                  trust=TrustConfig(audit_rate=0.5,
                                                    num_verifiers=1,
                                                    challenge_window=1,
                                                    chunks_per_expert=3)))
        print("NON POW2 REJECTED")
    """, 2, repo_src)
    assert "NON POW2 REJECTED" in out


# --------------------------------------------------- mesh == oracle
_COMMON = """
        import numpy as np
        import jax
        from repro.core.attacks import AttackConfig
        from repro.core.bmoe import BMoEConfig, BMoESystem
        from repro.core.ledger import digest_tree
        from repro.core.reputation import ReputationConfig
        from repro.data.synthetic import FMNIST, make_image_dataset
        from repro.trust.protocol import TrustConfig
        xtr, ytr, xte, yte = make_image_dataset(FMNIST, n_train=600,
                                                n_test=100, seed=0)
        xtr = xtr.reshape(len(xtr), -1)
        xte = xte.reshape(len(xte), -1)
"""


def test_mesh_optimistic_round_loop_bit_identical(repo_src):
    """The headline acceptance: 5 attacked optimistic rounds + audits +
    slash + rollback on an 8-edge mesh vs the single-device oracle —
    parameters, commitment roots, shard-root reduction, fraud proofs,
    phases, inference logits, and per-shard audit-row accounting."""
    out = run_with_devices(_COMMON + """
        def build(mesh):
            return BMoESystem(BMoEConfig(
                framework="optimistic", dispatch="sparse", mesh=mesh,
                num_experts=8, top_k=2, capacity_factor=1.25,
                pow_difficulty=2,
                attack=AttackConfig(malicious_edges=(2,), attack_prob=1.0,
                                    noise_std=5.0),
                reputation=ReputationConfig(init=0.5, gain=0.01, slash=0.4,
                                            exclusion_threshold=0.2),
                trust=TrustConfig(audit_rate=1.0, num_verifiers=2,
                                  challenge_window=2,
                                  audit_backend="batched")))
        def run(mesh):
            s = build(mesh)
            rng = np.random.default_rng(0)
            for idx in [rng.integers(0, len(xtr), 48) for _ in range(5)]:
                s.train_round(xtr[idx], ytr[idx])
            s.flush_trust()
            return s
        a, b = run("off"), run("on")
        assert b.mesh_shards == 8, b.mesh_shards
        assert digest_tree(a.experts) == digest_tree(b.experts)
        assert digest_tree(a.gate) == digest_tree(b.gate)
        for rid in a.protocol.rounds:
            ra, rb = a.protocol.rounds[rid], b.protocol.rounds[rid]
            assert ra.commitment.root == rb.commitment.root, rid
            assert ra.phase is rb.phase, rid
            assert [(p.leaf_index, p.expert, p.claimed_digest,
                     p.recomputed_digest) for p in ra.proofs] == \
                   [(p.leaf_index, p.expert, p.claimed_digest,
                     p.recomputed_digest) for p in rb.proofs], rid
        com = b.protocol.rounds[0].commitment
        assert com.num_shards == 8
        from repro.trust.commitments import MerkleTree
        assert MerkleTree(com.shard_roots).root == com.root
        assert a.protocol.stats["rolled_back"] == \
            b.protocol.stats["rolled_back"] >= 1
        la, _, _ = a.infer(xte[:64], commit=False)
        lb, _, _ = b.infer(xte[:64], commit=False)
        assert np.asarray(la).tobytes() == np.asarray(lb).tobytes()
        # audit recompute ran shard-local: every sampled row was booked
        # against the shard owning its expert, ~uniformly (audit_rate=1
        # samples every leaf, so each of the 8 shards re-executes ~1/8
        # of the rows the oracle re-executes in one call)
        rows = {s: b.obs.metrics.value("bmoe.mesh.audit_rows", shard=str(s))
                for s in range(8)}
        total = sum(rows.values())
        cap_pad = 16                            # one capacity bucket of slack
        assert total > 0 and all(r > 0 for r in rows.values()), rows
        assert max(rows.values()) <= total / 8 + cap_pad, rows
        print("MESH ORACLE OK", b.protocol.stats["rolled_back"], total)
    """, 8, repo_src, timeout=900)
    assert "MESH ORACLE OK" in out


def test_mesh_frameworks_bit_identical(repo_src):
    """traditional (per-edge corruption) and bmoe (full redundancy vote)
    frameworks, mesh on/off, explicit 4-wide shards (E_l == 2): params
    and inference bitwise equal."""
    out = run_with_devices(_COMMON + """
        atk = AttackConfig(malicious_edges=(1, 2), attack_prob=1.0,
                           noise_std=3.0)
        for fw in ("traditional", "bmoe"):
            def run(mesh):
                s = BMoESystem(BMoEConfig(framework=fw, dispatch="sparse",
                                          mesh=mesh, mesh_shards=4,
                                          num_experts=8, top_k=2,
                                          pow_difficulty=2, attack=atk))
                for r in range(3):
                    s.train_round(xtr[r * 48:(r + 1) * 48],
                                  ytr[r * 48:(r + 1) * 48])
                return s
            a, b = run("off"), run("on")
            assert b.mesh_shards == 4
            assert digest_tree(a.experts) == digest_tree(b.experts), fw
            assert digest_tree(a.gate) == digest_tree(b.gate), fw
            la, _, _ = a.infer(xte[:32])
            lb, _, _ = b.infer(xte[:32])
            assert np.asarray(la).tobytes() == np.asarray(lb).tobytes(), fw
            print(fw, "MESH OK")
    """, 8, repo_src, timeout=900)
    assert out.count("MESH OK") == 2


def test_mesh_audit_recompute_runs_on_one_device(repo_src):
    """A Pallas kernel cannot be partitioned over a mesh (the TPU
    compiler refuses it; interpret mode on the CPU never notices), so
    every commitment/audit recompute call must be a one-device program:
    its committed arguments — the shard's expert slice and task — live
    on the device owning that edge shard."""
    out = run_with_devices(_COMMON + """
        seen = []
        s = BMoESystem(BMoEConfig(
            framework="optimistic", dispatch="sparse", mesh="on",
            mesh_shards=4, num_experts=8, top_k=2, pow_difficulty=2,
            attack=AttackConfig(malicious_edges=(1,), attack_prob=1.0),
            trust=TrustConfig(audit_rate=1.0, num_verifiers=2,
                              challenge_window=1)))
        call = s._batched_recompute_call
        def checked(*args):
            devs = set()
            for leaf in jax.tree_util.tree_leaves(args):
                if isinstance(leaf, jax.Array) and leaf.committed:
                    devs |= set(leaf.sharding.device_set)
            seen.append(frozenset(devs))
            return call(*args)
        s._batched_recompute_call = checked
        for r in range(3):
            s.train_round(xtr[r * 48:(r + 1) * 48], ytr[r * 48:(r + 1) * 48])
        s.flush_trust()
        assert s.protocol.stats["rolled_back"] >= 1
        assert seen and all(len(d) == 1 for d in seen), seen
        assert len(set(seen)) == 4, set(seen)       # one per edge shard
        print("ONE DEVICE PER RECOMPUTE OK", len(seen))
    """, 4, repo_src)
    assert "ONE DEVICE PER RECOMPUTE OK" in out


def test_mesh_bank_actually_sharded(repo_src):
    """The expert bank must really live sharded over the edge mesh (one
    E/msize slice per device), not replicated."""
    out = run_with_devices("""
        import numpy as np, jax
        from repro.core.bmoe import BMoEConfig, BMoESystem
        from repro.trust.protocol import TrustConfig
        s = BMoESystem(BMoEConfig(framework="optimistic", dispatch="sparse",
                                  mesh="on", num_experts=8, top_k=2,
                                  pow_difficulty=2,
                                  trust=TrustConfig(audit_rate=0.5,
                                                    num_verifiers=1,
                                                    challenge_window=1)))
        assert s.mesh_shards == 8
        leaf = jax.tree_util.tree_leaves(s.experts)[0]
        shard_shapes = {d.data.shape[0] for d in leaf.addressable_shards}
        assert shard_shapes == {1}, shard_shapes     # E_l = 8/8 experts
        assert len(leaf.addressable_shards) == 8
        print("BANK SHARDED OK")
    """, 8, repo_src)
    assert "BANK SHARDED OK" in out
