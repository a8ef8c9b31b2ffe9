"""Serving example: batched greedy generation through the serving engine,
a verified (commit-challenge-audit) serving session that finalizes only
audited outputs, plus the LM-scale trusted-MoE consensus demonstrated on
a multi-device mesh (a subprocess on 8 virtual CPU devices).

Run:  PYTHONPATH=src python examples/trusted_serving.py
"""
import os
import subprocess
import sys
import textwrap

from repro.configs import get_config
from repro.data.synthetic import serving_requests
from repro.serve.engine import ServingEngine
from repro.train.loop import init_model
from repro.trust.protocol import TrustConfig

# ------------------------------------------------ 1. serving engine
print("=== batched serving (smollm-360m reduced config) ===")
cfg = get_config("smollm-360m", smoke=True)
params = init_model(cfg, seed=0)
engine = ServingEngine(cfg, params, batch_slots=4, cache_len=96)
requests = list(serving_requests(cfg.vocab_size, 10, max_prompt=24,
                                 max_new=8, seed=0))
engine.submit(requests)
done = engine.run()
for rid in done:
    print(f"  request {rid}: generated {len(done[rid])} tokens "
          f"{done[rid][:6]}...")

# ------------------------- 2. verified serving (optimistic trust layer)
print("\n=== verified serving session (commit-challenge-audit) ===")
trust = TrustConfig(audit_rate=0.5, num_verifiers=2, challenge_window=6)
veng = ServingEngine(cfg, params, batch_slots=4, cache_len=96, trust=trust)
veng.submit(requests)
vdone = veng.run()
print(f"  finalized {len(vdone)}/{len(requests)} requests "
      f"(pending windows: {len(veng.pending_finalization)})")
assert {rid: toks for rid, toks in vdone.items()} == dict(done), \
    "verified session must serve the same tokens, just later"
commits = [e for e in veng.session_log if e["event"] == "commit"]
finals = [e for e in veng.session_log if e["event"] == "finalize"]
print(f"  session log: {len(commits)} commitments, {len(finals)} finalized")
print(f"  e.g. request {commits[0]['request']}: root "
      f"{commits[0]['root']}..., committed at tick {commits[0]['tick']}, "
      f"finalized at tick {finals[0]['tick']}")
# the audit pass: sampled per-tick leaves re-checked against each root
reports = veng.audit_all()
print(f"  audits: {len(reports)} passes, "
      f"{sum(len(r['sampled']) for r in reports)} leaves sampled, "
      f"revoked: {sum(r['revoked'] for r in reports)}")
# a tampered stream is caught and never finalizes
rid = requests[0]["id"]
rec = veng.records[rid]
rec.tokens = [t ^ 1 for t in rec.tokens]   # executor alters the stream
tam = [veng.audit_session(rid, v) for v in range(trust.num_verifiers)]
caught = any(t["revoked"] for t in tam)
print(f"  tampered request {rid}: revoked by audit -> {caught}; "
      f"still finalized -> {rid in veng.completed}")

# -------------------------------- 3. trusted vote on a replica mesh
print("\n=== B-MoE consensus at LM scale (r=4 replicas, 1 malicious) ===")
code = """
import jax, jax.numpy as jnp, numpy as np
from repro.core.trusted_moe import make_trust, LMAttack
from repro.launch.mesh import make_mesh
from repro.models.config import RedundancyConfig
mesh = make_mesh((1, 4, 2), ("data", "replica", "model"))
y = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 8, 32))  # (B,E,C,d)
for mode in ("faithful", "digest"):
    trust = make_trust(mesh, RedundancyConfig(4, mode), True,
                       LMAttack(malicious_replicas=(2,), noise_std=4.0))
    with mesh:
        out = jax.jit(trust)(y)
    ok = np.allclose(np.asarray(out), np.asarray(y), atol=1e-6)
    print(f"  mode={mode}: attack repaired by consensus -> {ok}")
"""
# the child runs on virtual CPU devices, pinned with JAX_PLATFORMS=cpu:
# this process already holds the accelerator (sections 1-2), and a child
# that asked for it too would fail or hang.  On a TPU host the replica
# mesh vote runs on real chips through ``chip_smoke.py --chips 4``.
env = dict(os.environ)
env["JAX_PLATFORMS"] = "cpu"
env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src") \
    + os.pathsep + env.get("PYTHONPATH", "")
out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                     capture_output=True, text=True, env=env)
print(out.stdout, end="")
if out.returncode:
    print(out.stderr)
print("done")
