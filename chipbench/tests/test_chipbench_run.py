"""Whole runs at a tiny size on the CPU, with the look for the chip
skipped (``run.execute``), and the command's refusals."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_paths  # noqa: F401  (first: the import path)
import run
import smoke

CHAT = "mixtral-8x7b.chat-verified"
BATCH = "mixtral-8x7b.batch-decode"
ROUNDS = "bmoe-rounds-fmnist.attacked"


def _cmd(root, workload=CHAT):
    return [sys.executable, str(root / "chipbench" / "run.py"),
            "--workload", workload, "--seed", str(2**31 + 5),
            "--seconds", "1", "--trace", "0"]


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(_cmd(run.ROOT), cwd=run.ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(_cmd(tmp_path), cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_metric_selection():
    b = smoke.bench()
    names = lambda cell, trace: [m["name"] for m in
                                 run.cell_metrics(b, cell, trace)]
    assert names(CHAT, False) == ["ttft_p95_ms", "tpot_p95_ms", "setup_s"]
    assert names(BATCH, False) == ["gen_tokens_s", "setup_s"]
    assert names(ROUNDS, False) == ["round_samples_s", "setup_s"]
    assert all(n.endswith(".rounds") for n in names(ROUNDS, True))
    assert "queue_wait_p95_ms.chat" in names(CHAT, True)
    assert not any(n.endswith(".batch") for n in names(CHAT, True))
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert m["moves"] in names(cell, False)


@pytest.mark.parametrize("cell", [CHAT, BATCH, ROUNDS])
def test_cell_runs_correct_at_smoke_size(cell, capsys):
    res = smoke.execute(cell, seconds=1.0 if cell == ROUNDS else 2.0)
    assert res["correct"] is True
    assert set(res["metrics"]) == {m["name"] for m in run.cell_metrics(
        smoke.bench(), cell, False)}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-1].startswith("check ")
    json.dumps(res)


@pytest.mark.parametrize("cell,host,device", [
    (CHAT, {"queue_wait_p95_ms.chat", "mixed_tick_ms.chat",
            "trust_ms_per_tick.chat", "mfu.chat"},
     {"device_idle.chat", "serve_step_roofline.chat"}),
    (BATCH, {"occupancy.batch", "decode_tick_ms.batch", "mfu.batch"},
     {"device_idle.batch", "serve_step_roofline.batch"}),
    (ROUNDS, {"dispatch_ms.rounds", "trust_ms.rounds", "storage_ms.rounds",
              "chain_ms.rounds", "mfu.rounds"}, {"device_idle.rounds"}),
])
def test_traced_run_reads_host_metrics(cell, host, device):
    """On the CPU the trace has no device plane: the device metrics are
    left out, never written as 0; the host-side ones are read."""
    res = smoke.execute(cell, seconds=1.0 if cell == ROUNDS else 2.0,
                        trace=True)
    got = set(res["metrics"])
    assert got == host
    assert all(res["metrics"][k]["value"] > 0 for k in got)
    assert not device & got
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
