"""A later change adds a cell, a traffic mix and a per-layer metric by
adding files and entries only: the harness finds them by name, and no
file that was there changes."""
import hashlib
import json
import shutil

import bench_paths  # noqa: F401  (first: the import path)
import run
import smoke


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_mix_and_metric_are_new_files_only(tmp_path, monkeypatch):
    bench_dir = tmp_path / "chipbench"
    shutil.copytree(run.HERE, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(bench_dir)

    mix = smoke.mix("chat-verified")
    mix.update(trust=None, description="chat without verification")
    (bench_dir / "traffic" / "chat-plain.json").write_text(json.dumps(mix))
    (bench_dir / "metrics" / "finished.chat-plain.py").write_text(
        '"""Requests finished in the window."""\n\n\n'
        'def read(w):\n'
        '    return sum(1 for r in w.records if r["done"])\n')
    bench = smoke.bench()
    cell = {"name": "mixtral-8x7b.chat-plain", "config": "mixtral-8x7b",
            "traffic": "chat-plain", "chips": 1, "why": "trust bypassed"}
    bench["workloads"].append(cell)
    bench["end_to_end"][0]["workloads"].append(cell["name"])
    bench["per_layer"].append({
        "name": "finished.chat-plain", "unit": "requests",
        "better": "higher", "source": "host_clock", "layer": "serve/scheduler",
        "moves": "ttft_p95_ms", "workloads": [cell["name"]]})

    after = _digests(bench_dir)
    assert {k: v for k, v in after.items() if k in before} == before

    monkeypatch.setattr(run, "HERE", bench_dir)
    c = smoke.config()
    plain = run.execute(bench, cell, 3, 2.0, False, smoke.CPU, smoke.PEAK, c=c)
    assert plain["correct"] is True
    assert set(plain["metrics"]) == {"ttft_p95_ms", "setup_s"}
    assert "honest_revoked" not in plain["checks"]
    traced = run.execute(bench, cell, 3, 2.0, True, smoke.CPU, smoke.PEAK,
                         c=c)
    assert traced["metrics"]["finished.chat-plain"]["value"] > 0
