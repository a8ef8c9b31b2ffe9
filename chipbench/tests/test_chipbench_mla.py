"""The latent-attention cell ``moonlight-16b-a3b.reasoning-decode`` at a
tiny size on the CPU: it runs end to end through ``run.execute`` from
files and entries it adds, changing no file of the benchmark; the
control and two faults of the program read ``correct`` false; the
selection bias changes selections at the published router width; and
the cost functions agree with counts made by hand."""
import copy
import hashlib

import jax
import jax.numpy as jnp
import pytest

import bench_paths  # noqa: F401  (first: the import path)
import mla_costs
import mla_weights
import run
import smoke
from systems import lm_serve_mla

CELL = "moonlight-16b-a3b.reasoning-decode"
# the program's products are exact float32 on the CPU and reassociate
# the reference's (absorbed against expanded attention): a mean gap of
# 1e-6 or more is a departure; the bf16 control reads about 4e-5
LIMIT = 1e-6
SIZES = dict(num_layers=3, d_model=128, num_heads=4, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             d_ff=256, moe_d_ff=64, vocab_size=512)


def config():
    c = run.load_config("moonlight-16b-a3b")
    c.update(SIZES, serving={"slots": 3, "cache_len": 128,
                             "prefill_chunk": 4})
    return c


def mix():
    m = copy.deepcopy(run.load_json(run.HERE / "traffic"
                                    / "reasoning-decode.json"))
    m["trace_seconds"] = 0.5
    m["prompt_tokens"].update(min=4, max=24)
    m["output_tokens"].update(min=3, max=40, median=12)
    m["backlog"] = 3
    m["check"].update(sample_tokens=200, min_tokens=100,
                      mean_logit_gap=LIMIT)
    return m


def execute(trace=False, seconds=3.0):
    return run.execute(smoke.bench(), smoke.cell(CELL), 2**31 + 7, seconds,
                       trace, smoke.CPU, smoke.PEAK, c=config(), mix=mix())


def _digests():
    return {p.relative_to(run.HERE): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(run.HERE.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_cell_runs_end_to_end_and_changes_no_file():
    before = _digests()
    res = execute()
    assert res["correct"] is True
    assert set(res["metrics"]) == {"gen_tokens_s", "setup_s"}
    assert res["checks"]["sampled_tokens"]["value"] >= 100
    traced = execute(trace=True)
    assert traced["correct"] is True
    # on the CPU the trace has no device ops: the rooflines read nothing
    assert set(traced["metrics"]) == {"mfu.reasoning"}
    assert traced["metrics"]["mfu.reasoning"]["value"] > 0
    assert _digests() == before


def test_the_cells_files_are_found_by_name():
    bench = smoke.bench()
    cell = smoke.cell(CELL)
    assert run.load_config(cell["config"])["system"] == "lm_serve_mla"
    names = {m["name"] for m in run.cell_metrics(bench, CELL, True)}
    assert names == {"mla_attention_roofline.reasoning",
                     "serve_step_roofline.reasoning", "mfu.reasoning"}
    for n in names:
        assert (run.HERE / "metrics" / f"{n}.py").exists()
    assert {m["name"] for m in run.cell_metrics(bench, CELL, False)} \
        == {"gen_tokens_s", "setup_s"}


def test_bf16_control_in_the_programs_place_is_not_correct(monkeypatch):
    import lm_reference
    read = lm_reference.readings

    def control(ref, sample, controls=()):
        return {"served": read(ref, sample, ("bf16",))["bf16"]}

    monkeypatch.setattr(lm_reference, "readings", control)
    res = execute()
    assert res["correct"] is False
    gap = res["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]


def _break(monkeypatch, fault):
    if fault == "rope_term":
        # the q_pe . k_pe term of the scores left out
        from repro.models import layers
        project = layers._mla_project

        def without_rope(params, x, positions, cfg):
            q_nope, q_pe, c, k_pe = project(params, x, positions, cfg)
            return q_nope, jnp.zeros_like(q_pe), c, k_pe
        monkeypatch.setattr(layers, "_mla_project", without_rope)
    else:
        # softmax scoring in the place of sigmoid
        import dataclasses
        program_config = lm_serve_mla.program_config
        monkeypatch.setattr(lm_serve_mla, "program_config", lambda c: (
            dataclasses.replace(program_config(c), router_scoring="softmax")))


@pytest.mark.parametrize("fault", ["rope_term", "softmax_router"])
def test_broken_program_is_not_correct(monkeypatch, fault):
    _break(monkeypatch, fault)
    res = execute()
    assert res["correct"] is False
    gap = res["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("n_group", 8), ("norm_topk_prob", False),
    ("rope_scaling", {"type": "yarn", "factor": 40})])
def test_resolve_refuses_what_the_program_does_not_cover(key, value):
    """A published key the program and the reference do not cover is
    refused when the file is read, not served as something else."""
    c = run.load_json(run.HERE / "configs" / "moonlight-16b-a3b.json")
    lm_serve_mla.resolve(dict(c))
    with pytest.raises(ValueError, match=key):
        lm_serve_mla.resolve({**c, key: value})


def test_selection_bias_changes_some_selections():
    """At the published router width (2048 -> 64, top 6) and the
    weights' standard deviations, the bias ``b_corr`` changes the chosen
    set of some tokens and leaves most alone."""
    c = run.load_config("moonlight-16b-a3b")
    key = jax.random.PRNGKey(0)
    router = 0.02 * jax.random.normal(key, (c["d_model"], c["num_experts"]))
    bias = mla_weights.B_CORR_STD * jax.random.normal(
        jax.random.fold_in(key, 1), (c["num_experts"],))
    h = jax.random.normal(jax.random.fold_in(key, 2), (4096, c["d_model"]))
    s = jax.nn.sigmoid(h @ router)
    k = c["num_experts_per_tok"]
    plain = jnp.sort(jax.lax.top_k(s, k)[1], -1)
    biased = jnp.sort(jax.lax.top_k(s + bias, k)[1], -1)
    changed = float(jnp.any(plain != biased, -1).mean())
    assert 0.05 < changed < 0.95


def test_scoped_ops_are_device_ops_of_the_step():
    c = config()
    params, eng = lm_serve_mla.setup(c, mix(), 5)
    text = lm_serve_mla.step_hlo(eng, c["serving"]["prefill_chunk"])
    ops = lm_serve_mla.scoped_ops(text)
    assert ops and all(f"%{n} = " in text or f"{n} = " in text for n in ops)
    assert not lm_serve_mla.scoped_ops(text, "no_such_scope")


def test_costs_match_hand_counts():
    c = dict(SIZES, num_layers=2, leading_dense=1, num_experts=8,
             held_experts=2, num_experts_per_tok=3, num_shared_experts=1,
             torch_dtype="float32", matmul_precision="default")
    d, H, V = 128, 4, 512
    attn = d * H * 24 + d * 40 + 32 * H * 32 + H * 16 * d
    assert mla_costs.attn_params(c) == attn
    # per token: 2 layers' attention products, 1 dense MLP, 1 router,
    # 1 shared expert of 64, the head
    dense = 2 * (2 * attn + 3 * d * 256 + d * 8 + 3 * d * 64 + d * V)
    assert mla_costs.dense_token_flops(c) == dense
    key = 2 * 2 * H * (2 * 32 + 8)
    assert mla_costs.attn_key_flops(c) == key
    # positions 5, 6, 7 with 2 held assignments each
    assert mla_costs.runs_flops(c, [(5, 8)], 2.0) == pytest.approx(
        3 * (dense + 2 * 2 * 3 * d * 64) + key * (6 + 7 + 8))
    row = 2 * 40 * 4
    assert mla_costs.runs_latent_bytes(c, [(5, 8)]) == row * (7 + 8 + 9)
    # weights: products at 2 B (default precision), norms and bias at
    # 4 B, 3 gathered embedding rows at 4 B
    prod = (2 * attn + 3 * d * 256 + d * 8 + 2 * 3 * d * 64
            + 3 * d * 64 + d * V)
    elem = 4 * d + d + 32 * 2 + 8
    assert mla_costs.weight_bytes(c, 2, 3) == 2 * (2 * prod + 4 * elem) \
        + 3 * d * 4
    assert mla_costs.mla_step_bytes(c, 1, [(5, 8)]) == 2 * 2 * attn \
        + row * 24
    assert mla_costs.serve_step_bytes(c, 3, [(5, 8)]) == \
        mla_costs.weight_bytes(c, 3, 3) + row * 24
