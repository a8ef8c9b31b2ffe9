"""The yardstick's arithmetic, pinned to hand counts of mixtral-8x7b as
the benchmark cuts it (one of its 32 layers)."""
import jax
import pytest

import bench_paths  # noqa: F401  (first: the import path)
import costs
import lm_weights
import run
from peaks import peaks

C = run.load_config("mixtral-8x7b")


def test_published_keys_resolve():
    assert (C["num_layers"], C["d_model"], C["num_heads"], C["num_kv_heads"],
            C["head_dim"], C["moe_d_ff"], C["num_experts"],
            C["num_experts_per_tok"], C["vocab_size"], C["norm_eps"],
            C["rope_theta"], C["tie_embeddings"]) == (
        1, 4096, 32, 8, 128, 14336, 8, 2, 32000, 1e-5, 1e6, False)


def test_weight_bytes_and_parameters():
    # per layer: 2 norms (8,192) + attention 4096*(4096+1024+1024+4096)
    # (41,943,040) + router 32,768 + 8 experts * 3 * 4096 * 14336
    # (1,409,286,144) = 1,451,270,144; embedding + head (2 * 32000 *
    # 4096) + final norm = 1,713,418,240 parameters, 4 bytes each
    total, active = lm_weights.param_count(C)
    assert total == 1_713_418_240
    assert lm_weights.weight_bytes(C) == 6_853_672_960
    # one token: attention 41,943,040 + router 32,768 + 2 experts *
    # 176,160,768 + head 131,072,000 = 525,369,344
    assert active == 525_369_344
    # at the published 32 layers: 46.7 B parameters, 12.9 B active (the
    # source counts the embedding, a row lookup here, among the active)
    total, active = lm_weights.param_count(dict(C, num_layers=32))
    assert round(total / 1e9, 1) == 46.7
    assert round((active + 32000 * 4096) / 1e9, 1) == 12.9


def test_kv_bytes():
    assert costs.kv_row_bytes(C) == 2 * 1 * 8 * 128 * 4
    assert costs.kv_cache_bytes(C, 32, 2048) == 536_870_912


def test_flops_closed_form_matches_token_sum():
    for a, b in ((0, 1), (0, 17), (100, 164), (2000, 2047)):
        assert costs.positions_flops(C, [(a, b)]) == sum(
            costs.token_flops(C, p) for p in range(a, b))
    # 2 * 525,369,344 for the products, 4 * 1 * 32 * 128 per key
    assert costs.token_flops(C, 0) == 2 * 525_369_344 + 16_384


# weights one micro-step streams, under "default" (one bfloat16 pass):
# the product weights, attention 41,943,040 + router 32,768 + 8 experts
# 1,409,286,144 + head 131,072,000 = 1,582,333,952 parameters, at 2
# bytes (3,164,667,904); the norm scales, 3 * 4096 at 4 bytes (49,152);
# the embedding only in the rows the busy slots gather, 4096 * 4 each
PRODUCT_PARAMS = 41_943_040 + 32_768 + 1_409_286_144 + 131_072_000
STREAMED_PER_TICK = 2 * PRODUCT_PARAMS + 4 * 3 * 4096
EMBED_ROW = 4096 * 4


def test_streamed_weight_bytes():
    assert PRODUCT_PARAMS == 1_582_333_952
    assert STREAMED_PER_TICK == 3_164_717_056
    assert lm_weights.streamed_bytes(C, 1, 0) == STREAMED_PER_TICK
    # 32 busy slots: 3,165,241,344 bytes, 3.86 ms at 819 GB/s
    assert lm_weights.streamed_bytes(C, 1, 32) == (STREAMED_PER_TICK
                                                   + 32 * EMBED_ROW)
    assert lm_weights.streamed_bytes(C, 16, 512) == 16 * (
        STREAMED_PER_TICK + 32 * EMBED_ROW)
    # one micro-step of 32 slots at positions 0..31: the weights above,
    # and slot p reads p + 1 cached rows and writes one (8,192 bytes each)
    assert costs.serve_microstep_bytes(C, range(32)) == (
        STREAMED_PER_TICK + 32 * EMBED_ROW
        + sum(p + 2 for p in range(32)) * 2 * 8 * 128 * 4)


@pytest.mark.parametrize("precision, stored, width", [
    ("default", "float32", 2), ("highest", "float32", 4),
    ("high", "float32", 4), ("highest", "bfloat16", 2)])
def test_product_width_follows_the_file(precision, stored, width):
    c = dict(C, matmul_precision=precision, torch_dtype=stored)
    assert lm_weights.product_width(c) == width
    norms_and_row = lm_weights.stored_width(c) * (3 * 4096 + 4096)
    assert lm_weights.streamed_bytes(c, 1, 1) == (width * PRODUCT_PARAMS
                                                  + norms_and_row)


def test_highest_streams_the_stored_float32():
    c = dict(C, matmul_precision="highest")
    # every weight but the embedding, at 4 bytes: the stored size less
    # the table, plus the one row gathered
    assert lm_weights.streamed_bytes(c, 1, 1) == (
        lm_weights.weight_bytes(C) - 4 * 32000 * 4096 + EMBED_ROW)


def test_unknown_precision_is_refused():
    with pytest.raises(KeyError, match="matmul_precision"):
        lm_weights.product_width(dict(C, matmul_precision="exact"))


def test_bytes_closed_form_matches_microsteps():
    runs = [(5, 9), (0, 3)]
    per_step = [costs.serve_microstep_bytes(C, [5 + t, t]) for t in range(3)]
    per_step.append(costs.serve_microstep_bytes(C, [8]))
    # 4 micro-steps, 7 positions processed
    assert sum(per_step) == (4 * STREAMED_PER_TICK + 7 * EMBED_ROW
                             + costs.runs_kv_bytes(C, runs))
    assert costs.serve_step_bytes(C, 4, runs) == sum(per_step)


def test_weight_layout_is_the_programs():
    """The benchmark's weights fit the serving engine's parameter tree."""
    from repro.models import transformer as tfm
    from repro.models.builder import abstract
    from systems.lm_serve import program_config
    want = jax.tree_util.tree_map(lambda a: tuple(a.shape),
                                  abstract(tfm.model_decl(program_config(C))))
    got = jax.tree_util.tree_map(lambda leaf: tuple(leaf[0]),
                                 lm_weights.shapes(C),
                                 is_leaf=lambda x: isinstance(x, tuple))
    assert got == want


def test_round_and_moe_gemm_costs():
    r = run.load_json(run.HERE / "configs" / "bmoe-rounds-fmnist.json")
    # gate 2*784*10 + 3 experts * (2*784*256 + 2*256*10) = 1,235,264
    # forward, times 3 with the backward pass
    assert costs.round_sample_flops(r) == 3 * 1_235_264
    # 10 experts, capacity 96 rows, 784 -> 256: 2*10*96*784*256 FLOPs;
    # 4 bytes * 10 * (96*784 + 784*256 + 96*256) moved
    assert costs.moe_gemm_cost(10, 96, 784, 256) == (385_351_680,
                                                     12_021_760)


def test_peak_table():
    v5e = peaks("TPU v5 lite")
    assert (v5e["bf16_flops_s"], v5e["hbm_bytes_s"], v5e["hbm_bytes"]) == \
        (197e12, 819e9, 16e9)
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v9 imaginary")
