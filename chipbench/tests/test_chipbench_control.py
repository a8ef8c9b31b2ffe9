"""The control: the reference in bfloat16, one step below the float32
the configuration states, put in the program's place at the same
prompts and served tokens, or at the same first three training steps.
It must read above the limit where the program reads under it."""
import gc

import jax.numpy as jnp

import bench_paths  # noqa: F401  (first: the import path)
import smoke
from lm_reference import Reference, readings
from systems import lm_serve


def test_bf16_control_fails_where_program_passes():
    c = smoke.config()
    m = smoke.mix("chat-verified")
    m["check"]["sample_tokens"] = 120
    params, eng = lm_serve.setup(c, m, 11)
    w = lm_serve.drive(eng, c, m, 11, 3.0)
    chosen = lm_serve.sample(eng, w, m, 11)
    del eng
    w.driver.eng = None
    gc.collect()
    got = readings(Reference(c, params), chosen, ("bf16",))
    limit = m["check"]["mean_logit_gap"]
    assert got["served"]["tokens"] >= 60
    assert got["served"]["mean"] <= limit
    assert got["bf16"]["mean"] > limit


def test_bf16_control_fails_the_rounds_limits():
    import rounds_reference as ref
    from systems import bmoe_rounds
    cell = smoke.cell("bmoe-rounds-fmnist.attacked")
    c, m = smoke.config(cell["config"]), smoke.mix(cell["traffic"])
    s, batches, record = bmoe_rounds.setup(c, m, 2**31 + 21)
    del s
    want = ref.trajectory(c, record["p0"], batches,
                          precision=c["matmul_precision"])
    limits = m["check"]
    program = ref.numbers(c, record, want)
    control = ref.numbers(c, ref.trajectory(
        c, record["p0"], batches, precision=c["matmul_precision"],
        dtype=jnp.bfloat16), want)
    assert all(program[k] <= limits[k] for k in limits)
    assert any(control[k] > limits[k] for k in limits)


def test_bf16_control_in_the_programs_place_is_not_correct(monkeypatch):
    """A whole serving run through ``run.execute`` with the control put
    in the program's place: at the same prompts and served tokens, the
    token the bfloat16 reference puts first is what is compared."""
    import lm_reference
    read = lm_reference.readings

    def control(ref, sample, controls=()):
        return {"served": read(ref, sample, ("bf16",))["bf16"]}

    monkeypatch.setattr(lm_reference, "readings", control)
    res = smoke.execute("mixtral-8x7b.chat-verified")
    assert res["correct"] is False
    gap = res["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_bf16_control_in_the_rounds_place_is_not_correct(monkeypatch):
    """A whole rounds run through ``run.execute`` with the control's
    first three steps (the reference in bfloat16) in the place of the
    system's."""
    import rounds_reference as ref
    from systems import bmoe_rounds
    setup = bmoe_rounds.setup

    def control_setup(c, mix, seed):
        s, batches, record = setup(c, mix, seed)
        low = ref.trajectory(c, record["p0"], batches,
                             precision=c["matmul_precision"],
                             dtype=jnp.bfloat16)
        return s, batches, dict(record, losses=low["losses"],
                                p1=low["p1"], p3=low["p3"])

    monkeypatch.setattr(bmoe_rounds, "setup", control_setup)
    res = smoke.execute("bmoe-rounds-fmnist.attacked", seconds=1.0)
    assert res["correct"] is False
    assert any(res["checks"][k]["value"] > res["checks"][k]["limit"]
               for k in ("loss_gap", "first_grad_gap", "change_gap"))
