"""The timed path broken underneath a whole run: ``correct`` must come
out false.  Faults a serving cell can have: a token altered where it is
produced, and a step that hands back its state (the KV cache)
unchanged.  Faults a rounds cell can have: a step that hands back its
parameters unchanged, and half of the batch left out, the mean taken
over the rest.  (No cell runs across chips, so there is no exchange
between chips to leave out.)"""
import pytest

import bench_paths  # noqa: F401  (first: the import path)
import smoke
from systems import lm_serve


def _break(monkeypatch, fault):
    setup = lm_serve.setup

    def broken_setup(c, mix, seed):
        params, eng = setup(c, mix, seed)
        step, vocab = eng._step_fn, c["vocab_size"]

        def altered(params, caches, batch):
            outs, new = step(params, caches, batch)
            if fault == "token":
                return (outs + 1) % vocab, new
            return outs, caches                  # state left unchanged
        eng._step_fn = altered
        return params, eng

    monkeypatch.setattr(lm_serve, "setup", broken_setup)


@pytest.mark.parametrize("cell,fault", [
    ("mixtral-8x7b.chat-verified", "token"),
    ("mixtral-8x7b.chat-verified", "state"),
    ("mixtral-8x7b.batch-decode", "token"),
])
def test_broken_step_is_not_correct(monkeypatch, cell, fault):
    _break(monkeypatch, fault)
    res = smoke.execute(cell)
    assert res["correct"] is False
    gap = res["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]


def _break_rounds(monkeypatch, fault):
    from repro.core import bmoe
    init = bmoe.BMoESystem.__init__

    def broken_init(self, cfg):
        init(self, cfg)
        step = self._train_step

        def broken(gate, experts, x, y, *rest):
            if fault == "half":
                h = x.shape[0] // 2
                return step(gate, experts, x[:h], y[:h], *rest)
            _, _, metrics = step(gate, experts, x, y, *rest)
            return gate, experts, metrics        # state left unchanged
        self._train_step = broken

    monkeypatch.setattr(bmoe.BMoESystem, "__init__", broken_init)


@pytest.mark.parametrize("fault", ["state", "half"])
def test_broken_round_is_not_correct(monkeypatch, fault):
    _break_rounds(monkeypatch, fault)
    res = smoke.execute("bmoe-rounds-fmnist.attacked", seconds=1.0)
    assert res["correct"] is False
    numbers = ("loss_gap", "first_grad_gap", "change_gap")
    assert any(res["checks"][k]["value"] > res["checks"][k]["limit"]
               for k in numbers)
