"""A run with the timed path broken underneath comes out not correct.

Each test skips only the look for a chip and drives the rest of a run at
the small size, with one fault planted in the program: a served token
altered where it is produced, a completion lost or published twice; a
train step that returns its state unchanged, or that takes the mean over
half of its batch."""

import jax
import numpy as np
import pytest

from bench.kinds import open_loop_lm, stream_train
from repro.serve import ContinuousLMEngine, LMServingGroup
from test_rehearsal import rehearse


def failed_checks(res) -> set[str]:
    assert res["correct"] is False
    return {k for k, c in res["checks"].items() if not c["ok"]}


def patch_step(monkeypatch, alter):
    real = ContinuousLMEngine.step

    def step(self):
        return alter(real(self))

    monkeypatch.setattr(ContinuousLMEngine, "step", step)


def test_altered_token(monkeypatch, tmp_path):
    def alter(out):
        return [(rid, np.where(np.arange(len(g)) == len(g) // 2, (g + 1) % 256, g))
                for rid, g in out]

    patch_step(monkeypatch, alter)
    assert "served_logit_gap" in failed_checks(rehearse("yi6b.chat.steady", False, tmp_path))


@pytest.mark.parametrize("owes_unconsumed", [True, False])
def test_lost_completion(monkeypatch, tmp_path, owes_unconsumed):
    """Lost after the group consumed and committed it: owed either way."""
    monkeypatch.setattr(open_loop_lm, "GRACE_S", 2.0)
    lost = []

    def alter(out):
        if out and not lost and out[0][0] < open_loop_lm.WARM_RID:
            lost.append(out[0][0])
            return out[1:]
        return out

    patch_step(monkeypatch, alter)
    res = rehearse("yi6b.chat.steady", False, tmp_path, owes_unconsumed=owes_unconsumed)
    assert "unanswered" in failed_checks(res) and res["failed"] >= 1


def test_completion_published_twice(monkeypatch, tmp_path):
    def alter(out):
        return out + out[:1] if out and out[0][0] < open_loop_lm.WARM_RID else out

    patch_step(monkeypatch, alter)
    assert "duplicates" in failed_checks(rehearse("yi6b.chat.steady", False, tmp_path))


def test_state_left_unchanged(monkeypatch, tmp_path):
    def unchanged(model, opt):
        return jax.jit(lambda s, b: (s, {"loss": model.loss(s["params"], b)[0]})), None

    monkeypatch.setattr(stream_train, "build_train_step", unchanged)
    got = failed_checks(rehearse("yi6b-l2.train.stream", False, tmp_path))
    assert {"first_grad_norm_gap", "change_norm_gap"} <= got


def test_half_the_batch(monkeypatch, tmp_path):
    real = stream_train.build_train_step

    def half(model, opt):
        step, _ = real(model, opt)
        return (lambda s, b: step(s, {"tokens": b["tokens"][: b["tokens"].shape[0] // 2]})), None

    monkeypatch.setattr(stream_train, "build_train_step", half)
    assert failed_checks(rehearse("yi6b-l2.train.stream", False, tmp_path))


@pytest.mark.parametrize("owes_unconsumed", [True, False])
def test_requests_left_in_the_topic(monkeypatch, tmp_path, owes_unconsumed):
    """The group stops consuming partway through the window: what it left
    in the topic is owed only where the mix says so."""
    monkeypatch.setattr(open_loop_lm, "GRACE_S", 2.0)
    real = LMServingGroup.poll_all
    served = []

    def poll_all(self):  # the 4 warm-up requests, then 10 of the window's
        if sum(served) >= 14:
            return 0
        served.append(real(self))
        return served[-1]

    monkeypatch.setattr(LMServingGroup, "poll_all", poll_all)
    res = rehearse("yi6b.chat.steady", False, tmp_path, owes_unconsumed=owes_unconsumed)
    assert res["checks"]["unanswered"]["value"] > 0 if owes_unconsumed else \
        res["correct"] is True
