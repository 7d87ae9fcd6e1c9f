"""The correctness check fails when the timed path is broken underneath:
a step that returns its state unchanged, half of each batch left out,
and an answer altered where it is produced.  (One chip: no exchange
between chips to leave out.)"""

import importlib

import pytest

from bench.tests import tiny


def _step_unchanged(step):
    def broken(self, state, xbin, y):
        _, metrics = step(self, state, xbin, y)
        return state, metrics
    return broken


def _half_batch(step):
    def broken(self, state, xbin, y):
        h = y.shape[0] // 2
        return step(self, state, xbin[:h], y[:h])
    return broken


FAULTS = {
    "vht-dense1000.train": ("repro.ml.vht.VHT", "state_mismatch"),
    "vamr-waveform40.train": ("repro.ml.amrules.AMRules", "state_mismatch"),
}


@pytest.mark.parametrize("workload", list(FAULTS))
@pytest.mark.parametrize("fault", [_step_unchanged, _half_batch])
def test_broken_step_is_not_correct(workload, fault, monkeypatch):
    path, check = FAULTS[workload]
    mod, cls = path.rsplit(".", 1)
    klass = getattr(importlib.import_module(mod), cls)
    monkeypatch.setattr(klass, "step", fault(klass.step))
    out = tiny.run(workload, monkeypatch)
    assert not out["correct"]
    assert out["checks"][check]["value"] > out["checks"][check]["limit"]


def test_altered_prediction_is_not_correct(monkeypatch):
    from repro.ml import htree
    predict = htree.predict

    def flipped(state, xbin, tc):
        pred, leaf = predict(state, xbin, tc)
        return pred.at[0].set(1 - pred[0]), leaf

    monkeypatch.setattr(htree, "predict", flipped)
    out = tiny.run("vht-dense1000.train", monkeypatch)
    assert not out["correct"]
    assert out["checks"]["correct_diff"]["value"] > 0


def test_altered_rule_prediction_is_not_correct(monkeypatch):
    from repro.ml import amrules
    first_cover = amrules.first_cover

    def skewed(cov, rc):
        first = first_cover(cov, rc)
        return first.at[0].set(rc.max_rules)     # instance 0: default rule

    monkeypatch.setattr(amrules, "first_cover", skewed)
    out = tiny.run("vamr-waveform40.train", monkeypatch)
    assert not out["correct"]


def test_altered_served_answer_is_not_correct(monkeypatch):
    from repro.serving import server
    make = server.make_predict_fn

    def altered(learner):
        fn = make(learner)
        return lambda state, x: fn(state, x).at[0].set(
            1 - fn(state, x)[0])

    monkeypatch.setattr(server, "make_predict_fn", altered)
    out = tiny.run("vht-dense1000.serve", monkeypatch)
    assert not out["correct"]
    assert out["checks"]["served_wrong"]["value"] > 0
    assert out["checks"]["state_mismatch"]["value"] == 0
