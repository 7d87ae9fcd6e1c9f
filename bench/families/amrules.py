"""AMRules configurations: the program's VAMR learner, its stream, its
reference.

The harness finds this module by the configuration's ``family`` key.  It
builds the program's ``VAMR`` (ordered rules, expansion feedback delayed
one step) from the configuration file, draws the waveform stream with the
benchmark's own generator copy, and holds the program to
``bench/ref/amrules.py``.  The targets are the integers 0, 1 and 2, so
rule structure, counts and target moments are exact and compared exactly;
rule heads seeded from a default-rule mean and the Page-Hinkley
statistics are compared by their relative gap.
"""

from __future__ import annotations

import jax.numpy as jnp

from bench.gen.waveform import WaveformStream
from bench.ref import amrules as ref

METRIC = "abs_err"            # the prequential metric: absolute error
EXACT = ("active", "pred_attr", "pred_op", "pred_bin", "pred_valid",
         "head_n", "since", "stats", "d_stats", "d_n", "d_sum", "d_since",
         "n_created", "n_removed", "n_feats", "n_rules", "pend_rule_valid",
         "pend_attr", "pend_op", "pend_bin", "pend_timer")
FLOAT = ("head_sum", "ph_m", "ph_min", "ph_err")


def sizes(cfg: dict) -> dict:
    return {
        "n_attrs": cfg["n_signal"] + cfg["n_noise"],
        "n_bins": cfg["n_bins"], "max_rules": cfg["max_rules"],
        "max_feats": cfg["max_feats"], "n_min": cfg["n_min"],
        "delta": cfg["delta"], "tau": cfg["tau"],
        "ph_lambda": cfg["ph_lambda"], "ph_alpha": cfg["ph_alpha"],
        "delay": cfg["delay"],
    }


def stream(cfg: dict) -> WaveformStream:
    return WaveformStream()


def learner(cfg: dict):
    """The program's VAMR with its default implementations."""
    from repro.ml.amrules import VAMR, RulesConfig
    c = sizes(cfg)
    return VAMR(RulesConfig(
        n_attrs=c["n_attrs"], n_bins=c["n_bins"], max_rules=c["max_rules"],
        max_feats=c["max_feats"], n_min=c["n_min"], delta=c["delta"],
        tau=c["tau"], ph_lambda=c["ph_lambda"], ph_alpha=c["ph_alpha"],
        delay=c["delay"]))


def program_state(state) -> dict:
    return {k: state[k] for k in EXACT + FLOAT}


def ref_init(cfg: dict, dtype=jnp.float32):
    return ref.init(sizes(cfg), dtype)


def ref_chunk(s, x, y, cfg: dict):
    """(state, absolute error summed per step) over one chunk."""
    return ref.run_chunk(s, x, y, tuple(sorted(sizes(cfg).items())))


def ref_predict(s, x, cfg: dict):
    return ref.predict(s, x)


def ref_view(s) -> dict:
    return ref.program_view(s)


def metric_gap(prog_metric: float, ref_total: float, seen: float):
    """The prequential mean absolute error against the reference's summed
    error: their gap relative to the reference's mean."""
    ref_metric = ref_total / seen
    return "abs_err_gap", abs(prog_metric - ref_metric) / max(
        abs(ref_metric), 1e-30)
