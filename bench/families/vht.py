"""VHT configurations: the program's learner, its stream and its reference.

The harness finds this module by the configuration's ``family`` key.  It
builds the program's ``VHT`` from the sizes in the configuration file,
draws the stream with the benchmark's own generator copy, and holds the
program to ``bench/ref/vht.py``: every state array exactly, and the
count of correct predictions exactly (counts are integers in float32).
"""

from __future__ import annotations

import jax.numpy as jnp

from bench.gen.random_tree import RandomTreeStream
from bench.ref import vht as ref

METRIC = "correct"            # the prequential metric: correct predictions
# state arrays compared exactly (all of them: counts are exact integers)
EXACT = ("split_attr", "split_bin", "children", "depth", "n_nodes",
         "stats", "class_counts", "n_total", "since_attempt")
FLOAT = ()


def sizes(cfg: dict) -> dict:
    """The reference's configuration, from the configuration file."""
    return {
        "n_attrs": cfg["n_nominal"] + cfg["n_numeric"],
        "n_bins": cfg["n_bins"], "n_classes": cfg["n_classes"],
        "max_nodes": cfg["max_nodes"], "max_depth": cfg["max_depth"],
        "n_min": cfg["n_min"], "delta": cfg["delta"], "tau": cfg["tau"],
    }


def stream(cfg: dict) -> RandomTreeStream:
    return RandomTreeStream(cfg["n_nominal"], cfg["n_numeric"],
                            cfg["n_classes"], cfg["concept_depth"],
                            cfg["concept_seed"])


def learner(cfg: dict):
    """The program's VHT, variant "local" (split decisions applied in the
    step), with its default implementations."""
    from repro.ml.htree import TreeConfig
    from repro.ml.vht import VHT, VHTConfig
    c = sizes(cfg)
    return VHT(VHTConfig(TreeConfig(
        n_attrs=c["n_attrs"], n_bins=c["n_bins"], n_classes=c["n_classes"],
        max_nodes=c["max_nodes"], max_depth=c["max_depth"],
        n_min=c["n_min"], delta=c["delta"], tau=c["tau"])))


def program_state(state) -> dict:
    return {k: state[k] for k in EXACT + FLOAT}


def ref_init(cfg: dict, dtype=jnp.float32):
    return ref.init(sizes(cfg), dtype)


def ref_chunk(s, x, y, cfg: dict):
    """(state, correct predictions per step) over one chunk."""
    return ref.run_chunk(s, x, y, tuple(sorted(sizes(cfg).items())))


def ref_predict(s, x, cfg: dict):
    return ref.predict(s, x, cfg["max_depth"])


def ref_view(s) -> dict:
    return ref.program_view(s)


def metric_gap(prog_metric: float, ref_total: float, seen: float):
    """The prequential accuracy against the reference's correct
    predictions: the count of correct predictions that differ."""
    return "correct_diff", abs(round(prog_metric * seen - ref_total))
