"""Median host time per window chunk in the chunk loop's ``dispatch``
stage: the engine call through the finite flag, the metric update and
the ticket (the program's stage table, span ``repro.chunk.dispatch``)."""

from bench import counters


def read(run):
    table = counters.stages()
    if not table or "dispatch" not in table:
        return None
    return counters.quantile(table["dispatch"], 0.5)
