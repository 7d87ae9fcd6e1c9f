"""Device self time of the statistics kernel in the traced window over
the window's steps, in milliseconds.  The kernel is found by its stable
name, ``vht_stats_update`` (VHT) or ``rule_stats_update`` (AMRules)."""

NAMES = ("vht_stats_update", "rule_stats_update")


def _base(op: str) -> str:
    """``%vht_stats_update.3 = ...`` -> ``vht_stats_update``."""
    short = op.split(" = ")[0].lstrip("%")
    head, _, tail = short.rpartition(".")
    return head if head and tail.isdigit() else short


def read(run):
    if run.trace is None or not run.trace["devices"] or run.steps <= 0:
        return None
    ops = run.trace["devices"][0]["op_self_time"]
    found = [t for op, t in ops.items() if _base(op) in NAMES]
    if not found:
        return None
    return 1e3 * sum(found) / run.steps
