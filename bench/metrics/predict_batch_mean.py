"""Requests answered in the window per predict batch the server ran
(``ModelServer.status()``, less what set-up answered)."""


def read(run):
    if run.server is None:
        return None
    batches = run.server["batches"] - run.server0["batches"]
    answered = run.server["answered"] - run.server0["answered"]
    return answered / batches if batches else None
