"""Mean over the window's predict batches of the training chunks issued
but not yet drained when the batch's predict was issued: the chunk
programs it queued behind (``ModelServer.status()`` histogram
``chunks_ahead``, less what set-up counted)."""

from bench import counters


def read(run):
    return counters.mean(counters.window(run, "chunks_ahead"))
