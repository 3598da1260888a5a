"""resends_per_batch: insert calls per batch, k-mer counting.

Counted by the harness: every call of the insert program in the window,
re-sends of items whose ok was false included, over the batches sent.
1.0 means no batch needed a re-send.  Moves ``ops_per_s``.
"""


def read(ctx):
    batches = ctx.counters.get("batches", 0)
    if not batches:
        return None
    return ctx.counters["insert_calls"] / batches
