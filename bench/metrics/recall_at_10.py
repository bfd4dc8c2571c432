"""Mean recall@10 of every answer served in the run, against the exact
float32 scan of the raw vectors (``bench/checks.py``)."""


def read(ctx):
    return ctx.numbers["recall_at_10"] if ctx.numbers else None
