"""Share of the traced window in which the device runs no operation and no
``zen.dispatch`` span is open, in percent (``bench/spans.py``): idle left
by the scheduler, the interpreter lock or other threads. Idle inside
dispatches is ``device.idle`` less this."""
from bench import spans


def read(ctx):
    if ctx.trace is None:
        return None
    return spans.idle_between_dispatches_percent(ctx.trace)
