"""Host time per dispatch outside the wait for the device, in ms: the mean
over the traced window's ``zen.dispatch`` spans of their length less their
``zen.fetch`` spans (``bench/spans.py``). Enqueueing the eager chain,
stacking and padding rows, resolving handles."""
from bench import spans


def read(ctx):
    return None if ctx.trace is None else spans.host_ms_per_dispatch(ctx.trace)
