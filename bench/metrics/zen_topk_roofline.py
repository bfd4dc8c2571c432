"""Share of its roofline that the flat scan kernel reached in the trace."""
from bench import readers


def read(ctx):
    return readers.roofline_share(ctx, "nsimplex_zen_topk", readers.flat_work)
