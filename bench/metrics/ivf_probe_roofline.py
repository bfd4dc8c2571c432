"""Share of its roofline that the IVF probe kernel reached in the trace."""
from bench import readers


def read(ctx):
    return readers.roofline_share(ctx, "nsimplex_ivf_probe", readers.ivf_work)
