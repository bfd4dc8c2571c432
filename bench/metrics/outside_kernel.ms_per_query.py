"""Device busy time outside the nsimplex_* kernels per query answered in
the traced window, in ms. Read for each cell under its own name
(``outside_kernel.ms_per_query.<cell kind>``), each moving the end-to-end
metric that its cell reports."""
from bench import readers


def read(ctx):
    return readers.outside_kernel_ms_per_query(ctx)
