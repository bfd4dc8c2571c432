"""Seconds of the program's ``build_index`` on the host clock, ending in
``block_until_ready``."""


def read(ctx):
    return ctx.build_s
