"""Seconds from process start to the window's first arrival: data, index
build, server start and warm-up."""


def read(ctx):
    return ctx.setup_s
