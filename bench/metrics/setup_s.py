"""Seconds from process start to the start of the measured window:
weights, compiles (or cache loads), warm-up traffic."""


def read(ctx):
    return ctx.setup_s
