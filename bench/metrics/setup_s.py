"""Process start to the first due request: traffic, binding, weights, warm-up, compiles."""


def read(ctx):
    return ctx.setup_s
