"""Process start to the start of the window: data, weights, runtime,
compilation (or the compile cache) and the checked rounds."""


def read(ctx):
    return ctx.setup_s
