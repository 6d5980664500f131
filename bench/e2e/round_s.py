"""Window seconds over the rounds completed in it: the window runs from
its start to the end of its last completed round, so a stall counts."""


def read(ctx):
    return ctx.window.seconds / len(ctx.window.walls)
