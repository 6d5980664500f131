"""Device time per round of the batched FEL round program (the jitted
``round_fn`` of ``fl/batched_fel``, module ``jit_round_fn``)."""

MODULE = "jit_round_fn"


def read(ctx):
    ns = ctx.trace.module_ns(MODULE)
    if ns == 0 or ctx.rounds == 0:
        return None
    return ns / ctx.rounds / 1e6
