"""Host time per round in which the consensus holds the chip idle: the
harness's annotations around each consensus phase, minus their overlap
with device-busy intervals."""


def read(ctx):
    if not ctx.trace.annotated("bench.phase.") or ctx.rounds == 0:
        return None
    return ctx.trace.host_only_ns("bench.phase.") / ctx.rounds / 1e6
