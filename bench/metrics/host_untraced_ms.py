"""Device-idle ms per round inside the ``round`` span that no work span
covers (a work span is any but ``round``, ``consensus``, ``phase:*`` and
``hcds:*``): host time no span names yet. Reads the program's spans as
the profiler recorded them, on the device ops' clock."""

import program_spans


def read(ctx):
    events = program_spans.events(ctx)
    if not any(e.name == "round" for e in events) or ctx.rounds == 0:
        return None
    return (program_spans.untraced_ns(events, program_spans.busy(ctx))
            / ctx.rounds / 1e6)
