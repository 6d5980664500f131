"""Host time per round of ``repro.obs`` ``crypto.verify_batch`` spans."""


def read(ctx):
    spans = [s for s in ctx.spans if s.name == "crypto.verify_batch"]
    if not spans or ctx.rounds == 0:
        return None
    return sum(s.wall_dur for s in spans) / ctx.rounds * 1e3
