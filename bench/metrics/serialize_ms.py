"""Host time per round of ``serialize`` spans: pulling each W(k) row to
the host and packing its canonical bytes."""

import program_spans


def read(ctx):
    return program_spans.per_round_ms(ctx, "serialize")
