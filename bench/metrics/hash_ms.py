"""Host time per round inside ``crypto.sha256`` spans (their union, so a
hash nested in another is counted once)."""

import program_spans


def read(ctx):
    return program_spans.per_round_ms(ctx, "crypto.sha256", union=True)
