"""Host time per round of ``crypto.sign`` spans (ECDSA signing: commit
envelopes, votes, the block)."""

import program_spans


def read(ctx):
    return program_spans.per_round_ms(ctx, "crypto.sign")
