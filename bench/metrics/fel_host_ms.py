"""Host time per round of the FEL round's own host work: the ``fel.prep``
spans (the batch plan and the upload of its index and seed tensors)."""

import program_spans


def read(ctx):
    return program_spans.per_round_ms(ctx, "fel.prep")
