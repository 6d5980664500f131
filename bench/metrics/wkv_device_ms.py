"""Device time per round of the ops under the ``wkv6`` named scope (the
RWKV-6 WKV recurrence of ``models/rwkv6``: forward, rematerialised
forward and backward in FEL training, and the test set's forward), the
union of their intervals."""

import op_scopes


def read(ctx):
    return op_scopes.per_round_ms(ctx, "wkv6")
