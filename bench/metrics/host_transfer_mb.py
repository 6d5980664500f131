"""Megabytes per round copied between host and device where the program
copies on purpose: the ``h2d_bytes`` and ``d2h_bytes`` attrs of its
spans (W(k) rows, gw(k), the FEL batch plan, the test set)."""

import program_spans


def read(ctx):
    total = program_spans.attr_per_round(ctx, ("h2d_bytes", "d2h_bytes"))
    return None if total is None else total / 1e6
