"""Host time per round blocked on the chip: the ``device.wait`` spans,
where the host first needs a device result (W(k), ME's similarities and
gw(k), the test metrics)."""

import program_spans


def read(ctx):
    return program_spans.per_round_ms(ctx, "device.wait")
