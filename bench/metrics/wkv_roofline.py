"""The WKV recurrence's share of its roofline: its least time in a round,
the larger of its operations over the chip's bf16 peak and its bytes over
HBM bandwidth (``wkv_flops`` / ``wkv_bytes``, counted by the
configuration's ``wkv_counts``: forward and backward without
recomputation), over the device time of the ops under the ``wkv6`` scope
per round. A chunked or Pallas WKV reads as a gain and never above
100%."""

import op_scopes


def read(ctx):
    peak = ctx.peaks.get("bf16_flops")
    bw = ctx.peaks.get("hbm_bytes_per_s")
    flops, moved = ctx.flops.get("wkv_flops"), ctx.flops.get("wkv_bytes")
    ms = op_scopes.per_round_ms(ctx, "wkv6")
    if not (peak and bw and flops and moved) or ms is None:
        return None
    least = max(flops / peak, moved / bw)
    return 100.0 * least / (ms * 1e-3)
