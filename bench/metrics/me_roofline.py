"""ME's share of its roofline: one read of the (N, D) float32 W at the
chip's HBM bandwidth, over the device time of all of ME's ops per round
(module ``jit_model_evaluation``: today the Eq. 1 einsum and the Pallas
cosine kernel, two passes over W). The bytes are what any ME needs, so a
one-pass ME reads as a gain and never as more than 100%."""

MODULE = "jit_model_evaluation"


def read(ctx):
    bw = ctx.peaks.get("hbm_bytes_per_s")
    ns = ctx.trace.module_ns(MODULE)
    if not bw or ns == 0 or ctx.rounds == 0:
        return None
    least = ctx.flops["me_bytes"] / bw
    return 100.0 * least / (ns * 1e-9 / ctx.rounds)
