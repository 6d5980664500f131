"""Training operations of one round over the FEL program's device time
per round and the chip's bf16 peak."""

MODULE = "jit_round_fn"


def read(ctx):
    peak = ctx.peaks.get("bf16_flops")
    ns = ctx.trace.module_ns(MODULE)
    if not peak or ns == 0 or ctx.rounds == 0:
        return None
    return 100.0 * ctx.flops["train"] / (ns * 1e-9 / ctx.rounds) / peak
