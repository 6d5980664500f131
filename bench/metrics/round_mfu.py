"""The round's operations (FEL training, the test-set evaluation and ME's
4·N·D) over the mean traced round wall and the chip's bf16 peak. The
program trains in float32 at the default matmul precision, so the bf16
peak is the ceiling it is held against."""


def read(ctx):
    peak = ctx.peaks.get("bf16_flops")
    if not peak or ctx.rounds == 0:
        return None
    f = ctx.flops
    per_round = ctx.window.seconds / ctx.rounds
    return 100.0 * (f["train"] + f["eval"] + f["me"]) / per_round / peak
