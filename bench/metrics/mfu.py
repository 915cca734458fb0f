"""Model FLOP utilisation of the whole round: the model's forward and
backward FLOPs per round (``bench/flops/``, no recompute; plus the eval's
forward where the cell evaluates every round), times the rounds completed,
over the window, the chips and the chip's bf16 peak."""


def read(ctx):
    peak = ctx.peaks.get("bf16_flops_per_s")
    if not peak or not ctx.rounds:
        return None
    return 100.0 * ctx.flops_per_round * len(ctx.rounds) / (
        ctx.window_s * ctx.chips * peak)
