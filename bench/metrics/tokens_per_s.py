"""Training tokens completed per second over the whole window: samples
times the sequence length, over the window's wall time on the host clock."""


def read(ctx):
    return ctx.work("tokens") / ctx.window_s
