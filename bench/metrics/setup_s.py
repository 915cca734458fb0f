"""Seconds from the process's start to the window's first round: imports,
data from the seed, compiling the job, and the first rounds that compile,
warm and are compared with the reference."""


def read(ctx):
    return ctx.setup_s
