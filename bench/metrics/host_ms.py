"""Host cost of a round: the mean over the window's rounds of the program's
``round`` span less the fenced device wait (``sync_s``) of its
``round/execute`` span."""


def read(ctx):
    return ctx.host_ms()
