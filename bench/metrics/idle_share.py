"""Share of the traced window in which no operation ran on the device:
100 * (1 - union of the device's operation intervals / window), averaged
over the cell's chips. The window runs from the first traced round's call
to the last one's return."""


def read(ctx):
    busy = ctx.busy_share()
    return None if busy is None else 100.0 * (1.0 - busy)
