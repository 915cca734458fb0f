"""Server-tier device time per round: the ops under the program's named
scope ``sl/server`` (``core/split.py``: the server blocks, the head and the
loss, forward, recompute and backward; ``fleet/engine.py``: the server
gradient's reduction and update), in ms on the busiest chip. The per-round
breakdown by scope goes to standard error."""
from bench import scopes


def read(ctx):
    scopes.log_breakdown(ctx, __file__)
    ns = scopes.scope_ns_per_round(ctx, "sl/server", __file__)
    return None if ns is None else 1e-6 * ns
