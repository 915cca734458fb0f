"""Client-tier device time per round: the ops under the program's named
scope ``sl/client`` (``core/split.py``: the embedding and the client
blocks, forward, recompute and backward; ``fleet/engine.py``: the client
update), in ms on the busiest chip."""
from bench import scopes


def read(ctx):
    ns = scopes.scope_ns_per_round(ctx, "sl/client", __file__)
    return None if ns is None else 1e-6 * ns
