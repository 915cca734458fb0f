"""Attention-backward device time per round: the ops under the program's
named scope ``flash_bwd`` (``kernels/attn/flash.py`` ``_flash_vjp_bwd``,
in both tiers), in ms on the busiest chip."""
from bench import scopes


def read(ctx):
    ns = scopes.scope_ns_per_round(ctx, "flash_bwd", __file__)
    return None if ns is None else 1e-6 * ns
