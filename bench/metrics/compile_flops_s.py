"""Seconds of set-up in the energy bill's FLOP count: the program's
``compile/flops`` spans (``api/plan.py``, inside ``compile_experiment``),
summed."""
from bench import scopes


def read(ctx):
    d = [e["dur_s"] for e in scopes.setup_spans(ctx, __file__)
         if e.get("name") == "compile/flops"]
    return float(sum(d)) if d else None
