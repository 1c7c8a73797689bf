"""Device milliseconds per step of the model's backward: the program's
``model`` scope under ``transpose(`` (``bench.scopes``), averaged over
the chips."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "model", bwd=True)
