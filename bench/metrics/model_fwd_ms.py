"""Device milliseconds per step of the model's forward, loss and head:
the program's ``model`` scope outside ``transpose(`` (``bench.scopes``),
averaged over the chips."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "model", bwd=False)
