"""Device milliseconds per step of the optimizer update and the unpack of
the new params: the program's ``gf.update`` scope (``bench.scopes``),
averaged over the chips."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "gf.update")
