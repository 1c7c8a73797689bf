"""Device milliseconds per step of the gradient pack into the wire pool,
with its census where the wire has one: the program's ``gf.pack`` scope
(``bench.scopes``), averaged over the chips."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "gf.pack")
