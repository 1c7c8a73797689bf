"""Device milliseconds per step of the f32 master pack, the params packed
anew into the pool the update reads: the program's ``gf.master_pack``
scope (``bench.scopes``), averaged over the chips."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "gf.master_pack")
