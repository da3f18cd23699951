"""Share of the traced window's device-busy time spent routing MoE tokens:
ops under the program's ``moe.router``, ``moe.dispatch`` or ``moe.combine``
scopes in any phase (router softmax and top-k, the sort or one-hot dispatch
with its padding, and the gate-weighted combine; not the expert GEMMs;
``chipbench.scopes.share``)."""
from chipbench import scopes


def read(ctx):
    return scopes.share(ctx, ("moe.router", "moe.dispatch", "moe.combine"))
