"""Share of the traced window's device-busy time spent inverting reversible
blocks: ops under the program's ``rev.invert`` scope (``block_inv`` with all
of its fixed-point passes, in every backward walk;
``chipbench.scopes.share``)."""
from chipbench import scopes


def read(ctx):
    return scopes.share(ctx, ("rev.invert",))
