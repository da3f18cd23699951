"""Share of the traced window's device-busy time spent in attention: ops
under the program's ``attn`` scope in any phase (adapters, projections,
rope and the kernel or einsum path, in forward, inversion, recompute and
vjp; ``chipbench.scopes.share``)."""
from chipbench import scopes


def read(ctx):
    return scopes.share(ctx, ("attn",))
