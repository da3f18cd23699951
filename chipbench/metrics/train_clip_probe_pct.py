"""Share of the traced window's device-busy time spent in the clip-norm
probe: ops under the program's ``walk.probe`` scope, the backward walk that
only reduces each layer's gradient to its squared norm
(``chipbench.scopes.share``)."""
from chipbench import scopes


def read(ctx):
    return scopes.share(ctx, ("walk.probe",))
