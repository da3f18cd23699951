"""90th percentile of the engine's own queue wait (submit -> admitted),
from its ``serve_request`` events (``queue_s``)."""
import numpy as np


def read(ctx):
    ev = [e["queue_s"] for e in ctx.get("program") or [] if "queue_s" in e]
    if ctx.get("kind") != "serve" or not ev:
        return None
    return float(np.percentile(ev, 90)) * 1e3
