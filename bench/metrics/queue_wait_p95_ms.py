"""Orchestrator queue wait, 95th percentile, in ms: from the time each
request was due to the engine's ``admit`` stamp (its first admission),
over every request due in the window."""
import numpy as np


def read(ctx):
    w = ctx.window
    waits = [r.admit - r.due for r in w.due_in_window() if r.admit is not None]
    return 1e3 * float(np.percentile(waits, 95)) if waits else None
