"""Device idle time an admission, in ms: the idle time of the traced
window that lies inside the program's own ``engine.admit`` spans (one a
call of ``ServingEngine.add_requests``), over the number of those spans
in the window; read as ``tick_idle_ms`` reads ``engine.step``."""
from metrics_common import load_sibling

tick = load_sibling("tick_idle_ms")


def read(ctx):
    return tick.idle_per_span(ctx, "engine.admit")
