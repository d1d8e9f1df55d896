"""Device idle time a decode tick, in ms: the idle time of the traced
window that lies inside the program's own ``engine.step`` spans (one a
decode tick, ``ServingEngine.step``), over the number of those spans in
the window.  A span cut by the window's edge counts with its part inside
it.  ``None`` where the trace holds no such span (a program that does
not emit it) or no device event."""
import trace as T


def idle_per_span(ctx, name):
    """Device idle ms inside the host spans named exactly ``name``, per
    span: gaps and the union of the spans intersected, so idle time that
    overlapping spans share counts once."""
    if ctx.trace is None or not (ctx.trace.ops.get(ctx.device)
                                 or ctx.trace.modules.get(ctx.device)):
        return None
    spans = [(e.start, e.end) for e in ctx.trace.host if e.name == name]
    if not spans:
        return None
    gaps = T.idle_gaps(ctx.trace, ctx.device, ctx.t0, ctx.t1)
    inside, i = 0.0, 0
    for s, t in T.union(spans):
        while i < len(gaps) and gaps[i][1] <= s:
            i += 1
        j = i
        while j < len(gaps) and gaps[j][0] < t:
            inside += min(t, gaps[j][1]) - max(s, gaps[j][0])
            j += 1
    return 1e3 * inside / len(spans)


def read(ctx):
    return idle_per_span(ctx, "engine.step")
