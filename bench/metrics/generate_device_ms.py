"""Device time of one generate call, in ms: the generate program's time in
the traced window over its executions there.

Matching rule: ``TransprecisionEngine`` jits ``_generate_impl``, which
runs as ``jit__generate_impl``."""
import trace as T


def is_generate(program):
    return program.endswith("_generate_impl")


def read(ctx):
    if ctx.trace is None:
        return None
    secs, n = T.program_time(ctx.trace, ctx.device, is_generate)
    return 1e3 * secs / n if n else None
