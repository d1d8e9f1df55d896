"""Device time of the prefill programs in the traced window, in us per
true prompt token admitted there; bucket padding shows as a higher number.

Matching rule (read off a TPU v5e trace): ``TransprecisionEngine.prefill``
jits a local function named ``impl``, so its programs run as
``jit_impl``."""
import trace as T


def is_prefill(program):
    return program == "jit_impl"


def read(ctx):
    if ctx.trace is None:
        return None
    secs, n = T.program_time(ctx.trace, ctx.device, is_prefill)
    toks = sum(p[1] for p in ctx.prefill_calls)
    if n == 0 or toks == 0:
        return None
    return 1e6 * secs / toks
