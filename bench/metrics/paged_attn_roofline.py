"""Share of its roofline that the paged decode-attention kernel reaches,
in %: the least time of one call (one layer, the live K/V rows of the
active slots at the declared KV format, their scales at 4 B a row and
head) over the kernel's measured device time per call, both averaged over
the traced window.

Matching rule (read off a TPU v5e trace): the kernel runs as a custom-call
named after its jitted wrapper, ``%paged_decode_attention.<n> = ...
custom-call(...)``."""
import trace as T
from cost import least_seconds


def is_kernel(op):
    return T.op_name(op) == "paged_decode_attention"


def read(ctx):
    if ctx.trace is None or not ctx.gen_calls:
        return None
    secs, n = T.op_time(ctx.trace, ctx.device, is_kernel)
    if n == 0:
        return None
    least = [least_seconds(*ctx.cost.attention_cost(rows), ctx.peaks)
             for _, _, rows in ctx.gen_calls]
    return 100.0 * (sum(least) / len(least)) / (secs / n)
