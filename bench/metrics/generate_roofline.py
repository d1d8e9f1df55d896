"""Share of its roofline that a generate call reaches, in %: the least
time of a call (larger of its operations at peak bf16 rate and its bytes
at full HBM bandwidth, from ``bench/cost.py`` with the slots active and
their live K/V rows as the harness recorded them) over the measured
device time of a call, both averaged over the traced window."""
from cost import least_seconds
from metrics_common import load_sibling

gen = load_sibling("generate_device_ms")


def read(ctx):
    ms = gen.read(ctx)
    if ms is None or not ctx.gen_calls:
        return None
    least = [least_seconds(*ctx.cost.generate_cost(a, rows), ctx.peaks)
             for _, a, rows in ctx.gen_calls]
    return 100.0 * (sum(least) / len(least)) / (ms * 1e-3)
