"""The whole step's share of the chip's peak bf16 rate, in %: the model
operations of the prompt tokens admitted and the output tokens decoded in
the window (``bench/cost.py``: no bucket padding, no fake-quantization,
attention over the live keys only) over the window's length and the peak.
"""


def read(ctx):
    w, c = ctx.window, ctx.cost
    flops = 0.0
    for r in w.recs:
        a = r.admit
        if a is not None and w.open <= a < w.close:
            flops += c.prompt_flops(len(r.prompt))
        n = len(r.prompt)
        for j, t in enumerate(r.tokens):
            if j and w.open <= t < w.close:    # token j fed at n + j - 1
                flops += c.token_flops(n + j)
    if flops == 0:
        return None
    return 100.0 * flops / w.seconds / ctx.peaks["bf16_flops_per_s"]
