"""Time to first token, 95th percentile, in ms, over every request due in
the window, from the time it was due (the harness's definition of the
end-to-end ``ttft_p95_ms``).  At 0.8 of the knee it is set by how the
seed orders long requests, so it is a layer reading of the queue in
front of the engine, not an end-to-end metric with a bound."""
import harness


def read(ctx):
    return harness.e2e_values(ctx.window).get("ttft_p95_ms")
