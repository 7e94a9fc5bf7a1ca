"""95th percentile of the time to first streamed token at the client over the
window's measured requests (as ``ttft_p50_ms``; the run prints the sample
count). A tail over the ~40-130 requests a window holds spreads by 5-7 % from
run to run, too wide for a bound of 10 %, so it stands here, unbounded, beside
the median it should move with. Source: host_clock."""


def read(ctx):
    return ctx.client.get("ttft_p95_ms")
