"""95th percentile of request latency over every request of the window,
send to reply, on the host clock."""
import statistics


def read(ctx):
    if len(ctx.latencies) < 2:
        return ctx.latencies[0] * 1e3
    return statistics.quantiles(ctx.latencies, n=20,
                                method="inclusive")[-1] * 1e3
