"""Median request latency over every request of the window, send to
reply, on the host clock."""
import statistics


def read(ctx):
    return statistics.median(ctx.latencies) * 1e3
