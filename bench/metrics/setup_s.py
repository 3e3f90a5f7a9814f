"""Seconds from the start of set-up (building, profiling, planning,
compiling, warming up) to the start of the window."""


def read(ctx):
    return ctx.setup_s
