"""Requests completed in the window over the window's length."""


def read(ctx):
    return ctx.completed / ctx.window_s
