"""Longest over median iteration of the window, from the callback's times."""
import statistics


def read(ctx):
    w = ctx.window
    t = w.iter_end[w.warmup - 1:]
    d = [b - a for a, b in zip(t, t[1:])]
    return max(d) / statistics.median(d) if d else None
