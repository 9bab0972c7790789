"""Recall@10 over every request served in the window, against the
reference's exact top-10 (bench/checks.py computes it)."""


def read(ctx):
    return ctx.recall
