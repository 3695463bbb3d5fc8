"""Answers that came back certified, over the whole window's seconds.
A failed, rejected or fallen-back answer is not counted."""


def read(ctx):
    w = ctx.window
    return w.ok / w.seconds if w.seconds > 0 else None
