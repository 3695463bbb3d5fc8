"""Mean of the engine's ``iters`` count over the window's answers."""


def read(ctx):
    its = [a.iters for a in ctx.window.answers if a.iters is not None]
    return sum(its) / len(its) if its else None
