"""Super-steps per coloring, serial tail included:
the mean of ``ColoringResult.iterations`` over the traced window."""


def read(ctx):
    its = ctx["layer"].get("iterations")
    return sum(its) / len(its) if its else None
