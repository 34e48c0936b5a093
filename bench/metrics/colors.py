"""Colors used, the paper's quality measure: the mean over the traced
window's colorings of the largest color returned (colors start at 1)."""


def read(ctx):
    colors = ctx["layer"].get("colors")
    return sum(colors) / len(colors) if colors else None
