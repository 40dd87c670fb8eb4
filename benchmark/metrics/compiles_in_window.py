"""Backend compiles inside the window (`CompileCounter`); reads 0."""


def read(ctx):
    return sum(c["compiles"] for c in ctx["cycles"])
