"""The slowest cycle of the window, in milliseconds. Every job of a cycle
gets its verdict when `run_cycle` returns, so this is the longest any job
judged in the window waited from the start of the cycle that fetched its
newest sample to its verdict in the store."""


def read(ctx):
    return 1e3 * max(c["seconds"] for c in ctx["cycles"])
