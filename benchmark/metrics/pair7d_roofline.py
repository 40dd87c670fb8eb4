"""The pair launch's share of its roofline at the 7-day bucket: what
`pair_roofline` reads, by its own reader (`costs.pair` over the device
time of `jit__score_rows`). It is a metric of its own name only because
an accepted metric's list of cells is not widened outside a benchmark
change. `costs.pair` counts the real samples of both windows, not the
elements the program sorts (2 x 16,384 a row), so a program that sorts
less reads higher here."""
import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "pair_roofline.py")


def read(ctx):
    spec = importlib.util.spec_from_file_location("bench_metric_pair7d",
                                                  _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)
