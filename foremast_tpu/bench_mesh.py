# lint: disable-file=knob-registry -- bench-only BENCH_* knobs, not a deployment surface (docs/benchmarks.md)
"""Virtual-mesh measurement of the fleet scorer's collective tail.

The 100k-pair headline pro-rates one chip's shard across a v5e-8 on the
assumption that scoring is embarrassingly parallel and the only
cross-chip traffic — the O(k·n_chips) psum + all_gather top-k verdict
reduction (parallel/fleet.py:make_fleet_scorer) — is negligible. No
multi-chip hardware is available here, so this bench puts a NUMBER under
that assumption the only way possible without it: on the 8-device
virtual CPU mesh, time the full sharded program against an identical
program with the reduction tail removed (same shard_map, same sharding,
same per-pair verdict work) and report the difference.

Two caveats, encoded in the output rather than hidden:
  * virtual-mesh "collectives" move bytes through host RAM, not ICI —
    absolute numbers do not transfer; the useful signals are the
    OVERHEAD (with − without) and its SHARE of the launch.
  * on a real v5e the scoring denominator is ~100× faster than CPU, so
    the share measured here UNDERSTATES what the reduction would cost on
    TPU by roughly that factor; `share_vs_device_scoring_est` re-rates
    the measured overhead against the real-chip scoring time THIS run's
    device leg measured (BENCH_DEVICE_SCORE_S, exported by bench.py) for
    an upper-bound estimate. With no measured device time the estimate
    is null: nothing is assumed.

Run as a module inside an 8-virtual-device CPU process; prints ONE JSON
line (bench.py runs it as a child and merges `mesh_*` fields):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python -m foremast_tpu.bench_mesh
"""
from __future__ import annotations

import json
import os
import time
from functools import partial

import numpy as np


def run(B_total: int = 8192, T: int = 128, k: int = 8,
        n_runs: int = 15) -> dict:
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from .parallel import fleet
    from .parallel.mesh import FLEET_AXIS, fleet_mesh

    mesh = fleet_mesh()
    n_dev = mesh.shape[FLEET_AXIS]
    B = (B_total // n_dev) * n_dev

    rng = np.random.default_rng(0)
    baseline = rng.normal(10.0, 2.0, (B, T)).astype(np.float32)
    current = rng.normal(10.0, 2.0, (B, T)).astype(np.float32)
    b_mask = rng.random((B, T)) > 0.05
    c_mask = rng.random((B, T)) > 0.05
    cfg = {
        "pvalue_threshold": np.full(B, 0.01, np.float32),
        "test_mask": np.full(B, 0b1111, np.int32),
        "combine": np.zeros(B, np.int32),
        "ma_window": np.full(B, 10, np.int32),
        "band_threshold": np.full(B, 3.0, np.float32),
        "bound_mode": np.zeros(B, np.int32),
        "min_lower_bound": np.zeros(B, np.float32),
    }

    # -- full program: scoring + psum/all_gather/top-k reduction tail --
    scorer = fleet.make_fleet_scorer(mesh, k=k)

    def digest(tree):
        return jax.tree.reduce(
            lambda a, b: a + jnp.asarray(b).sum().astype(jnp.float32),
            tree, jnp.float32(0))

    def run_with():
        out, total, top_v, top_idx = scorer(
            baseline, b_mask, current, c_mask, cfg)
        return float(digest(out)) + float(total) + float(top_v.sum())

    # -- identical program WITHOUT the reduction tail --
    min_points = np.tile(
        np.asarray([fleet.MIN_MANN_WHITNEY, fleet.MIN_WILCOXON,
                    fleet.MIN_KRUSKAL, fleet.MIN_FRIEDMAN]), (B, 1))

    @jax.jit
    @partial(shard_map, mesh=mesh,
             in_specs=(P(FLEET_AXIS),) * 12, out_specs=P(FLEET_AXIS),
             check_vma=False)
    def score_only(*args):
        return jax.vmap(fleet._pair_verdict)(*args)

    args = (baseline, b_mask, current, c_mask,
            cfg["pvalue_threshold"], cfg["test_mask"], cfg["combine"],
            cfg["ma_window"], cfg["band_threshold"], cfg["bound_mode"],
            cfg["min_lower_bound"], min_points)

    def run_without():
        return float(digest(score_only(*args)))

    def timed(fn):
        fn()  # compile + warm
        ts = []
        for _ in range(n_runs):
            t0 = time.perf_counter()
            fn()  # forced completion: digest fetched to host
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts)), float(np.std(ts))

    with_s, with_std = timed(run_with)
    without_s, without_std = timed(run_without)
    # a negative difference means the tail costs less than run noise;
    # the noise floor is reported so a 0.0 overhead is interpretable
    overhead = max(with_s - without_s, 0.0)
    noise = max(with_std, without_std)
    raw = os.environ.get("BENCH_DEVICE_SCORE_S", "")
    device_score_s = float(raw) if raw else None
    return {
        "metric": "fleet_reduction_overhead",
        "value": round(overhead, 6),
        "unit": "s",
        "with_reduction_s": round(with_s, 6),
        "score_only_s": round(without_s, 6),
        "noise_floor_s": round(noise, 6),
        "overhead_below_noise": overhead <= noise,
        "reduction_share_cpu_mesh": round(overhead / with_s, 5) if with_s else 0.0,
        # overhead re-rated against the real-chip scoring denominator:
        # an upper-bound estimate (host-RAM collectives vs ICI)
        "share_vs_device_scoring_est": None if device_score_s is None
        else round(overhead / (overhead + device_score_s), 5),
        "device_score_s_measured": device_score_s,
        "pairs": B,
        "window": T,
        "k": k,
        "n_devices": n_dev,
        "runs": n_runs,
    }


def main() -> None:
    B = int(os.environ.get("BENCH_MESH_PAIRS", "8192"))
    T = int(os.environ.get("BENCH_MESH_WINDOW", "128"))
    runs = int(os.environ.get("BENCH_MESH_RUNS", "15"))
    print(json.dumps(run(B_total=B, T=T, n_runs=runs)))


if __name__ == "__main__":
    main()
