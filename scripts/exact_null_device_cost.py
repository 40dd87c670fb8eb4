"""Device cost of the EXACT pairwise nulls (VERDICT r04 #4).

Round 4 doubled the CPU score stage with the exact finite-n KS
(lattice-path DP) and exact Wilcoxon (subset-sum DP) nulls; whether the
TPU absorbs that cost was the unmeasured claim. This measures the fused
two-sample family at the headline shard shape (B=12,500, T=128) under
the CURRENT process's FOREMAST_KS_EXACT_MAX_T / _WILCOXON_EXACT_MAX_N
(read at module import — callers run one subprocess per variant) with
the bench's forced-completion protocol, and prints ONE JSON line.

Run (on a machine with a chip, as the only process using it; never run on the
device by this repo's record — ROADMAP.md D4):
  python scripts/exact_null_device_cost.py                        # both on
  FOREMAST_KS_EXACT_MAX_T=0 python scripts/...                    # KS off
  FOREMAST_KS_EXACT_MAX_T=0 FOREMAST_WILCOXON_EXACT_MAX_N=0 ...   # both off
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import jax
    import jax.numpy as jnp

    from foremast_tpu.ops import pairwise as pw

    B = int(os.environ.get("EXACTNULL_B", "12500"))
    T = int(os.environ.get("EXACTNULL_T", "128"))
    reps = int(os.environ.get("EXACTNULL_REPS", "30"))
    rng = np.random.default_rng(0)
    x = jax.device_put(rng.normal(10, 2, (B, T)).astype(np.float32))
    xm = jax.device_put(rng.random((B, T)) > 0.05)
    y = jax.device_put(rng.normal(10, 2, (B, T)).astype(np.float32))
    ym = jax.device_put(rng.random((B, T)) > 0.05)

    def red(d):
        return jax.tree.reduce(
            lambda a, b: a + b.sum().astype(jnp.float32), d, jnp.float32(0))

    tiny = jax.jit(lambda v: v.sum())
    z8 = jax.device_put(np.ones(8, np.float32))
    float(tiny(z8))
    rtts = []
    for _ in range(5):
        t0 = time.perf_counter()
        float(tiny(z8))
        rtts.append(time.perf_counter() - t0)
    rtt = float(np.median(rtts))

    jf = jax.jit(lambda *a: red(jax.vmap(pw.two_sample_tests)(*a)))
    t0 = time.perf_counter()
    digest = float(jf(x, xm, y, ym))  # compile + first run, forced
    compile_s = time.perf_counter() - t0
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        float(jf(x, xm, y, ym))
        ts.append(time.perf_counter() - t0)
    ts = np.sort(np.asarray(ts))
    print(json.dumps({
        "metric": "two_sample_fused_exec_ms",
        "value": round(float(np.median(ts) - rtt) * 1e3, 3),
        "unit": "ms",
        "p99_ms": round(float(np.percentile(ts, 99) - rtt) * 1e3, 3),
        "rtt_ms": round(rtt * 1e3, 3),
        "compile_s": round(compile_s, 3),
        "B": B, "T": T, "reps": reps,
        "ks_exact_max_t": pw.KS_EXACT_MAX_T,
        "wilcoxon_exact_max_n": pw.WILCOXON_EXACT_MAX_N,
        "digest": digest,
        "backend": jax.default_backend(),
    }))


if __name__ == "__main__":
    main()
