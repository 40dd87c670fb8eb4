"""Post-optimization TPU measurement: components + full verdict, forced completion.
Holds the accelerator for its whole run; on a machine with a chip (through
the chip tool here), as the only process using it:
  python scripts/tpu_component_profile.py
Never run on the device by this repo's record (ROADMAP.md D4).
To isolate the exact-KS DP's device cost, run once more with
FOREMAST_KS_EXACT_MAX_T=0 (Stephens-only) and diff the fused line.
"""
import os, sys, time, numpy as np, jax, jax.numpy as jnp

# runnable as `python scripts/tpu_component_profile.py` without an
# installed package (sys.path[0] is scripts/, not the repo root)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from foremast_tpu.ops import pairwise as pw
from foremast_tpu.ops import forecast as fc
from foremast_tpu.parallel import fleet

B, T = 12_500, 128
rng = np.random.default_rng(0)
x = jax.device_put(rng.normal(10, 2, (B, T)).astype(np.float32))
xm = jax.device_put(rng.random((B, T)) > 0.05)
y = jax.device_put(rng.normal(10, 2, (B, T)).astype(np.float32))
ym = jax.device_put(rng.random((B, T)) > 0.05)
cfgB = [jax.device_put(a) for a in (
    np.full(B, 0.01, np.float32), np.full(B, 0b1111, np.int32),
    np.zeros(B, np.int32), np.full(B, 10, np.int32),
    np.full(B, 3.0, np.float32), np.zeros(B, np.int32),
    np.zeros(B, np.float32), np.tile(np.asarray([20,20,5], np.int32), (B,1)))]
def red(d):
    return jax.tree.reduce(lambda a, b: a + b.sum().astype(jnp.float32), d, jnp.float32(0))
tiny = jax.jit(lambda v: v.sum()); z8 = jax.device_put(np.ones(8, np.float32)); float(tiny(z8))
ts = []
for _ in range(5):
    t0 = time.perf_counter(); float(tiny(z8)); ts.append(time.perf_counter()-t0)
rtt = float(np.median(ts)); print(f"rtt {rtt*1e3:.1f} ms", flush=True)
def prof(name, fn, *args, reps=7):
    jf = jax.jit(lambda *a: red(fn(*a)))
    float(jf(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter(); float(jf(*args)); ts.append(time.perf_counter()-t0)
    print(f"{name}: exec~{(np.median(ts)-rtt)*1e3:.1f} ms", flush=True)
prof("two_sample_fused(MW+K+W+KS)", jax.vmap(pw.two_sample_tests), x, xm, y, ym)
prof("sign_lgamma", lambda a, b, m: jax.vmap(pw.sign_test_exact)(a, b, m), x, y, xm & ym)
def band1(b, bm, c, cm):
    concat = jnp.concatenate([b, c]); cm2 = jnp.concatenate([bm, cm])
    region = jnp.arange(concat.shape[-1]) >= b.shape[-1]
    return fc._moving_average_1d(concat, cm2 & ~region, jnp.int32(10)).sum()
prof("band_rollscan", jax.vmap(band1), x, xm, y, ym)
prof("FULL_pair_verdict", lambda *a: jax.vmap(fleet._pair_verdict)(*a), x, xm, y, ym, *cfgB)
