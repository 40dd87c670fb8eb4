"""Benchmark: canary metric-pair scoring, shaped like the north-star claim.

North star (BASELINE.json / BASELINE.md): score 100k concurrent
(baseline, canary) metric-pair windows in <1 s **p99** on a v5e-8.
The engine shards the fleet batch evenly over the 8-chip fleet axis
(parallel/fleet.py:make_fleet_scorer), so each chip scores exactly
B_total/8 = 12,500 pairs; the scoring itself is embarrassingly parallel
(the only cross-chip traffic is the O(k*n_chips) verdict reduction).
This bench therefore runs the per-chip shard — B=12,500 pairs, T=128
(~2h of 60s-step points, wider than the reference's 10-min canary
window) — on the one available chip and pro-rates explicitly: the wall
time of one chip's shard IS the fleet's time to 100k, up to the top-k
reduction, which is validated (compiled + executed, not timed — no
multi-chip hardware here) on the 8-device dryrun mesh.

Protocol (VERDICT r02 #2): p99 over >=100 timed runs (default 150,
override BENCH_RUNS); compile time reported separately; min/max/std
included so round-over-round drift in the headline is characterized
instead of mysterious.

Additionally the UNPRORATED claim is measured outright: the entire
100k-pair fleet batch on the ONE available chip, same run count and p99
protocol (p99_s_100k_single_chip). If that is < 1 s, the v5e-8 claim is
beaten on an eighth of the claimed hardware, no pro-rating needed.

MEASUREMENT INTEGRITY: JAX dispatch is asynchronous — a jitted call
returns before the device has finished, so a timing that never consumes
the result measures the enqueue, not the compute. Every timed run here
therefore ends by fetching a 4-byte on-device reduction of the outputs
to the host, which forces — and proves — completion. The fetch costs one
host<->device round-trip, reported separately as readback_rtt_floor_s,
so the e2e numbers are conservative.

One process holds the accelerator at a time: this parent never touches
JAX, and the device legs run one after another in children. A run that
produced no device number exits non-zero and prints no JSON line.

Prints exactly one JSON line on success.
"""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import time

import numpy as np

TARGET_PAIRS_PER_SEC_PER_CHIP = 100_000 / 8.0  # north star pro-rated per chip
# BENCH_PAIRS_TOTAL exists for CPU smoke-tests of the bench itself; the
# recorded artifact always uses the real 100k claim shape, and the JSON
# self-describes the batch via "pairs_total" so an overridden run can
# never masquerade as a real one.
B_TOTAL = int(os.environ.get("BENCH_PAIRS_TOTAL", "100000"))
N_CHIPS = 8
B_CHIP = max(B_TOTAL // N_CHIPS, 1)  # 12,500: one chip's shard of 100k


def _run_json_child(cmd: list, timeout_s: float, env: dict | None = None,
                    cwd: str | None = None):
    """Run a child that prints one JSON line; returns (record, error).

    Shared by the cycle and device legs: a failing child must yield a
    DIAGNOSABLE error string (stderr tail included — subprocess errors
    alone say only 'non-zero exit status'), never a hang or a lost cause.
    """
    try:
        out = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout_s,
            env=env, check=True, cwd=cwd,
        )
        return json.loads(out.stdout.strip().splitlines()[-1]), None
    except Exception as e:  # noqa: BLE001 - callers report, never hang
        stderr = getattr(e, "stderr", None) or ""
        if isinstance(stderr, bytes):
            stderr = stderr.decode(errors="replace")
        tail = stderr.strip().splitlines()[-3:]
        msg = f"{type(e).__name__}: {e}"
        if tail:
            msg += " | stderr: " + " / ".join(tail)
        return None, msg


def _cycle_bench() -> dict:
    """Host-path numbers: a 10k-job cycle through analyzer.run_cycle with
    the native parser on vs off (foremast_tpu/bench_cycle.py). One
    subprocess per variant (FOREMAST_NATIVE latches at first load),
    CPU-pinned: the host path is what these measure (their score stage is
    a CPU count, never a device number); the device bound is the
    headline."""
    def run_child(native_flag: str, mix: bool):
        """One CPU-pinned bench_cycle child (FOREMAST_NATIVE latches at
        first load, so every variant needs its own process)."""
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["FOREMAST_NATIVE"] = native_flag
        env["BENCH_CYCLE_MIX"] = "1" if mix else "0"
        env.setdefault("BENCH_CYCLE_JOBS", "10000")
        return _run_json_child(
            [sys.executable, "-m", "foremast_tpu.bench_cycle"],
            timeout_s=900, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )

    extra: dict = {}
    for flag, key in (("1", "native"), ("0", "python")):
        rec, err = run_child(flag, mix=False)
        if rec is not None:
            extra[f"cycle_jobs_per_sec_{key}"] = rec["value"]
            # the meaningful host-path number: cycle minus the CPU-pinned
            # score stage (device-bound in production; the headline above
            # measures it on the real chip). The raw cycle_jobs_per_sec_*
            # stays for continuity but is score-dominated on CPU. When the
            # child omits the decomposed field (clock-step anomaly), the
            # key is omitted here too — never silently substituted with
            # the score-dominated number it exists to correct.
            if "host_jobs_per_sec" in rec:
                extra[f"cycle_host_jobs_per_sec_{key}"] = rec["host_jobs_per_sec"]
            extra[f"cycle_preprocess_s_{key}"] = rec["preprocess_s_per_cycle"]
            extra[f"cycle_score_s_{key}"] = rec.get("score_s_per_cycle", 0.0)
        else:
            extra[f"cycle_error_{key}"] = err
    nat = extra.get("cycle_preprocess_s_native")
    py = extra.get("cycle_preprocess_s_python")
    if nat and py:
        extra["cycle_native_preprocess_speedup"] = round(py / nat, 2)
    nat_h = extra.get("cycle_host_jobs_per_sec_native")
    py_h = extra.get("cycle_host_jobs_per_sec_python")
    if nat_h and py_h:
        extra["cycle_native_host_speedup"] = round(nat_h / py_h, 2)
    # third leg: the MIXED model-family fleet (pair+band+bivariate+LSTM+HPA,
    # native parser) — per-family score decomposition and the bounded
    # LSTM train-on-miss cost (VERDICT r3 #3). The pure-pair legs above
    # stay as the round-over-round continuity numbers.
    # fourth leg: the 8-device virtual-mesh reduction share (VERDICT r3
    # #7) — time the sharded fleet program with and without its
    # psum/all_gather top-k tail; turns "validated, not timed" into a
    # measured fraction (bench_mesh.py documents the CPU-mesh caveats).
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    mrec, merr = _run_json_child(
        [sys.executable, "-m", "foremast_tpu.bench_mesh"],
        timeout_s=600, env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    if mrec is not None:
        for k_ in ("value", "with_reduction_s", "score_only_s",
                   "noise_floor_s", "overhead_below_noise",
                   "reduction_share_cpu_mesh", "share_vs_device_scoring_est"):
            extra[f"mesh_{k_}" if k_ != "value"
                  else "mesh_reduction_overhead_s"] = mrec.get(k_)
    else:
        extra["mesh_error"] = merr

    rec, err = run_child("1", mix=True)
    if rec is not None:
        extra["cycle_mixed_jobs_per_sec"] = rec["value"]
        if "host_jobs_per_sec" in rec:
            extra["cycle_mixed_host_jobs_per_sec"] = rec["host_jobs_per_sec"]
        extra["cycle_mixed_family_jobs"] = rec.get("family_jobs")
        extra["cycle_mixed_family_score_s"] = rec.get("family_score_s_per_cycle")
        extra["cycle_mixed_lstm_train_s"] = rec.get("lstm_train_s_per_cycle")
        extra["cycle_mixed_lstm_trains"] = rec.get("lstm_trains_per_cycle")
        # steady-state warm-up accounting (round 5): the timed cycles are
        # train-free; the one-time warm-up cost is recorded separately
        extra["cycle_mixed_warmup_cycles"] = rec.get("warmup_cycles")
        extra["cycle_mixed_lstm_train_warmup_s"] = rec.get("lstm_train_warmup_s")
        # pipeline-stage decomposition + compile counters (ISSUE 2): the
        # overlap story per cycle, and proof steady state never compiles
        extra["cycle_mixed_stage_s"] = rec.get("stage_s_per_cycle")
        extra["cycle_mixed_compiles_warmup"] = rec.get("compiles_warmup")
        extra["cycle_mixed_compiles_steady"] = rec.get("compiles_steady_state")
    else:
        extra["cycle_mixed_error"] = err
    return extra


def _rtt_floor(n: int = 5) -> float:
    """Host<->device round-trip floor: fetch a tiny precomputed reduction.
    This is the transfer cost baked into every timed run below."""
    import jax

    tiny = jax.jit(lambda v: v.sum())
    z = jax.device_put(np.ones(8, np.float32))
    float(tiny(z))  # compile
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        float(tiny(z))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _measure(B: int, T: int, n_runs: int) -> dict:
    """Time score_pairs at batch B: p50/p99/min/max/std over n_runs, plus
    compile time for this batch shape.

    Each timed run ends with a host fetch of a jitted scalar reduction of
    the verdict outputs — completion is FORCED, not assumed (see module
    docstring: dispatch is asynchronous)."""
    import jax

    from foremast_tpu.parallel.fleet import score_pairs

    rng = np.random.default_rng(0)
    baseline = rng.normal(10.0, 2.0, (B, T)).astype(np.float32)
    current = rng.normal(10.0, 2.0, (B, T)).astype(np.float32)
    b_mask = rng.random((B, T)) > 0.05
    c_mask = rng.random((B, T)) > 0.05
    cfg = (
        np.full(B, 0.01, np.float32),
        np.full(B, 0b1111, np.int32),
        np.zeros(B, np.int32),
        np.full(B, 10, np.int32),
        np.full(B, 3.0, np.float32),
        np.zeros(B, np.int32),
        np.zeros(B, np.float32),
        np.tile(np.asarray([20, 20, 5], np.int32), (B, 1)),
    )
    args = [jax.device_put(a) for a in (baseline, b_mask, current, c_mask, *cfg)]

    import jax.numpy as jnp

    @jax.jit
    def _consume(out):
        # scalar digest of every output: nothing can be elided
        return jax.tree.reduce(
            lambda a, b: a + b.sum().astype(jnp.float32), out, jnp.float32(0)
        )

    def run():
        out = score_pairs(*args)
        return float(_consume(out))  # 4-byte host readback = proof of completion

    t0 = time.perf_counter()
    digest = run()  # compile + first execute
    compile_s = time.perf_counter() - t0

    times = []
    for _ in range(n_runs):
        t0 = time.perf_counter()
        run()
        times.append(time.perf_counter() - t0)
    ts = np.sort(np.asarray(times))
    return {
        "p50": float(np.median(ts)),
        "p99": float(np.percentile(ts, 99)),
        "min": float(ts[0]),
        "max": float(ts[-1]),
        "std": float(np.std(ts)),
        "compile_s": compile_s,
        "runs": n_runs,
        "digest": digest,
    }


def _digest_fields(key: str, value: float) -> dict:
    """Digest scalar, kept JSON-strict: a NaN/inf digest would make
    json.dumps emit a non-strict NaN/Infinity token and break the
    one-JSON-line contract for strict parsers — emit null + error instead
    (a non-finite digest is itself a finding: the kernel produced
    non-finite outputs)."""
    if math.isfinite(value):
        return {key: value}
    return {key: None, f"{key}_error": f"non-finite digest: {value!r}"}


def _long_window_fields() -> dict:
    """Long-window leg: the 7-day historical shapes (VERDICT r3 #4).

    The reference's historical model runs on ~10,080-point windows
    (metricsquery.go:93-99) — where `lax.scan` serialization and the
    60-candidate Holt-Winters grid actually bite, none of which the
    T=128 headline exercises. Three measurements, forced completion:

      * p50/p99 for a B-job moving-average BAND batch at T=10,080
        (predict + sigma + anomalies — the production band path);
      * sequential vs associative-scan SES at the same shape — the
        LONG_WINDOW_STEPS switch's justification, measured;
      * the Holt-Winters grid fit (60 candidates via lax.map) at a
        daily period on a smaller batch (its cost scales with G*B*T).
    """
    import jax
    import jax.numpy as jnp

    from foremast_tpu.ops import forecast as fc
    from foremast_tpu.ops import seqscan as sq

    T = int(os.environ.get("BENCH_LONG_WINDOW", "10080"))
    B = int(os.environ.get("BENCH_LONG_BATCH", "256"))
    B_HW = max(B // 8, 1)
    n_runs = int(os.environ.get("BENCH_LONG_RUNS", "30"))

    rng = np.random.default_rng(1)
    x = np.cumsum(rng.normal(0, 0.2, (B, T)), axis=-1).astype(np.float32) + 50.0
    m = rng.random((B, T)) > 0.05
    region = np.zeros((B, T), bool)
    region[:, -30:] = True  # judged current window: the last 30 min
    alphas = np.full(B, 0.3, np.float32)
    thr = np.full(B, 3.0, np.float32)
    bound = np.zeros(B, np.int32)
    mlb = np.zeros(B, np.float32)
    xd, md, rd = jax.device_put(x), jax.device_put(m), jax.device_put(region)

    @jax.jit
    def band_fn(xv, xm, reg):
        hist = xm & ~reg
        preds = fc.moving_average_predictions(xv, hist, 30)
        sigma = fc.residual_sigma(xv, preds, hist, ~reg)
        out = fc.band_anomalies(xv, xm, reg, preds, sigma, thr, bound, mlb)
        return jax.tree.reduce(
            lambda a, b: a + b.sum().astype(jnp.float32), out, jnp.float32(0))

    def timed(fn, runs):
        fn()  # compile + warm
        ts = []
        for _ in range(runs):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        ts = np.sort(np.asarray(ts))
        return {"p50": float(np.median(ts)),
                "p99": float(np.percentile(ts, 99))}

    out: dict = {"long_window": T, "long_batch": B}
    band = timed(lambda: float(band_fn(xd, md, rd)), n_runs)
    out["long_band_p99_s"] = round(band["p99"], 6)
    out["long_band_p50_s"] = round(band["p50"], 6)

    hist_mask = md & ~rd
    seq = jax.jit(lambda: fc.ses_predictions(xd, hist_mask, alphas).sum())
    assoc = jax.jit(
        lambda: sq.ses_predictions_assoc(xd, hist_mask, alphas).sum())
    seq_t = timed(lambda: float(seq()), max(n_runs // 3, 5))
    assoc_t = timed(lambda: float(assoc()), max(n_runs // 3, 5))
    out["long_ses_sequential_p50_s"] = round(seq_t["p50"], 6)
    out["long_ses_assoc_p50_s"] = round(assoc_t["p50"], 6)
    out["long_ses_assoc_speedup"] = round(
        seq_t["p50"] / max(assoc_t["p50"], 1e-9), 2)

    period = min(1440, T // 2)
    fitm = np.asarray(hist_mask).copy()
    fitm[:, : 2 * period] = False
    xh, mh, fh = (jax.device_put(a[:B_HW]) for a in
                  (x, np.asarray(hist_mask), fitm))
    hw = jax.jit(
        lambda: fc.fit_holt_winters(xh, mh, fh, period)[1].sum())
    hw_t = timed(lambda: float(hw()), max(n_runs // 6, 3))
    out["long_hw_fit_p50_s"] = round(hw_t["p50"], 6)
    out["long_hw_batch"] = B_HW
    return out


def _device_fields() -> dict:
    """The on-device measurements (runs inside the --device-only child)."""
    import jax

    T = 128
    n_runs = int(os.environ.get("BENCH_RUNS", "150"))
    rtt = _rtt_floor()
    shard = _measure(B_CHIP, T, n_runs)
    # the stronger statement: the ENTIRE 100k fleet batch on ONE chip —
    # no pro-rating, no fleet needed. Same run count (same p99 protocol).
    # A failure here (an 8x-batch OOM) fails the leg: a record that
    # silently lacks its unprorated half is not a result.
    whole = _measure(B_TOTAL, T, n_runs)
    whole_fields = {
        "p99_s_100k_single_chip": round(whole["p99"], 6),
        "p50_s_100k_single_chip": round(whole["p50"], 6),
        "single_chip_runs": whole["runs"],
        "compile_s_100k": round(whole["compile_s"], 3),
        **_digest_fields("digest_100k", whole["digest"]),
    }

    p50, p99 = shard["p50"], shard["p99"]
    pairs_per_sec = B_CHIP / p50
    # device-compute estimate: the same run with the measured readback
    # round-trip removed
    exec_est = max(p50 - rtt, 1e-9)
    return {
        "value": round(pairs_per_sec, 1),
        "vs_baseline": round(pairs_per_sec / TARGET_PAIRS_PER_SEC_PER_CHIP, 3),
        # the claim, measured in its own shape: time for one chip's 12,500-pair
        # shard of the 100k fleet batch == fleet time to 100k on v5e-8
        # (pro-rated; the O(k*8) top-k reduction is excluded — see docstring).
        # Forced-completion protocol: includes one readback round-trip.
        "p99_s_at_100k": round(p99, 6),
        "p50_s_at_100k": round(p50, 6),
        "min_s": round(shard["min"], 6),
        "max_s": round(shard["max"], 6),
        "std_s": round(shard["std"], 6),
        "runs": shard["runs"],
        "batch_per_chip": B_CHIP,
        "pairs_total": B_TOTAL,
        "compile_s": round(shard["compile_s"], 3),
        "readback_rtt_floor_s": round(rtt, 6),
        "pairs_per_sec_rtt_adjusted": round(B_CHIP / exec_est, 1),
        # the completion-proof scalar (also catches silent numerical drift
        # in score_pairs round-over-round: same seed, same digest)
        **_digest_fields("digest", shard["digest"]),
        # the whole 100k batch on ONE chip (unprorated: beats the 8-chip
        # claim outright if < 1 s)
        **whole_fields,
        "backend": jax.default_backend(),
    }


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, str(default)))
    except ValueError:
        return default


def main() -> None:
    if "--device-only" in sys.argv or "--long-only" in sys.argv:
        # the children that hold the accelerator: persistent compile
        # cache on first (JAX_COMPILATION_CACHE_DIR, else this checkout's
        # .jax_cache/), then the leg
        from foremast_tpu.engine.pipeline import enable_compile_cache

        enable_compile_cache()
        leg = (_device_fields if "--device-only" in sys.argv
               else _long_window_fields)
        print(json.dumps(leg()))
        return

    # parse the deadlines FIRST: a malformed env var must not throw away
    # a 15-minute cycle bench later
    timeout_s = _env_float("BENCH_DEVICE_TIMEOUT", 1200.0)
    # The device leg runs FIRST, in a CHILD with a hard deadline: this
    # parent never initialises a JAX backend, so exactly one process
    # holds the accelerator at a time and a hung device costs one
    # deadline, not the run.
    cpu_run = os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"
    child_env = dict(os.environ)
    if cpu_run:
        # CPU smoke runs (`make bench-smoke`): the real device protocol
        # (150 forced-completion runs plus a B=100k XLA:CPU compile)
        # cannot finish inside the child deadline. Shrink to a completing
        # protocol unless the caller pinned sizes; the JSON stays
        # self-describing (backend=cpu, runs, pairs_total) and is a
        # plumbing check, never a device number.
        child_env.setdefault("BENCH_RUNS", "20")
        child_env.setdefault("BENCH_PAIRS_TOTAL", "25000")
        child_env.setdefault("BENCH_LONG_WINDOW", "2048")
        child_env.setdefault("BENCH_LONG_BATCH", "64")
        child_env.setdefault("BENCH_LONG_RUNS", "10")
    device, err = _run_json_child(
        [sys.executable, os.path.abspath(__file__), "--device-only"],
        timeout_s=timeout_s, env=child_env,
    )
    if device is None:
        # no device number, no record: nothing is printed that a reader
        # could take for a measurement, and the exit code says so
        print(f"bench: device leg failed: {err}", file=sys.stderr)
        sys.exit(1)
    failed = False
    if os.environ.get("BENCH_SKIP_LONG", "0").strip().lower() in (
            "1", "true", "yes", "on"):
        device["long_window_skipped"] = True
    else:
        # the 7-day-window leg gets its OWN child + deadline: 10k-step
        # scan compiles are slow, and a long-leg death must not cost the
        # headline numbers already in hand — but it does fail the run
        long_rec, long_err = _run_json_child(
            [sys.executable, os.path.abspath(__file__), "--long-only"],
            timeout_s=_env_float("BENCH_LONG_TIMEOUT", 600.0),
            env=child_env,
        )
        if long_rec is not None:
            device.update(long_rec)
        else:
            device["long_window_error"] = long_err
            failed = True
    # calibrate the mesh leg's reduction-share estimate with THIS run's
    # measured device score time (p50 minus the readback round-trip)
    p50 = device.get("p50_s_at_100k")
    rtt = device.get("readback_rtt_floor_s", 0.0)
    if p50 and not cpu_run:
        # setdefault: an operator-exported BENCH_DEVICE_SCORE_S is a
        # documented override and must win over self-calibration
        os.environ.setdefault(
            "BENCH_DEVICE_SCORE_S", str(max(p50 - rtt, 1e-6)))
    cycle_extra = _cycle_bench()
    print(json.dumps({
        "metric": "canary_pairs_scored_per_sec_per_chip",
        "unit": "pairs/s/chip",
        **device,
        **cycle_extra,
    }))
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
