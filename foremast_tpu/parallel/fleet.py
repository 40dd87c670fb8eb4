"""Fleet-scale canary scoring: one device launch for the whole fleet.

This is the north-star path (BASELINE.json): 100k concurrent (baseline,
canary) metric-pair windows scored in one jitted, mesh-sharded program —
replacing the reference brain's one-job-at-a-time CPU worker loop
(ES poll -> fetch -> scipy -> write, SURVEY.md §2.4).

Structure:
  * `score_pairs` — the fused per-pair program: full pairwise test family +
    moving-average band check + combined verdict, vmapped over the batch.
    With inputs sharded over the fleet axis it runs embarrassingly parallel;
    XLA partitions it without communication.
  * `fleet_summary` — the cross-chip part: unhealthy counts and worst-k
    services. Written with shard_map + ICI collectives (psum / all_gather of
    per-shard top-k) so the reduction cost is O(k * n_devices), never a
    gather of the full fleet.

Verdict codes follow the brain's combinator semantics: a pair is unhealthy
if the enabled pairwise tests reject under the ALL/ANY combinator
(foremast-brain/README.md:34-38) OR the band check flags anomalies.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ops import forecast as fc
from ..ops.pairwise import paired_columns, sign_test_exact, two_sample_tests
from ..ops.rowblock import vmap_rows
from .mesh import FLEET_AXIS, fleet_sharding

__all__ = ["score_pairs", "pair_arg_spec", "make_fleet_scorer",
           "fleet_summary", "COMBINE_ANY", "COMBINE_ALL"]

_F = jnp.float32

# test-enable bitmask positions
TEST_MANN_WHITNEY = 1
TEST_WILCOXON = 2
TEST_KRUSKAL = 4
TEST_KS = 8
TEST_FRIEDMAN = 16  # paired (baseline_t, current_t) blocks, k=2 treatments

COMBINE_ANY = 0  # unhealthy if ANY enabled test rejects
COMBINE_ALL = 1  # unhealthy only if ALL enabled tests reject

# minimum valid points per test (deploy/foremast/3_brain/foremast-brain.yaml:74-79)
MIN_MANN_WHITNEY = 20
MIN_WILCOXON = 20
MIN_KRUSKAL = 5
MIN_FRIEDMAN = 5  # complete (both-sides-valid) blocks


def _pair_verdict(
    baseline,
    b_mask,
    current,
    c_mask,
    pvalue_threshold,
    test_mask,
    combine,
    ma_window,
    band_threshold,
    bound_mode,
    min_lower_bound,
    min_points=None,
):
    """Single (baseline, current) judgment. vmapped by score_pairs.

    The two windows may differ in width (each is packed at its own
    bucket): the rank sort and the KS grid cover each side at its own,
    the paired tests the common width, and the band's judged region
    starts where the baseline ends.

    min_points: (3,) or (4,) gates for mann-whitney/wilcoxon/kruskal
    [/friedman] — the MIN_*_DATA_POINTS config surface
    (foremast-brain.yaml:74-79); a 3-wide vector keeps Friedman at its
    MIN_FRIEDMAN default for callers that predate the fifth test.
    """
    if min_points is None:
        min_points = jnp.asarray(
            [MIN_MANN_WHITNEY, MIN_WILCOXON, MIN_KRUSKAL, MIN_FRIEDMAN]
        )
    friedman_gate = (
        min_points[3] if min_points.shape[-1] >= 4 else MIN_FRIEDMAN
    )
    n_b = jnp.sum(b_mask.astype(_F))
    n_c = jnp.sum(c_mask.astype(_F))
    n_min = jnp.minimum(n_b, n_c)

    tests = two_sample_tests(baseline, b_mask, current, c_mask)
    # Friedman over time blocks: each timestep with both sides valid is a
    # block ranked across the 2 treatments (the paired-comparison member of
    # the family, design.md:89-92). With k=2 the exact null is binomial, so
    # the p-value comes from the exact sign test rather than the df=1
    # chi-square approximation, which is anti-conservative at small block
    # counts (see ops.pairwise.sign_test_exact).
    pb, pb_mask, pc, pc_mask = paired_columns(baseline, b_mask, current,
                                              c_mask)
    paired_blocks = pb_mask & pc_mask
    n_blocks = jnp.sum(paired_blocks.astype(_F))
    _, p_friedman = sign_test_exact(pb, pc, paired_blocks)
    pvals = jnp.stack(
        [
            tests["mann_whitney"][1],
            tests["wilcoxon"][1],
            tests["kruskal"][1],
            tests["ks"][1],
            p_friedman,
        ]
    )

    # a test participates only if enabled AND it has enough data
    enough = jnp.stack(
        [
            n_min >= min_points[0],
            n_min >= min_points[1],
            n_min >= min_points[2],
            n_min >= 2,
            n_blocks >= friedman_gate,
        ]
    )
    bits = jnp.asarray([TEST_MANN_WHITNEY, TEST_WILCOXON, TEST_KRUSKAL,
                        TEST_KS, TEST_FRIEDMAN])
    enabled = ((test_mask & bits) > 0) & enough
    rejects = (pvals < pvalue_threshold) & enabled
    n_enabled = jnp.sum(enabled)
    any_reject = jnp.any(rejects)
    all_reject = jnp.all(rejects | ~enabled) & (n_enabled > 0)
    pairwise_unhealthy = jnp.where(combine == COMBINE_ALL, all_reject, any_reject)

    # band check: baseline window drives an MA band; current judged against it
    concat = jnp.concatenate([baseline, current])
    concat_m = jnp.concatenate([b_mask, c_mask])
    Tb = baseline.shape[-1]
    region = jnp.arange(concat.shape[-1]) >= Tb
    preds = fc._moving_average_1d(concat, concat_m & ~region, ma_window)
    hist_sel = concat_m & ~region
    r = jnp.where(hist_sel, concat - preds, 0.0)
    nh = jnp.sum(hist_sel.astype(_F))
    # no baseline history -> infinite band -> fail-open (cannot judge)
    sigma = jnp.where(
        nh >= 2.0, jnp.sqrt(jnp.sum(r * r) / jnp.maximum(nh, 1.0)), jnp.inf
    )
    thr = band_threshold * sigma
    upper = preds + thr
    lower = jnp.maximum(preds - thr, min_lower_bound)
    mode = jnp.where(bound_mode == 0, 3, bound_mode)
    viol = ((concat > upper) & ((mode & 1) > 0)) | ((concat < lower) & ((mode & 2) > 0))
    flags = viol & concat_m & region
    band_count = jnp.sum(flags)
    n_checked = jnp.maximum(jnp.sum((concat_m & region).astype(_F)), 1.0)
    band_unhealthy = band_count.astype(_F) / n_checked > 0.3

    unhealthy = pairwise_unhealthy | band_unhealthy
    # severity: how loudly this pair is anomalous (for fleet top-k);
    # -log10(min enabled p) + band violation fraction
    min_p = jnp.min(jnp.where(enabled, pvals, 1.0))
    severity = -jnp.log10(jnp.maximum(min_p, 1e-12)) + band_count.astype(_F) / n_checked
    return {
        "unhealthy": unhealthy,
        "severity": severity,
        "pvalues": pvals,
        "band_count": band_count,
        "min_p": min_p,
        # which detector fired, so verdict reasons can say the true cause
        "pairwise_unhealthy": pairwise_unhealthy,
        "band_unhealthy": band_unhealthy,
    }


def _score_rows(*args):
    """`vmap(_pair_verdict)` over the batch axis in row blocks
    (ops/rowblock.py: the TPU compiler's time grows with the elements of
    the block it is shown; a row's sorted view spans both samples)."""
    return vmap_rows(_pair_verdict, args,
                     args[0].shape[-1] + args[2].shape[-1])


# NOTE: jitted calls ASYNC-dispatch — the returned dict holds device
# values that materialize only when the caller converts them (the engine's
# launch/collect split in analyzer._launch_chunks rides exactly this).
score_pairs = jax.jit(_score_rows)


def pair_arg_spec(B: int, T: int, Tc: int | None = None):
    """Zeroed argument tuple matching score_pairs' PRODUCTION signature.

    Mirrors analyzer._launch_pairs' packing (shapes and dtypes) so a
    caller (chip_smoke.py, the row-block tests) can compile or cost a
    (rung, T) program without synthesizing windows; the transfer-counter
    test (tests/test_pipeline.py) holds its bytes to the real packing's.
    `Tc` is the current window's width where it is packed narrower than
    the baseline's T (default T).
    """
    import numpy as np

    Tc = T if Tc is None else Tc
    return (
        np.zeros((B, T), np.float32), np.zeros((B, T), bool),
        np.zeros((B, Tc), np.float32), np.zeros((B, Tc), bool),
        np.zeros(B, np.float32),                    # pairwise p threshold
        np.zeros(B, np.int32),                      # enabled-test bitmask
        np.zeros(B, np.int32),                      # ANY/ALL combinator
        np.full(B, 30, np.int32),                   # ma_window
        np.zeros(B, np.float32),                    # band threshold
        np.ones(B, np.int32),                       # bound mode
        np.zeros(B, np.float32),                    # min lower bound
        np.tile(np.asarray(
            [MIN_MANN_WHITNEY, MIN_WILCOXON, MIN_KRUSKAL, MIN_FRIEDMAN],
            np.int32), (B, 1)),
    )


def make_fleet_scorer(mesh, k: int = 8):
    """Build the sharded fleet program for a given mesh.

    Returns a jitted fn taking batched pair inputs (B divisible by the fleet
    axis size) and returning per-pair verdicts plus the fleet summary
    (unhealthy count, worst-k severities and indices) — one launch, with the
    verdict reduction riding ICI.
    """
    shard = fleet_sharding(mesh)
    n_shards = mesh.shape[FLEET_AXIS]

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(FLEET_AXIS),) * 4 + (P(FLEET_AXIS),) * 8 + (P(FLEET_AXIS),),
        out_specs=(P(FLEET_AXIS), P(), P(), P()),
        check_vma=False,
    )
    def _sharded(
        baseline, b_mask, current, c_mask,
        pvalue_threshold, test_mask, combine, ma_window,
        band_threshold, bound_mode, min_lower_bound, min_points, global_idx,
    ):
        out = _score_rows(
            baseline, b_mask, current, c_mask,
            pvalue_threshold, test_mask, combine, ma_window,
            band_threshold, bound_mode, min_lower_bound, min_points,
        )
        local_unhealthy = jnp.sum(out["unhealthy"].astype(jnp.int32))
        total_unhealthy = jax.lax.psum(local_unhealthy, FLEET_AXIS)
        # communication-lean top-k: local k, then gather k*n_shards candidates
        sev = jnp.where(out["unhealthy"], out["severity"], -jnp.inf)
        loc_v, loc_i = jax.lax.top_k(sev, min(k, sev.shape[0]))
        cand_v = jax.lax.all_gather(loc_v, FLEET_AXIS, tiled=True)
        cand_idx = jax.lax.all_gather(global_idx[loc_i], FLEET_AXIS, tiled=True)
        top_v, top_pos = jax.lax.top_k(cand_v, min(k, cand_v.shape[0]))
        top_idx = cand_idx[top_pos]
        return out, total_unhealthy, top_v, top_idx

    def run(baseline, b_mask, current, c_mask, cfg):
        B = baseline.shape[0]
        if B % n_shards:
            raise ValueError(f"batch {B} not divisible by fleet axis {n_shards}")
        gidx = jnp.arange(B)
        min_points = cfg.get(
            "min_points",
            jnp.tile(
                jnp.asarray(
                    [MIN_MANN_WHITNEY, MIN_WILCOXON, MIN_KRUSKAL, MIN_FRIEDMAN]
                ),
                (B, 1),
            ),
        )
        args = (
            baseline, b_mask, current, c_mask,
            cfg["pvalue_threshold"], cfg["test_mask"], cfg["combine"],
            cfg["ma_window"], cfg["band_threshold"], cfg["bound_mode"],
            cfg["min_lower_bound"], min_points, gidx,
        )
        args = jax.device_put(
            args, tuple(shard for _ in args)
        )
        out, total, top_v, top_idx = _jit(args)
        return out, int(total), top_v, top_idx

    @jax.jit
    def _jit(args):
        return _sharded(*args)

    return run


def fleet_summary(unhealthy, severity, mesh, k: int = 8):
    """Standalone summary reduction for already-scored fleets."""
    scorer_in = NamedSharding(mesh, P(FLEET_AXIS))

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(FLEET_AXIS), P(FLEET_AXIS), P(FLEET_AXIS)),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    def _sum(u, s, gi):
        total = jax.lax.psum(jnp.sum(u.astype(jnp.int32)), FLEET_AXIS)
        sev = jnp.where(u, s, -jnp.inf)
        v, i = jax.lax.top_k(sev, min(k, sev.shape[0]))
        cv = jax.lax.all_gather(v, FLEET_AXIS, tiled=True)
        ci = jax.lax.all_gather(gi[i], FLEET_AXIS, tiled=True)
        tv, tp = jax.lax.top_k(cv, min(k, cv.shape[0]))
        return total, tv, ci[tp]

    gidx = jnp.arange(unhealthy.shape[0])
    u, s, gi = jax.device_put((unhealthy, severity, gidx), (scorer_in,) * 3)
    return jax.jit(_sum)(u, s, gi)
