"""Pairwise test kernels vs scipy reference implementations.

This is the health-score parity harness required by BASELINE.md: every TPU
kernel is cross-checked against the scipy call the reference brain would have
made, over random ragged (masked) windows with and without ties.
"""
import numpy as np
import pytest
import scipy.stats as sps

from foremast_tpu.ops import (
    friedman_chi_square,
    kruskal_wallis,
    ks_2samp,
    mann_whitney_u,
    wilcoxon_signed_rank,
)

ATOL = 2e-4


def _windows(seed, T=30, ties=False, shift=0.0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=T).astype(np.float32)
    y = (rng.normal(size=T) + shift).astype(np.float32)
    if ties:
        x = np.round(x * 2) / 2
        y = np.round(y * 2) / 2
    xm = rng.random(T) > 0.2
    ym = rng.random(T) > 0.2
    # keep enough points for the asymptotic branch to be meaningful
    xm[:20] = True
    ym[:20] = True
    return x, xm, y, ym


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("shift", [0.0, 1.5])
def test_mann_whitney(seed, ties, shift):
    x, xm, y, ym = _windows(seed, ties=ties, shift=shift)
    U, p = mann_whitney_u(x, xm, y, ym)
    ref = sps.mannwhitneyu(
        x[xm], y[ym], alternative="two-sided", method="asymptotic", use_continuity=True
    )
    np.testing.assert_allclose(float(U), ref.statistic, rtol=1e-5)
    np.testing.assert_allclose(float(p), ref.pvalue, atol=ATOL, rtol=1e-3)


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("shift", [0.0, 1.0])
def test_wilcoxon(seed, ties, shift):
    """Parity against the branch the kernel documents: exact null for
    untied, zero-free n <= 50 (where the engine's live windows sit and
    the normal approximation drifts up to ~0.02), TIE-CORRECTED normal
    approximation with ties.

    Tied windows pin scipy method='approx', not the default auto
    dispatch. Root cause of the former 14 red cases: scipy >= 1.13
    changed auto to select the EXACT null for n <= 50 even when ties are
    present — an exact distribution derived assuming distinct ranks, fed
    a midrank statistic (scipy documents the exact method as "not
    appropriate" for ties; older scipy, and the reference brain's
    scipy-1.x era default, used the normal approximation there). Our
    kernel keeps the tie-corrected approximation — the statistically
    defensible branch for tied data and the reference-era behavior — and
    matches scipy's own approx method to float32 precision, so the test
    now pins THAT equivalence instead of chasing scipy's auto heuristic
    across versions."""
    x, xm, y, ym = _windows(seed, ties=ties, shift=shift)
    both = xm & ym
    W, p = wilcoxon_signed_rank(x, xm, y, ym)
    d_all = (x - y)[both]
    d = d_all[d_all != 0]
    # the kernel's documented branch condition: exact only for untied,
    # zero-free samples (n <= WILCOXON_EXACT_MAX_N); ties among |d| or
    # dropped zero pairs select the tie-corrected approximation
    approx = (len(d) < len(d_all)
              or len(np.unique(np.abs(d))) < len(d))
    ref = sps.wilcoxon(d, zero_method="wilcox", correction=False,
                       method="approx" if approx else "auto")
    np.testing.assert_allclose(float(W), ref.statistic, rtol=1e-5)
    np.testing.assert_allclose(float(p), ref.pvalue, atol=ATOL, rtol=1e-3)


def test_wilcoxon_large_n_uses_approx():
    """Beyond WILCOXON_EXACT_MAX_N the tie-corrected normal approximation
    remains the (documented) branch, matching scipy method='approx'."""
    from foremast_tpu.ops.pairwise import WILCOXON_EXACT_MAX_N

    n = WILCOXON_EXACT_MAX_N + 10
    rng = np.random.default_rng(3)
    x = rng.normal(0, 1, n).astype(np.float32)
    y = (x - rng.normal(0.3, 1, n)).astype(np.float32)
    m = np.ones(n, bool)
    W, p = wilcoxon_signed_rank(x, m, y, m)
    d = (x - y)[(x - y) != 0]
    ref = sps.wilcoxon(d, zero_method="wilcox", correction=False,
                       method="approx")
    np.testing.assert_allclose(float(p), ref.pvalue, atol=ATOL, rtol=1e-3)


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("shift", [0.0, 1.5])
def test_kruskal_two_groups(seed, ties, shift):
    x, xm, y, ym = _windows(seed, ties=ties, shift=shift)
    groups = np.stack([x, y])
    masks = np.stack([xm, ym])
    H, p = kruskal_wallis(groups, masks)
    ref = sps.kruskal(x[xm], y[ym])
    np.testing.assert_allclose(float(H), ref.statistic, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(float(p), ref.pvalue, atol=ATOL, rtol=1e-3)


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("k", [3, 4])
def test_friedman(seed, k):
    rng = np.random.default_rng(seed)
    n = 24
    data = np.round(rng.normal(size=(n, k)) * 2).astype(np.float32) / 2
    bmask = rng.random(n) > 0.2
    bmask[:10] = True
    chi, p = friedman_chi_square(data, bmask)
    cols = [data[bmask, j] for j in range(k)]
    ref = sps.friedmanchisquare(*cols)
    np.testing.assert_allclose(float(chi), ref.statistic, rtol=2e-3, atol=1e-5)
    np.testing.assert_allclose(float(p), ref.pvalue, atol=ATOL, rtol=1e-3)


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("shift", [0.0, 1.0])
def test_ks_2samp_exact(seed, ties, shift):
    """Window buckets <= KS_EXACT_MAX_T get the EXACT finite-n null (the
    lattice-path DP), matching scipy's exact mode to float32 precision —
    the round-3 verdict's 0.024 Stephens drift (which could flip verdicts
    near the 0.01 threshold) is gone in the regime the engine scores."""
    x, xm, y, ym = _windows(seed, T=40, ties=ties, shift=shift)
    D, p = ks_2samp(x, xm, y, ym)
    ref = sps.ks_2samp(x[xm], y[ym], method="exact")
    np.testing.assert_allclose(float(D), ref.statistic, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(p), ref.pvalue, atol=1e-4)


def test_ks_2samp_exact_tiny_and_full_windows():
    # the small-n corner where Stephens drifted most, plus a dense T=128
    # window (the headline bench shape) — exact parity at both ends
    for T, thr in ((8, 1e-5), (128, 1e-4)):
        rng = np.random.default_rng(T)
        x = rng.normal(size=T).astype(np.float32)
        y = (rng.normal(size=T) + 0.3).astype(np.float32)
        m = np.ones(T, bool)
        D, p = ks_2samp(x, m, y, m)
        ref = sps.ks_2samp(x, y, method="exact")
        np.testing.assert_allclose(float(p), ref.pvalue, atol=thr)


def test_ks_2samp_sparse_long_bucket_still_exact():
    """Exactness is selected on the DYNAMIC valid counts, not the buffer
    length: a sparsely-masked long bucket (review probe: T=400, ~30 valid
    per side, where Stephens drifted 0.057 absolute) must match scipy's
    auto mode, which is exact by sample count."""
    T = 400
    rng = np.random.default_rng(11)
    x = rng.normal(size=T).astype(np.float32)
    y = (rng.normal(size=T) + 0.4).astype(np.float32)
    xm = rng.random(T) < 0.08
    ym = rng.random(T) < 0.08
    assert 5 < xm.sum() < 60 and 5 < ym.sum() < 60
    D, p = ks_2samp(x, xm, y, ym)
    ref = sps.ks_2samp(x[xm], y[ym])  # auto -> exact at these counts
    np.testing.assert_allclose(float(D), ref.statistic, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(p), ref.pvalue, atol=1e-4)


def test_ks_2samp_large_samples_use_stephens():
    """Samples BEYOND the exact grid bound fall back to the
    Stephens-corrected asymptotic. Parity against the classic corrected
    formula, and sanity against scipy asymp."""
    import scipy.stats.distributions as dist

    from foremast_tpu.ops.pairwise import KS_EXACT_MAX_T

    T = KS_EXACT_MAX_T + 44  # dense masks => n1, n2 > the exact grid bound
    rng = np.random.default_rng(7)
    x = rng.normal(size=T).astype(np.float32)
    y = (rng.normal(size=T) + 0.2).astype(np.float32)
    xm = np.ones(T, bool)
    ym = np.ones(T, bool)
    D, p = ks_2samp(x, xm, y, ym)
    ref = sps.ks_2samp(x[xm], y[ym], method="asymp")
    np.testing.assert_allclose(float(D), ref.statistic, rtol=1e-5, atol=1e-6)
    n1, n2 = xm.sum(), ym.sum()
    en = np.sqrt(n1 * n2 / (n1 + n2))
    classic = dist.kstwobign.sf((en + 0.12 + 0.11 / en) * ref.statistic)
    np.testing.assert_allclose(float(p), classic, atol=2e-4)


def test_degenerate_identical_windows():
    x = np.ones(30, np.float32)
    m = np.ones(30, bool)
    _, p_mw = mann_whitney_u(x, m, x, m)
    _, p_w = wilcoxon_signed_rank(x, m, x, m)
    assert float(p_mw) == 1.0
    assert float(p_w) == 1.0


def test_all_masked_degenerate_p1_everywhere():
    # review finding: kruskal/friedman returned NaN on fully-masked input
    from foremast_tpu.ops import friedman_chi_square

    z = np.zeros(16, np.float32)
    zm = np.zeros(16, bool)
    for stat, p in (
        mann_whitney_u(z, zm, z, zm),
        wilcoxon_signed_rank(z, zm, z, zm),
        kruskal_wallis(np.stack([z, z]), np.stack([zm, zm])),
        ks_2samp(z, zm, z, zm),
        friedman_chi_square(np.zeros((8, 3), np.float32), np.zeros(8, bool)),
    ):
        assert np.isfinite(float(stat)), stat
        assert float(p) == 1.0, p


def test_two_sample_tests_matches_standalone():
    from foremast_tpu.ops import two_sample_tests

    x, xm, y, ym = _windows(3, ties=True, shift=0.7)
    fused = two_sample_tests(x, xm, y, ym)
    np.testing.assert_allclose(
        float(fused["mann_whitney"][1]), float(mann_whitney_u(x, xm, y, ym)[1]), rtol=1e-6
    )
    np.testing.assert_allclose(
        float(fused["kruskal"][1]),
        float(kruskal_wallis(np.stack([x, y]), np.stack([xm, ym]))[1]),
        rtol=1e-4,
    )
    np.testing.assert_allclose(
        float(fused["wilcoxon"][1]), float(wilcoxon_signed_rank(x, xm, y, ym)[1]), rtol=1e-6
    )
    np.testing.assert_allclose(
        float(fused["ks"][1]), float(ks_2samp(x, xm, y, ym)[1]), rtol=1e-6
    )


# ------------------------------------------------------------ exact sign test
@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("shift", [0.0, 1.5])
def test_sign_test_exact_matches_binomtest(seed, shift):
    """k=2 Friedman member: exact binomial p, parity vs scipy.binomtest.

    For p=1/2 the null is symmetric, so scipy's minlike two-sided p equals
    2*min-tail (clipped at 1) — the form sign_test_exact computes.
    """
    from foremast_tpu.ops import sign_test_exact

    x, xm, y, ym = _windows(seed, T=40, shift=shift)
    pm = xm & ym
    n, p = sign_test_exact(x, y, pm)
    pos = int(np.sum((y > x) & pm))
    neg = int(np.sum((y < x) & pm))
    assert int(n) == pos + neg
    if pos + neg == 0:
        assert float(p) == 1.0
        return
    ref = sps.binomtest(min(pos, neg), pos + neg, 0.5, alternative="two-sided")
    assert float(p) == pytest.approx(ref.pvalue, abs=ATOL)


def test_sign_test_exact_small_blocks_not_anticonservative():
    """5/5 one-sided wins: exact p = 2*(1/2)^5 = 0.0625, NOT the df=1
    chi-square approximation's ~0.025 (the advisor-flagged false-fire risk
    in 'all'/'any' composite mode at MIN_FRIEDMAN=5)."""
    from foremast_tpu.ops import sign_test_exact

    x = np.zeros(5, np.float32)
    y = np.ones(5, np.float32)
    m = np.ones(5, bool)
    n, p = sign_test_exact(x, y, m)
    assert int(n) == 5
    assert float(p) == pytest.approx(0.0625, abs=1e-6)
    # and therefore it cannot reject at the default alpha=0.01
    assert float(p) > 0.01


def test_sign_test_exact_all_tied_is_p1():
    from foremast_tpu.ops import sign_test_exact

    x = np.ones(30, np.float32)
    m = np.ones(30, bool)
    n, p = sign_test_exact(x, x, m)
    assert int(n) == 0 and float(p) == 1.0


# ------------------------------------------- a short window against 7 days
# foremast-trigger's rollover judges a 20-to-31-point current window
# against the trailing 7 days (10,081 points at 60 s), in the T=16384
# bucket. The rank sum of the 10,081-point side is about 5e7, where a
# float32 step is 4: U is taken from the short side's rank sum instead.
LONG_T = 16384
LONG_SHAPES = [(10081, 31), (31, 10081), (10081, 20)]
LONG_P_GAP = 1e-6


def _long_rows(seed, n_x, n_y, rows=8):
    """`rows` pairs in the T=16384 bucket, samples at four decimals (as
    a metric store serves them), the y side shifted a little per row."""
    rng = np.random.default_rng(seed)
    x = np.zeros((rows, LONG_T), np.float32)
    y = np.zeros((rows, LONG_T), np.float32)
    xm = np.zeros((rows, LONG_T), bool)
    ym = np.zeros((rows, LONG_T), bool)
    for r in range(rows):
        x[r, :n_x] = np.round(10.0 + rng.normal(size=n_x), 4)
        y[r, :n_y] = np.round(10.0 + rng.normal(size=n_y)
                              + 0.3 * rng.random(), 4)
        xm[r, :n_x] = ym[r, :n_y] = True
    return x, xm, y, ym


def _scipy_rows(x, xm, y, ym, test):
    return np.asarray([test(a[am].astype(np.float64),
                            b[bm].astype(np.float64))
                       for a, am, b, bm in zip(x, xm, y, ym)])


def _mw_ref(a, b):
    r = sps.mannwhitneyu(a, b, alternative="two-sided",
                         method="asymptotic", use_continuity=True)
    return r.statistic, r.pvalue


def _kw_ref(a, b):
    r = sps.kruskal(a, b)
    return r.statistic, r.pvalue


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("n_x,n_y", LONG_SHAPES)
@pytest.mark.parametrize("route", ["fused", "standalone"])
def test_mann_whitney_long_bucket(route, n_x, n_y, seed):
    import jax

    from foremast_tpu.ops import two_sample_tests
    from foremast_tpu.ops.pairwise import mann_whitney_u_batch

    x, xm, y, ym = _long_rows(seed, n_x, n_y)
    if route == "fused":
        U, p = jax.jit(jax.vmap(two_sample_tests))(
            x, xm, y, ym)["mann_whitney"]
    else:
        U, p = mann_whitney_u_batch(x, xm, y, ym)
    ref = _scipy_rows(x, xm, y, ym, _mw_ref)
    # U is a whole or half number of at most n_x n_y: exact
    np.testing.assert_array_equal(np.asarray(U, np.float64), ref[:, 0])
    assert np.max(np.abs(np.asarray(p, np.float64) - ref[:, 1])) \
        <= LONG_P_GAP


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("n_x,n_y", LONG_SHAPES)
def test_kruskal_two_groups_long_bucket(n_x, n_y, seed):
    import jax

    from foremast_tpu.ops import two_sample_tests

    x, xm, y, ym = _long_rows(seed, n_x, n_y)
    H, p = jax.jit(jax.vmap(two_sample_tests))(x, xm, y, ym)["kruskal"]
    ref = _scipy_rows(x, xm, y, ym, _kw_ref)
    np.testing.assert_allclose(np.asarray(H, np.float64), ref[:, 0],
                               rtol=1e-5, atol=1e-5)
    assert np.max(np.abs(np.asarray(p, np.float64) - ref[:, 1])) \
        <= LONG_P_GAP


@pytest.mark.parametrize("n_x,n_y", LONG_SHAPES)
def test_score_pairs_long_bucket_min_p(n_x, n_y):
    """The fleet program under `mann_whitney_all`: the baseline is x, its
    smallest enabled p-value is Mann-Whitney's."""
    from foremast_tpu.parallel import fleet as fl

    x, xm, y, ym = _long_rows(3, n_x, n_y)
    args = list(fl.pair_arg_spec(8, LONG_T))
    args[:4] = x, xm, y, ym
    args[4] = np.full(8, 0.01, np.float32)
    args[5] = np.full(8, fl.TEST_MANN_WHITNEY, np.int32)
    args[6] = np.full(8, fl.COMBINE_ALL, np.int32)
    args[8] = np.full(8, 3.0, np.float32)
    out = fl.score_pairs(*args)
    ref = _scipy_rows(x, xm, y, ym, _mw_ref)
    assert np.max(np.abs(np.asarray(out["min_p"], np.float64)
                         - ref[:, 1])) <= LONG_P_GAP


def _mw_long_side(x, x_mask, y, y_mask):
    """Mann-Whitney with U taken from x's own rank sum, whichever side is
    shorter: the route the kernels took before the short side's."""
    import jax.numpy as jnp

    from foremast_tpu.ops.ranks import rank_sum_stats
    from foremast_tpu.ops.stats import norm_sf

    f32 = jnp.float32
    comb = jnp.concatenate([x, y]).astype(f32)
    cmask = jnp.concatenate([x_mask, y_mask])
    from_x = jnp.concatenate([jnp.ones(x.shape, f32), jnp.zeros(y.shape, f32)])
    R1, tie, _ = rank_sum_stats(comb, cmask, from_x)
    n1 = jnp.sum(x_mask.astype(f32))
    n2 = jnp.sum(y_mask.astype(f32))
    N = n1 + n2
    U1 = R1 - n1 * (n1 + 1.0) / 2.0
    U = jnp.maximum(U1, n1 * n2 - U1)
    s2 = n1 * n2 / 12.0 * ((N + 1.0) - tie / jnp.where(
        N * (N - 1.0) == 0, 1.0, N * (N - 1.0)))
    s = jnp.sqrt(jnp.maximum(s2, 0.0))
    z = (U - n1 * n2 / 2.0 - 0.5) / jnp.where(s == 0, 1.0, s)
    p = jnp.where(s > 0.0, jnp.clip(2.0 * norm_sf(z), 0.0, 1.0), 1.0)
    return U1, p


@pytest.mark.parametrize("route", ["fused", "standalone"])
@pytest.mark.parametrize("ties", [False, True])
def test_short_side_route_is_todays_at_t128(route, ties):
    """At T=128, as in the rolling-update cells, every rank sum is exact
    in float32 whichever side it is taken of: the short side's route
    gives today's U1 and p bit for bit, with x the short side and with y."""
    import jax

    from foremast_tpu.ops import two_sample_tests
    from foremast_tpu.ops.pairwise import mann_whitney_u_batch

    rng = np.random.default_rng(128)
    B, T = 16, 128
    x = np.round(rng.normal(10.0, 1.0, (B, T)), 1 if ties else 4)
    y = np.round(rng.normal(10.3, 1.0, (B, T)), 1 if ties else 4)
    n_x = rng.integers(20, T + 1, B)
    n_y = rng.integers(20, T + 1, B)
    n_x[:4] = 80  # the rolling-update cells' baseline against 80..128
    n_y[:4] = [80, 81, 100, 128]
    cols = np.arange(T)
    xm, ym = cols < n_x[:, None], cols < n_y[:, None]
    x, y = x.astype(np.float32), y.astype(np.float32)
    assert (n_x < n_y).any() and (n_x > n_y).any()
    if route == "fused":
        U, p = jax.jit(jax.vmap(two_sample_tests))(
            x, xm, y, ym)["mann_whitney"]
    else:
        U, p = mann_whitney_u_batch(x, xm, y, ym)
    U0, p0 = jax.jit(jax.vmap(_mw_long_side))(x, xm, y, ym)
    np.testing.assert_array_equal(np.asarray(U), np.asarray(U0))
    np.testing.assert_array_equal(np.asarray(p), np.asarray(p0))


# --------------------------------- each window of a pair at its own bucket
# A pair row is packed with its current window at the current's own
# bucket (Tc = 32 for a 20-to-31-point window) and its baseline at the
# row's: the program must judge it as it judged the same row padded
# symmetric at the baseline's bucket.
SPLIT_TC = 32


def _split_rows(Tb, nb, seed):
    """Eight (Tb, 32) pair rows, each a case the split must carry:
    float samples, heavy integer ties, a NaN, an all-masked current,
    a current window with more samples than its baseline, gaps."""
    rng = np.random.default_rng(seed)
    rows, Tc = 8, SPLIT_TC
    b = np.zeros((rows, Tb), np.float32)
    c = np.zeros((rows, Tc), np.float32)
    bm = np.zeros((rows, Tb), bool)
    cm = np.zeros((rows, Tc), bool)

    def put(r, base, cur):
        b[r, :len(base)], c[r, :len(cur)] = base, cur
        bm[r, :len(base)], cm[r, :len(cur)] = True, True

    put(0, np.round(10 + rng.normal(size=nb), 4),
        np.round(10.4 + rng.normal(size=31), 4))
    put(1, np.round(10 + rng.normal(size=nb), 4),
        np.round(10 + rng.normal(size=20), 4))
    put(2, rng.integers(0, 4, nb), rng.integers(0, 4, 27))
    put(3, rng.integers(0, 4, nb), rng.integers(2, 6, 31))
    put(4, np.round(10 + rng.normal(size=nb), 4),
        np.round(10 + rng.normal(size=24), 4))
    c[4, 7] = np.nan
    put(5, np.round(10 + rng.normal(size=nb), 4),
        np.round(10 + rng.normal(size=25), 4))
    cm[5] = False
    put(6, np.round(10 + rng.normal(size=12), 4),
        np.round(10.2 + rng.normal(size=31), 4))
    put(7, rng.integers(0, 50, nb), rng.integers(0, 50, 30))
    bm[7, rng.random(Tb) < 0.1] = False
    cm[7, [2, 3, 17]] = False
    return b, bm, c, cm


def _symmetric(c, cm, Tb):
    """The same current windows right-padded to the baseline's bucket."""
    pad = ((0, 0), (0, Tb - c.shape[1]))
    return np.pad(c, pad), np.pad(cm, pad)


SPLIT_SHAPES = [(1024, 600), (16384, 10081)]


@pytest.mark.parametrize("Tb,nb", SPLIT_SHAPES)
def test_score_pairs_split_equals_symmetric(Tb, nb):
    """Every output of the fleet program on (Tb, 32) rows equals the same
    rows at (Tb, Tb): flags and counts exactly, p-values within 1e-7
    (a float32 tie term or tail sum may be reduced in another order)."""
    from foremast_tpu.parallel import fleet as fl

    b, bm, c, cm = _split_rows(Tb, nb, seed=Tb)
    rows = b.shape[0]
    args = list(fl.pair_arg_spec(rows, Tb, SPLIT_TC))
    args[:4] = b, bm, c, cm
    args[4] = np.full(rows, 0.01, np.float32)
    args[5] = np.full(rows, 31, np.int32)  # all five tests
    args[6] = np.full(rows, fl.COMBINE_ANY, np.int32)
    args[8] = np.full(rows, 3.0, np.float32)
    args[9] = np.full(rows, 3, np.int32)  # both sides of the band
    split = {k: np.asarray(v) for k, v in fl.score_pairs(*args).items()}
    args[2:4] = _symmetric(c, cm, Tb)
    sym = {k: np.asarray(v) for k, v in fl.score_pairs(*args).items()}
    for k in ("unhealthy", "pairwise_unhealthy", "band_unhealthy",
              "band_count"):
        np.testing.assert_array_equal(split[k], sym[k], err_msg=k)
    for k in ("pvalues", "min_p"):
        np.testing.assert_allclose(split[k], sym[k], rtol=0, atol=1e-7,
                                   err_msg=k)
    # the cases are live: a rejection, a band hit, a row that cannot judge
    assert split["pairwise_unhealthy"].any()
    assert split["band_count"].max() > 0
    assert split["min_p"][5] == 1.0


@pytest.mark.parametrize("Tb,nb", SPLIT_SHAPES)
def test_two_sample_tests_split_statistics_are_exact(Tb, nb):
    """U, W and the KS distance are counts and half-integers: the split
    gives them bit for bit; H carries the tie term's float32 sum."""
    import jax

    from foremast_tpu.ops import two_sample_tests

    b, bm, c, cm = _split_rows(Tb, nb, seed=Tb + 1)
    run = jax.jit(jax.vmap(two_sample_tests))
    split = run(b, bm, c, cm)
    sym = run(b, bm, *_symmetric(c, cm, Tb))
    for test in ("mann_whitney", "wilcoxon", "ks"):
        np.testing.assert_array_equal(np.asarray(split[test][0]),
                                      np.asarray(sym[test][0]),
                                      err_msg=test)
    np.testing.assert_allclose(np.asarray(split["kruskal"][0]),
                               np.asarray(sym["kruskal"][0]), rtol=1e-6)
    for test in split:
        np.testing.assert_allclose(np.asarray(split[test][1]),
                                   np.asarray(sym[test][1]), rtol=0,
                                   atol=1e-7, err_msg=test)
