"""ops/rowblock.py: row-blocked vmap. A batch beyond one block must give
every row the bits a plain vmap gives it, and must show the compiler a
block-sized body (that is the whole point: on the TPU compile time grows
with the elements of the block, PR 21)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from foremast_tpu.ops import rowblock
from foremast_tpu.ops import triage as triage_ops
from foremast_tpu.parallel import fleet as fl


def _pair_args(B, T, seed=0):
    rng = np.random.default_rng(seed)
    spec = fl.pair_arg_spec(B, T)
    x = rng.normal(10, 2, (B, T)).astype(np.float32)
    y = rng.normal(10.4, 2, (B, T)).astype(np.float32)
    xm = rng.random((B, T)) > 0.05
    ym = rng.random((B, T)) > 0.05
    return (x, xm, y, ym, np.full(B, 0.01, np.float32),
            np.full(B, 0b11111, np.int32)) + tuple(spec[6:])


def test_block_rows_follow_the_window_length():
    def rows(T):
        return max(rowblock.MIN_BLOCK_ROWS, rowblock.BLOCK_ELEMS // T)

    assert rows(256) == 1024       # a T=128 canary pair (both samples)
    assert rows(16384) == 16       # a 7-day window: the smallest rung
    assert rows(1 << 20) == rowblock.MIN_BLOCK_ROWS


def test_blocked_pair_scores_equal_plain_vmap_bit_for_bit():
    # 2500 rows at T=128: two full 1024-row blocks and a 452-row remainder
    args = _pair_args(2500, 128)
    blocked = jax.tree.map(np.asarray, fl.score_pairs(*args))
    plain = jax.tree.map(
        np.asarray, jax.jit(jax.vmap(fl._pair_verdict))(*args))
    assert blocked["unhealthy"].shape == (2500,)
    assert 0 < int(blocked["unhealthy"].sum()) < 2500
    for k in plain:
        assert np.array_equal(blocked[k], plain[k], equal_nan=True), k


def test_blocked_triage_screen_equals_plain_vmap_bit_for_bit():
    rng = np.random.default_rng(1)
    B, T = 48, 16384  # 16-row blocks at the 7-day bucket
    x = rng.normal(50, 3, (B, T)).astype(np.float32)
    m = rng.random((B, T)) > 0.1
    reg = np.zeros((B, T), bool)
    reg[:, -64:] = True
    args = (x, m, reg, np.full(B, 3.0, np.float32), np.ones(B, np.int32),
            np.zeros(B, np.float32), np.full(B, 0.25, np.float32))
    blocked = jax.tree.map(np.asarray, triage_ops.screen_rows(*args, 30))
    plain = jax.tree.map(np.asarray, jax.jit(jax.vmap(
        partial(triage_ops._screen_1d, window=30)))(*args))
    for k in plain:
        assert np.array_equal(blocked[k], plain[k], equal_nan=True), k


def test_compiler_sees_a_block_sized_body_beyond_one_block():
    """The 8192-row default rung lowers to a loop whose sort runs on
    1024-row blocks; a batch inside one block stays one plain vmap."""
    big = fl.score_pairs.lower(*_pair_args(8192, 128)).as_text()
    assert "tensor<1024x256xf32>" in big       # the block the sort sees
    assert "tensor<8192x256xf32>" not in big   # never the whole rung
    small = fl.score_pairs.lower(*_pair_args(512, 128)).as_text()
    assert "tensor<512x256xf32>" in small


def test_vmap_rows_handles_a_batch_smaller_than_min_rows():
    out = rowblock.vmap_rows(lambda a, b: jnp.sum(a) + b,
                             (jnp.ones((3, 8)), jnp.arange(3.0)), 8)
    assert np.allclose(np.asarray(out), [8.0, 9.0, 10.0])
