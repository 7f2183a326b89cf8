"""The span kernels' 64-bit indexed writes, as 32-bit scatters.

`scatter_set` writes an int64 target as two 32-bit scatters of its
words; the PHOLD kernel's repeating-row drop counts go through an
int32 scatter-add, then a dense int64 add.  Both must give the bits
of the plain int64 `.at[]` form they replace.
"""

import numpy as np
import pytest

I64_MIN = np.iinfo(np.int64).min
I64_MAX = np.iinfo(np.int64).max
EDGES = np.array([0, -1, 1 << 32, -(1 << 32), 1 << 31, (1 << 31) - 1,
                  (1 << 32) - 1, I64_MAX, I64_MIN], np.int64)


def _values(rng, n):
    v = rng.integers(I64_MIN, I64_MAX, size=n, dtype=np.int64,
                     endpoint=True)
    v[:len(EDGES)] = EDGES
    return v


@pytest.mark.parametrize("ndim", [1, 2])
def test_scatter_set_int64_matches_at_set(ndim):
    import jax.numpy as jnp

    from shadow_tpu.ops.span_mesh import scatter_set
    rng = np.random.default_rng(7 + ndim)
    rows, cols, n = 1000, 16, 700
    if ndim == 1:
        a = _values(rng, rows * cols)
        # unique in-range slots plus out-of-range ones, dropped
        idx = rng.permutation(rows * cols)[:n]
        idx[::7] = rows * cols + 8
        idx = jnp.asarray(idx)
    else:
        a = _values(rng, rows * cols).reshape(rows, cols)
        r = rng.permutation(rows)[:n]
        r[::7] = rows + 1
        idx = (jnp.asarray(r), jnp.asarray(rng.integers(0, cols, n)))
    a = jnp.asarray(a)
    v = jnp.asarray(_values(rng, n)[::-1].copy())
    want = np.asarray(a.at[idx].set(v, mode="drop"))
    got = np.asarray(scatter_set(a, idx, v))
    assert got.dtype == np.int64
    np.testing.assert_array_equal(got, want)
    # every edge value lands somewhere and reads back exact
    assert set(EDGES) <= set(got.ravel().tolist())


def test_scatter_set_scalar_value_and_narrow_dtypes():
    import jax.numpy as jnp

    from shadow_tpu.ops.span_mesh import scatter_set
    idx = jnp.asarray([3, 0, 99])
    a64 = jnp.zeros(8, jnp.int64)
    np.testing.assert_array_equal(
        np.asarray(scatter_set(a64, idx, I64_MIN)),
        np.asarray(a64.at[idx].set(I64_MIN, mode="drop")))
    for dt, v in ((jnp.int32, -5), (jnp.uint32, 7), (jnp.bool_, True)):
        a = jnp.zeros(8, dt)
        got = scatter_set(a, idx, v)
        assert got.dtype == a.dtype
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(a.at[idx].set(v, mode="drop")))


def test_count_then_add_matches_int64_scatter_add():
    """propagate's per-source drop counts: rows repeat, masked-out
    lanes go to an out-of-range row and drop."""
    import jax.numpy as jnp
    rng = np.random.default_rng(11)
    H, O, tel = 50, 4000, 3
    src = jnp.asarray(rng.integers(0, H, O).astype(np.int32))
    miss = jnp.asarray(rng.random(O) < 0.4)
    rows = jnp.where(miss, src, H + 1)
    base = jnp.asarray(_values(rng, H) >> 2)
    causes = jnp.asarray((_values(rng, H * 15) >> 2).reshape(H, 15))
    want_p = base.at[rows].add(1, mode="drop")
    want_c = causes.at[rows, tel].add(1, mode="drop")
    cnt = jnp.zeros(H, jnp.int32).at[rows].add(1, mode="drop")
    got_p = base + cnt
    got_c = causes.at[:, tel].add(cnt)
    assert got_p.dtype == got_c.dtype == np.int64
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))
    assert int(cnt.max()) > 1  # rows did repeat
