"""``robust_lam`` by exact selection: the same bits as the sort formulas.

``robust_lam`` finds each median's order statistics by bisection over an
int32 key that orders as ``lax.sort`` orders floats, instead of sorting
the plane.  The sort-based formula it replaced stays here as the
reference: ``jnp.median`` unmasked, ``factorized._masked_median`` masked.
Every case compares the returned ``lam`` bit for bit (NaN against NaN).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import factorized as fz
from repro.core.problems import pack_mask
from repro.core.factorized import robust_lam


@jax.jit
def _lam_by_sort(m_obs, mask=None):
    x = m_obs.ravel().astype(jnp.float32)
    if mask is None:
        med = jnp.median(x)
        return 2.0 * 1.4826 * jnp.median(jnp.abs(x - med))
    keep = mask.ravel() > 0
    count = jnp.maximum(jnp.sum(keep.astype(jnp.int32)), 1)
    med = fz._masked_median(x, keep, count)
    return 2.0 * 1.4826 * fz._masked_median(jnp.abs(x - med), keep, count)


def _assert_same_bits(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    nan = np.isnan(want)
    np.testing.assert_array_equal(np.isnan(got), nan)
    np.testing.assert_array_equal(got[~nan].view(np.uint32),
                                  want[~nan].view(np.uint32))


def _normal(shape, seed=0):
    return np.random.default_rng(seed).normal(size=shape).astype(np.float32)


def _ties(shape):
    # few distinct values, many of them on both sides of the median
    return np.round(2 * _normal(shape, 1)).astype(np.float32)


def _signed_zeros(shape):
    x = _normal(shape, 2)
    pick = np.random.default_rng(3).random(shape)
    x[pick < 0.35] = 0.0
    x[pick > 0.65] = -0.0
    return x


def _infs(shape):
    x = _normal(shape, 4)
    pick = np.random.default_rng(5).random(shape)
    x[pick < 0.2] = np.inf
    x[pick > 0.85] = -np.inf
    return x


def _mostly_inf(shape):
    # more than half +inf: the median is +inf and |inf - inf| is NaN
    x = _normal(shape, 6)
    x[np.random.default_rng(7).random(shape) < 0.6] = np.inf
    return x


def _nans(shape):
    x = _normal(shape, 8)
    pick = np.random.default_rng(9).random(shape)
    x[pick < 0.1] = np.nan
    x[pick > 0.95] = -np.nan
    return x


_NAN_INPUT = pytest.mark.sanitizer_incompatible("the case's lam is NaN")

PLANES = [
    pytest.param((1, 1), _normal, id="1x1"),
    pytest.param((1, 2), _normal, id="1x2"),
    pytest.param((3, 1), _normal, id="3x1"),
    pytest.param((7, 9), _normal, id="odd-63"),
    pytest.param((8, 8), _normal, id="even-64"),
    pytest.param((33, 65), _normal, id="odd-2145"),
    pytest.param((40, 50), _normal, id="even-2000"),
    pytest.param((31, 37), _ties, id="ties"),
    pytest.param((16, 16), lambda s: np.full(s, 1.5, np.float32),
                 id="all-equal"),
    pytest.param((16, 16), lambda s: np.zeros(s, np.float32),
                 id="all-zero"),
    pytest.param((25, 30), _signed_zeros, id="signed-zeros"),
    pytest.param((24, 24), _infs, id="infs"),
    pytest.param((20, 20), _mostly_inf, id="mostly-inf", marks=_NAN_INPUT),
    pytest.param((30, 21), _nans, id="nans", marks=_NAN_INPUT),
]

MASKS = {
    "unmasked": lambda shape: None,
    "masked": lambda shape: jnp.asarray(
        np.random.default_rng(11).random(shape) < 0.7, jnp.float32),
    "hidden": lambda shape: jnp.zeros(shape, jnp.float32),
}


@pytest.mark.parametrize("masking", sorted(MASKS))
@pytest.mark.parametrize("shape,make", PLANES)
def test_lam_bits_equal_sort(shape, make, masking):
    m_obs = jnp.asarray(make(shape))
    mask = MASKS[masking](shape)
    _assert_same_bits(robust_lam(m_obs, mask=mask), _lam_by_sort(m_obs, mask))


def test_lam_bits_equal_sort_packed_mask():
    m_obs = jnp.asarray(_normal((24, 40), 12))
    mask = jnp.asarray(np.random.default_rng(13).random((24, 40)) < 0.6,
                       jnp.float32)
    got = robust_lam(m_obs, mask=pack_mask(mask))
    _assert_same_bits(got, _lam_by_sort(m_obs, mask))


@pytest.mark.parametrize("masked", [False, True])
def test_lam_bits_equal_sort_bf16(masked):
    m_obs = jnp.asarray(_normal((32, 48), 14), jnp.bfloat16)
    mask = (jnp.asarray(np.random.default_rng(15).random((32, 48)) < 0.8,
                        jnp.float32) if masked else None)
    _assert_same_bits(robust_lam(m_obs, mask=mask), _lam_by_sort(m_obs, mask))


@pytest.mark.parametrize("masked", [False, True])
def test_lam_bits_equal_sort_vmapped(masked):
    """The per-slot calibration of a batched solve: one plane per slot."""
    planes = jnp.asarray(np.stack([_normal((20, 30), s) for s in range(4)]
                                  + [_ties((20, 30))]))
    masks = jnp.asarray(np.random.default_rng(16).random(planes.shape) < 0.7,
                        jnp.float32)
    masks = masks.at[1].set(0.0)  # one slot fully hidden
    if masked:
        got = jax.vmap(lambda m, w: robust_lam(m, mask=w))(planes, masks)
        want = jax.vmap(_lam_by_sort)(planes, masks)
    else:
        got = jax.vmap(robust_lam)(planes)
        want = jax.vmap(_lam_by_sort)(planes)
    _assert_same_bits(got, want)


@pytest.mark.parametrize("masked", [False, True])
def test_lam_bits_equal_sort_subsampled(masked):
    """``sample`` feeds the selection the strided subsample."""
    m, n, sample = 48, 80, 500
    x = jnp.asarray(_normal((m, n), 17))
    w = jnp.asarray(np.random.default_rng(18).random((m, n)) < 0.7,
                    jnp.float32)
    stride = -(-(m * n) // sample)
    while np.gcd(stride, n) > 1:
        stride += 1
    sub_w = w.ravel()[::stride] if masked else None
    want = _lam_by_sort(x.ravel()[::stride], sub_w)
    got = robust_lam(x, mask=w if masked else None, sample=sample)
    _assert_same_bits(got, want)


@pytest.mark.parametrize("make", [_normal, _ties, _signed_zeros, _infs,
                                  _nans],
                         ids=["normal", "ties", "signed-zeros", "infs",
                              "nans"])
def test_select_key_takes_every_rank(make):
    """The k-th key of the selection is the key of ``sort(x)[k]``."""
    x = jnp.asarray(make((9, 7)))
    want = np.asarray(fz._order_key(jnp.sort(x.ravel())))
    select = jax.jit(fz._select_key)
    got = [int(select(x, k)) for k in range(x.size)]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n,ranks", [
    (1 << 26, (33554432, 33554432)),       # f32(2**26 - 1) rounds up
    (int(0.8 * (1 << 26)), (26843546, 26843546)),
    ((1 << 24) + 1, (8388607, 8388608)),   # f32(2**24 + 1) rounds down
    (1 << 25, (16777216, 16777216)),
    (1, (0, 0)),
    (2, (0, 1)),
    (5, (2, 2)),
    (6, (2, 3)),
])
def test_midpoint_ranks_follow_jnp_f32_index(n, ranks):
    """The unmasked ranks are ``jnp.median``'s, whose index is computed in
    f32 (``quantile(method="midpoint")``), not the integer midpoint."""
    assert fz._midpoint_ranks(n) == ranks


@pytest.mark.parametrize("n", [1, 2, 3, 4, 17, 64, 1001, 4096])
def test_midpoint_ranks_match_jnp_median(n):
    """On a shuffled ``arange(n)`` the median's value names its ranks."""
    x = jnp.asarray(np.random.default_rng(n).permutation(n), jnp.float32)
    lo, hi = fz._midpoint_ranks(n)
    assert float(jnp.median(x)) == 0.5 * (lo + hi)


@pytest.mark.parametrize("mask", [None, "dense", "packed"])
def test_robust_lam_program_has_no_sort(mask):
    """The calibration program selects; it sorts nothing."""
    m_obs = jnp.asarray(_normal((64, 96), 19))
    dense = jnp.asarray(np.random.default_rng(20).random((64, 96)) < 0.8,
                        jnp.float32)
    w = {None: None, "dense": dense, "packed": pack_mask(dense)}[mask]
    assert "stablehlo.sort" not in robust_lam.lower(m_obs, mask=w).as_text()
    # the check can see a sort: the formula it replaced lowers to one
    ref = _lam_by_sort.lower(m_obs, None if mask is None else dense)
    assert "stablehlo.sort" in ref.as_text()
