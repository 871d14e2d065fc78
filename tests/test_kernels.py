"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle.

Shape/dtype/block sweeps + hypothesis property tests, per the kernel
contract: SC is bit-exact, analog/approx-mult allclose in f32.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.analog_matmul import analog_matmul
from repro.kernels.approx_mult import approx_mult_matmul
from repro.kernels.log_matmul import log_matmul
from repro.kernels import sc_matmul as SC


# ---------------------------------------------------------------------------
# Analog kernel
# ---------------------------------------------------------------------------

ANALOG_SHAPES = [
    (8, 16, 8, 16),     # M, K, N, array
    (50, 70, 30, 16),
    (128, 128, 128, 128),
    (33, 129, 65, 32),  # non-divisible everything
    (1, 9, 1, 9),       # paper's resnet-tiny array size
]


@pytest.mark.parametrize("M,K,N,A", ANALOG_SHAPES)
@pytest.mark.parametrize("adc_bits", [2, 4, 8])
def test_analog_matches_ref(M, K, N, A, adc_bits):
    k1, k2 = jax.random.split(jax.random.PRNGKey(M * K + N))
    x = jax.random.uniform(k1, (M, K))
    w = jax.random.uniform(k2, (K, N))
    got = analog_matmul(x, w, A, adc_bits, 4.0, interpret=True, block_m=32, block_n=32)
    want = ref.analog_matmul_ref(x, w, A, adc_bits, 4.0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_analog_quantization_bounds():
    """Every per-array partial sum contribution is within ADC range."""
    x = jnp.ones((4, 64)) * 10.0  # drives partial sums far beyond range
    w = jnp.ones((64, 4))
    out = ref.analog_matmul_ref(x, w, 16, 4, 4.0)
    # 4 arrays, each clamped at 4.0 -> total <= 16
    assert float(out.max()) <= 16.0 + 1e-5


@settings(max_examples=25, deadline=None)
@given(
    m=st.integers(1, 20), k=st.integers(1, 40), n=st.integers(1, 20),
    a=st.integers(1, 16), bits=st.integers(1, 6),
)
def test_analog_property(m, k, n, a, bits):
    key = jax.random.PRNGKey(m * 7 + k * 3 + n)
    x = jax.random.uniform(key, (m, k))
    w = jax.random.uniform(jax.random.fold_in(key, 1), (k, n))
    got = analog_matmul(x, w, a, bits, 2.0, interpret=True, block_m=8, block_n=8)
    want = ref.analog_matmul_ref(x, w, a, bits, 2.0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # monotone property: quantized output within additive bound of clamp-sum
    n_arrays = -(-k // a)
    assert float(got.max()) <= 2.0 * n_arrays + 1e-4


# ---------------------------------------------------------------------------
# Approximate-multiplier kernel
# ---------------------------------------------------------------------------

AMULT_SHAPES = [(8, 8, 8), (40, 60, 20), (128, 128, 128), (17, 33, 5)]


@pytest.mark.parametrize("M,K,N", AMULT_SHAPES)
@pytest.mark.parametrize("perforate", [0, 1, 2, 3])
def test_approx_mult_matches_ref(M, K, N, perforate):
    key = jax.random.PRNGKey(M + N)
    x = jnp.round(jax.random.uniform(key, (M, K), minval=-127, maxval=127))
    w = jnp.round(jax.random.uniform(jax.random.fold_in(key, 1), (K, N), minval=-127, maxval=127))
    got = approx_mult_matmul(x, w, 7, perforate, interpret=True, block_m=16, block_n=16, block_k=16)
    want = ref.approx_mult_matmul_ref(x, w, 7, perforate)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-3)


def test_approx_mult_zero_perforation_is_exact():
    key = jax.random.PRNGKey(3)
    x = jnp.round(jax.random.uniform(key, (16, 32), minval=-127, maxval=127))
    w = jnp.round(jax.random.uniform(jax.random.fold_in(key, 1), (32, 8), minval=-127, maxval=127))
    got = ref.approx_mult_matmul_ref(x, w, 7, 0)
    np.testing.assert_allclose(got, x @ w, rtol=0, atol=1e-3)


@settings(max_examples=25, deadline=None)
@given(a=st.integers(-127, 127), b=st.integers(-127, 127), p=st.integers(0, 3))
def test_approx_mul_error_bound(a, b, p):
    """|approx(a,b) - a*b| < 2^(2p); sign preserved; magnitude never grows."""
    drop = 2 * p
    got = float(ref.approx_mul(jnp.float32(a), jnp.float32(b), drop))
    exact = a * b
    assert abs(got - exact) < 2 ** drop
    assert abs(got) <= abs(exact)
    if got != 0:
        assert np.sign(got) == np.sign(exact)


# ---------------------------------------------------------------------------
# Stochastic-computing kernel
# ---------------------------------------------------------------------------

# (K, N) pairs, none a multiple of the 16-wide test tiles but the last;
# rows at decode and prefill sizes, on the tiles (8 pads to the kernel's
# 32-row minimum) and off them (20, 130), so row padding is checked too
SC_KN = [(8, 4), (33, 17), (64, 64)]
SC_ROWS = [8, 20, 128, 130]
SC_BLOCKS = dict(block_m=16, block_n=16, block_k=16)


def _sc_words(M, K, N, bits, scale, seed):
    """Packed streams of uniform probabilities times ``scale``: at 0.05 (as
    under an sc gain of 0.25) the OR over K is far from saturated, so a
    dropped or shifted bit plane shows in the popcounts."""
    key = jax.random.PRNGKey(seed)
    xp = jax.random.uniform(key, (M, K)) * scale
    ux = jax.random.uniform(jax.random.fold_in(key, 2), (K, bits))
    uw = jax.random.uniform(jax.random.fold_in(key, 3), (K, bits))
    xbits = ref.sc_pack_streams(xp, ux)
    wbits = [
        ref.sc_pack_streams(
            jax.random.uniform(jax.random.fold_in(key, 10 + i), (K, N)) * scale,
            uw[:, None, :],
        )
        for i in range(2)
    ]
    return xbits, wbits


@pytest.mark.parametrize("fused", [False, True], ids=["unfused", "fused"])
@pytest.mark.parametrize("scale", [1.0, 0.05], ids=["dense", "sparse"])
@pytest.mark.parametrize("M", SC_ROWS)
@pytest.mark.parametrize("K,N", SC_KN)
@pytest.mark.parametrize("bits", [32, 64])
def test_sc_bit_exact_vs_ref(bits, K, N, M, scale, fused):
    xbits, (wp, wn) = _sc_words(M, K, N, bits, scale, seed=M * N + K)
    want_p = np.asarray(ref.sc_matmul_packed_ref(xbits, wp)) / bits
    if not fused:
        got = SC.sc_matmul_packed(xbits, wp, bits, interpret=True, **SC_BLOCKS)
        np.testing.assert_array_equal(np.asarray(got), want_p)
        return
    want_n = np.asarray(ref.sc_matmul_packed_ref(xbits, wn)) / bits
    pre = jax.random.uniform(jax.random.PRNGKey(K), (M, 1), minval=0.5, maxval=2.0)
    got = SC.sc_matmul_packed_fused(
        xbits, wp, wn, bits, pre, jnp.bfloat16, interpret=True, **SC_BLOCKS
    )
    want = ((want_p - want_n) * np.asarray(pre)).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_sc_converges_with_stream_length():
    """Sampling error shrinks with stream length (toward the correlated
    OR expectation, estimated with a very long stream)."""
    key = jax.random.PRNGKey(0)
    xp = jax.random.uniform(key, (8, 32)) * 0.1
    wp = jax.random.uniform(jax.random.fold_in(key, 1), (32, 8)) * 0.1
    asymptote = jnp.stack([
        ref.sc_matmul_ref(xp, wp, 8192, jax.random.PRNGKey(50 + i), jax.random.PRNGKey(70 + i))
        for i in range(4)
    ]).mean(0)
    errs = []
    for bits in (32, 512):
        draws = jnp.stack([
            ref.sc_matmul_ref(xp, wp, bits, jax.random.PRNGKey(2 + i), jax.random.PRNGKey(3 + i))
            for i in range(4)
        ])
        errs.append(float(jnp.abs(draws.mean(0) - asymptote).mean()))
    assert errs[1] < errs[0], f"SC error should shrink with stream length: {errs}"


def test_sc_shared_generator_bias_exists():
    """The shared activation-side generator makes the OR accumulation
    biased relative to the independent-streams expectation — the
    input-dependent mean error of the paper's Fig. 2 (what Type-1
    injection calibrates)."""
    key = jax.random.PRNGKey(0)
    xp = jax.random.uniform(key, (16, 64)) * 0.5
    wp = jax.random.uniform(jax.random.fold_in(key, 1), (64, 8)) * 0.5
    indep_or = 1.0 - jnp.exp(jnp.log1p(-(xp[:, :, None] * wp[None])).sum(1))
    draws = jnp.stack([
        ref.sc_matmul_ref(xp, wp, 2048, jax.random.PRNGKey(10 + i), jax.random.PRNGKey(90 + i))
        for i in range(6)
    ])
    bias = float((draws.mean(0) - indep_or).mean())
    noise = float(draws.std(0).mean()) / np.sqrt(6)
    assert abs(bias) > 3 * noise, f"expected a real correlation bias: {bias} vs {noise}"


@settings(max_examples=15, deadline=None)
@given(m=st.integers(1, 8), k=st.integers(1, 16), n=st.integers(1, 8))
def test_sc_range_property(m, k, n):
    """SC outputs are valid stream probabilities in [0, 1]."""
    key = jax.random.PRNGKey(m * 31 + k * 7 + n)
    xp = jax.random.uniform(key, (m, k))
    wp = jax.random.uniform(jax.random.fold_in(key, 1), (k, n))
    r = ref.sc_matmul_ref(xp, wp, 32, jax.random.PRNGKey(2), jax.random.PRNGKey(3))
    assert float(r.min()) >= 0.0 and float(r.max()) <= 1.0


def test_sc_pack_popcount_roundtrip():
    """Packing preserves the bit count exactly."""
    key = jax.random.PRNGKey(5)
    p = jax.random.uniform(key, (6, 10))
    u = jax.random.uniform(jax.random.fold_in(key, 1), (10, 64))
    packed = ref.sc_pack_streams(p, u)
    raw_bits = (p[..., None] > u).sum(-1)
    counts = jax.lax.population_count(packed).sum(-1)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(raw_bits))


# ---------------------------------------------------------------------------
# Mitchell log-multiplier kernel
# ---------------------------------------------------------------------------

LOG_SHAPES = [(8, 8, 8), (40, 60, 20), (128, 128, 128), (17, 33, 5)]


@pytest.mark.parametrize("M,K,N", LOG_SHAPES)
def test_log_matmul_matches_ref(M, K, N):
    key = jax.random.PRNGKey(M + 2 * N)
    x = jnp.round(jax.random.uniform(key, (M, K), minval=-127, maxval=127))
    w = jnp.round(jax.random.uniform(jax.random.fold_in(key, 1), (K, N), minval=-127, maxval=127))
    got = log_matmul(x, w, interpret=True, block_m=16, block_n=16, block_k=16)
    want = ref.log_matmul_ref(x, w)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)


@settings(max_examples=25, deadline=None)
@given(a=st.integers(-255, 255), b=st.integers(-255, 255))
def test_mitchell_mul_error_bound(a, b):
    """Mitchell underestimates by at most ~11.1% and is exact when both
    mantissa residues are zero (power-of-two operands) or either is 0."""
    got = float(ref.mitchell_mul(jnp.float32(a), jnp.float32(b)))
    exact = float(a * b)
    slack = abs(exact) * 1e-5 + 1e-6  # float32 log2/exp2 rounding
    assert abs(got) <= abs(exact) + slack  # never overestimates magnitude
    assert abs(got - exact) <= abs(exact) / 9.0 + slack  # 1/9 max rel. error
    if got != 0:
        assert np.sign(got) == np.sign(exact)


def test_mitchell_exact_on_powers_of_two():
    """Zero mantissa residues -> no approximation error (up to float32
    log2/exp2 rounding, ~1e-7 relative)."""
    for a in (1, 2, 4, 64, -32):
        for b in (1, 8, 128, -2):
            got = float(ref.mitchell_mul(jnp.float32(a), jnp.float32(b)))
            np.testing.assert_allclose(got, a * b, rtol=2e-6)
