"""Fused MODEL-mode hot path vs the composed oracle.

The fused path (one kernel for the matmul, rescale and cast, then chip
perturbation + calibration correction) must be BIT-identical to the
composed sequence
``quantize -> matmul -> apply_chip -> predict_mean subtract`` — the
composed path is the repo's accuracy oracle, so any drift in the fused
path would silently change what "the hardware computes".  Exactness is
asserted for every backend x {no chip, sampled chip} x {correction
on/off}, in both kernel modes (Pallas interpret and the jnp reference).

Flash decode attention reassociates the softmax (online running max /
normalizer), so its contract is allclose, not bitwise — checked against
the einsum decode path under ragged per-row positions (right-padded
slots) with fixed seeds plus a hypothesis property on the raw kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_smoke_config
from repro.configs.base import ApproxConfig, Backend, TrainMode
from repro.core.approx_linear import ApproxCtx, dense, init_calibration
from repro.hw import variation
from repro.kernels import flash_decode as F
from repro.models import build_model
from repro.models import layers as L

BACKENDS = ("sc", "analog", "approx_mult", "log_mult")


def _operands(seed=0, M=4, K=48, N=40):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = (jax.random.normal(kx, (M, K), jnp.float32) * 0.5).astype(jnp.bfloat16)
    w = (jax.random.normal(kw, (K, N), jnp.float32) * 0.3).astype(jnp.bfloat16)
    return x, w


def _calib_stats(cfg):
    calib = init_calibration(["site"], cfg)
    P = calib["site"]["mean"].shape[0]
    return {
        "mean": jnp.linspace(0.01, 0.03, P).astype(jnp.float32),
        "var": calib["site"]["var"],
        "scale": jnp.float32(1.7),
    }


@pytest.mark.parametrize("kernels", ["ref", "pallas"])
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("use_chip", [False, True])
@pytest.mark.parametrize("correct", [False, True])
def test_fused_dense_bitexact(monkeypatch, kernels, backend, use_chip, correct):
    monkeypatch.setenv("REPRO_KERNELS", kernels)
    cfg = ApproxConfig(backend=Backend(backend), mode=TrainMode.MODEL)
    chip = variation.sample_profile(jax.random.PRNGKey(7)) if use_chip else None
    calib = {"site": _calib_stats(cfg)} if correct else None
    x, w = _operands()

    kw = dict(cfg=cfg, rng=jax.random.PRNGKey(3), chip=chip,
              correct=correct, calib=calib)
    composed = dense(x, w, site="site", ctx=ApproxCtx(fused=False, **kw))
    fused = dense(x, w, site="site", ctx=ApproxCtx(fused=True, **kw))
    np.testing.assert_array_equal(
        np.asarray(composed, np.float32), np.asarray(fused, np.float32)
    )


def test_fused_falls_back_without_fused_spec(monkeypatch):
    """A ctx with fused=True on a backend/mode with no fused kernel (here:
    exact) must route through the unchanged path, byte-identically."""
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    x, w = _operands()
    kw = dict(cfg=ApproxConfig(), rng=jax.random.PRNGKey(3))
    a = dense(x, w, site="site", ctx=ApproxCtx(fused=False, **kw))
    b = dense(x, w, site="site", ctx=ApproxCtx(fused=True, **kw))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_gradients_match_composed_proxy(monkeypatch):
    """The fused custom_vjp must differentiate through the same proxy +
    epilogue as the composed path (loss gradients steer training)."""
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    cfg = ApproxConfig(backend=Backend.LOG_MULT, mode=TrainMode.MODEL)
    chip = variation.sample_profile(jax.random.PRNGKey(7))
    x, w = _operands()
    kw = dict(cfg=cfg, rng=jax.random.PRNGKey(3), chip=chip)

    def loss(fused):
        def f(w_):
            y = dense(x, w_, site="site", ctx=ApproxCtx(fused=fused, **kw))
            return jnp.sum(y.astype(jnp.float32) ** 2)
        return jax.grad(f)(w)

    gc, gf = loss(False), loss(True)
    np.testing.assert_allclose(
        np.asarray(gc, np.float32), np.asarray(gf, np.float32),
        rtol=1e-2, atol=1e-2,
    )


# ---------------------------------------------------------------------------
# Flash decode attention
# ---------------------------------------------------------------------------


def _attn_inputs(seed, B, S):
    cfg = get_smoke_config("qwen2.5-3b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    p0 = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    cache = model.init_cache(B, S)
    ck = jax.tree_util.tree_map(lambda a: a[0], cache["k"])
    cv = jax.tree_util.tree_map(lambda a: a[0], cache["v"])
    x = jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(seed), 1), (B, 1, cfg.d_model)
    ).astype(cfg.compute_dtype)
    return cfg, p0["attn"], x, ck, cv


@pytest.mark.parametrize("kernels", ["ref", "pallas"])
@pytest.mark.parametrize("seed,B,S", [(0, 1, 16), (1, 4, 48), (2, 3, 33)])
def test_flash_decode_matches_einsum_path(monkeypatch, kernels, seed, B, S):
    monkeypatch.setenv("REPRO_KERNELS", kernels)
    cfg, attn_p, x, ck, cv = _attn_inputs(seed, B, S)
    ctx = ApproxCtx(cfg=ApproxConfig(), rng=jax.random.PRNGKey(0))
    # ragged right-padding: every slot row sits at a different offset,
    # including a freshly-admitted row at position 0
    pos = jnp.asarray(
        np.random.default_rng(seed).integers(0, S, size=B), jnp.int32
    ).at[0].set(0)
    # warm the caches so masked history is non-zero garbage the mask
    # must actually exclude
    ck = jax.random.normal(jax.random.PRNGKey(5), ck.shape).astype(ck.dtype)
    cv = jax.random.normal(jax.random.PRNGKey(6), cv.shape).astype(cv.dtype)

    out_e, ck_e, cv_e = L.decode_attention(
        x, attn_p, cfg, ctx, ck, cv, pos, flash=False
    )
    out_f, ck_f, cv_f = L.decode_attention(
        x, attn_p, cfg, ctx, ck, cv, pos, flash=True
    )
    np.testing.assert_array_equal(np.asarray(ck_e), np.asarray(ck_f))
    np.testing.assert_array_equal(np.asarray(cv_e), np.asarray(cv_f))
    np.testing.assert_allclose(
        np.asarray(out_e, np.float32), np.asarray(out_f, np.float32),
        rtol=2e-2, atol=2e-2,
    )


@settings(max_examples=15, deadline=None)
@given(b=st.integers(1, 3), s=st.integers(2, 40), kv=st.integers(1, 2),
       g=st.integers(1, 3), dh=st.integers(4, 16))
def test_flash_decode_kernel_property(b, s, kv, g, dh):
    key = jax.random.PRNGKey(b * 131 + s * 7 + kv * 3 + g + dh)
    kq, kk, kv_, kp = jax.random.split(key, 4)
    q = jax.random.normal(kq, (b, kv, g, dh), jnp.float32)
    ck = jax.random.normal(kk, (b, s, kv, dh), jnp.float32)
    cv = jax.random.normal(kv_, (b, s, kv, dh), jnp.float32)
    pos = jax.random.randint(kp, (b,), 0, s)
    got = F.flash_decode(q, ck, cv, pos, interpret=True)
    want = F.flash_decode_ref(q, ck, cv, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
