"""One-compile heterogeneous dispatch (repro.core.switch).

The contract: switch-dispatched projections (backend as a runtime int32
index, ``lax.switch`` / ``lax.select_n``) are BITWISE identical to the
static trace-time dispatch — the oracle — for every backend, composed
and fused, in both kernel modes; the site-map resolution (fnmatch over
``site_backends``) runs exactly once per distinct config; and the
per-layer index pytrees lay out like the scan-stacked weights.  A
hypothesis property drives random site maps through both paths at the
model level.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_smoke_config
from repro.configs.base import (
    AnalogParams,
    ApproxConfig,
    Backend,
    SCParams,
    TrainMode,
)
from repro.core import registry
from repro.core import switch as switch_lib
from repro.core.approx_linear import ApproxCtx, dense
from repro.models import build_model
from repro.models.transformer import ALL_SITES

BACKENDS = ("sc", "analog", "approx_mult", "log_mult")


# ---------------------------------------------------------------------------
# Table / site-order invariants
# ---------------------------------------------------------------------------


def test_site_order_matches_model_sites():
    # core must not import models, so SITE_ORDER is defined twice; the
    # index arrays are only meaningful if the two orders never diverge
    assert switch_lib.SITE_ORDER == ALL_SITES
    for i, site in enumerate(switch_lib.SITE_ORDER):
        assert switch_lib.site_pos(site) == i
    assert switch_lib.site_pos("not_a_site") is None


def test_switch_table_exact_first_sorted_stable():
    t = switch_lib.table()
    assert t[0] == Backend.EXACT.value
    assert tuple(sorted(t[1:])) == t[1:]
    assert set(t[1:]) == set(registry.approx_names())
    for name in t:
        assert t[switch_lib.backend_index(name)] == name
    assert switch_lib.backend_index(Backend.LOG_MULT) == t.index("log_mult")
    with pytest.raises(KeyError, match="not in the switch table"):
        switch_lib.backend_index("no_such_hw")


def test_subtable_restricted_dispatch_matches_full():
    """A closed backend world (ApproxConfig.switch_backends) builds fewer
    branches but must stay bitwise-equal to the full-table graph for any
    backend inside the world."""
    sub = switch_lib.subtable(("log_mult", "analog"))
    assert sub == ("exact", "analog", "log_mult")
    assert switch_lib.subtable(sub) == sub  # idempotent
    assert switch_lib.subtable(("exact",)) == ("exact",)
    with pytest.raises(KeyError, match="not in the switch table"):
        switch_lib.subtable(("no_such_hw",))
    assert switch_lib.backend_index("log_mult", sub) == 2

    cfg = ApproxConfig(
        backend=Backend.EXACT, mode=TrainMode.MODEL,
        site_backends=(("attn_q", "analog"), ("mlp_gate", "log_mult")),
    )
    sub_idx = switch_lib.site_indices(cfg, table=sub)
    full_idx = switch_lib.site_indices(cfg)
    pos = switch_lib.site_pos
    assert sub_idx[pos("attn_q")] == 1 and sub_idx[pos("mlp_gate")] == 2
    x, w = _operands()
    for site in ("attn_q", "mlp_gate"):
        _, full = _dense_pair(cfg, False, jnp.asarray(full_idx), x, w,
                              site=site)
        restricted = dataclasses.replace(cfg, switch_backends=sub)
        _, small = _dense_pair(restricted, False, jnp.asarray(sub_idx), x, w,
                               site=site)
        np.testing.assert_array_equal(full, small)


def test_site_indices_resolve_map_and_fold_skips():
    t = switch_lib.table()
    pos = switch_lib.site_pos
    cfg = ApproxConfig(
        mode=TrainMode.MODEL,
        site_backends=(("attn_*", "sc"), ("mlp_gate", "log_mult")),
    )
    idx = switch_lib.site_indices(cfg)
    assert idx.dtype == np.int32 and idx.shape == (len(switch_lib.SITE_ORDER),)
    assert idx[pos("attn_q")] == t.index("sc")
    assert idx[pos("attn_o")] == t.index("sc")
    assert idx[pos("mlp_gate")] == t.index("log_mult")
    assert idx[pos("mlp_down")] == 0  # unmatched -> default (exact)
    # skip flags fold to exact even when the map matches the site
    skipped = dataclasses.replace(
        cfg, site_backends=(("*", "sc"),), skip_lm_head=True, skip_router=True
    )
    idx2 = switch_lib.site_indices(skipped)
    assert idx2[pos("lm_head")] == 0 and idx2[pos("moe_router")] == 0
    assert idx2[pos("attn_q")] == t.index("sc")


def test_site_resolution_runs_once_per_config():
    # satellite: the fnmatch pass is hoisted into ONE cached resolution
    # per distinct config (knob values below are deliberately odd so this
    # test never hits another test's cache entries)
    cfg = ApproxConfig(
        site_backends=(("attn_[qk]", "analog"),), sc=SCParams(bits=24)
    )
    before = switch_lib.resolution_count()
    first = switch_lib.site_indices(cfg)
    for _ in range(5):
        np.testing.assert_array_equal(switch_lib.site_indices(cfg), first)
    assert switch_lib.resolution_count() == before + 1
    # an equal config built fresh hits the same cache entry
    clone = ApproxConfig(
        site_backends=(("attn_[qk]", "analog"),), sc=SCParams(bits=24)
    )
    switch_lib.site_indices(clone)
    assert switch_lib.resolution_count() == before + 1
    # a distinct map is one more resolution, not one per call
    other = dataclasses.replace(cfg, site_backends=(("mlp_[ud]*", "sc"),))
    switch_lib.site_indices(other)
    switch_lib.site_indices(other)
    assert switch_lib.resolution_count() == before + 2


def test_model_indices_layouts_and_per_layer_maps():
    S = len(switch_lib.SITE_ORDER)
    t = switch_lib.table()
    approx = ApproxConfig(site_backends=(("mlp_*", "log_mult"),))
    cfg = get_smoke_config("qwen2.5-3b")
    mi = switch_lib.model_indices(cfg, approx)
    assert mi["head"].shape == (S,)
    assert mi["layers"].shape == (cfg.n_layers, S)
    np.testing.assert_array_equal(
        mi["layers"], np.tile(mi["head"], (cfg.n_layers, 1))
    )
    # per-layer override: only layer 1 approximates attention
    lm = [None] * cfg.n_layers
    lm[1] = (("attn_*", "sc"),)
    mi2 = switch_lib.model_indices(cfg, approx, layer_maps=lm)
    q = switch_lib.site_pos("attn_q")
    assert mi2["layers"][1][q] == t.index("sc")
    assert mi2["layers"][0][q] == 0
    with pytest.raises(ValueError, match="one entry per layer"):
        switch_lib.model_indices(cfg, approx, layer_maps=[None])
    # hybrid: grouped mamba layers + per-group shared block (+ tail)
    hcfg = get_smoke_config("zamba2-1.2b")
    hmi = switch_lib.model_indices(hcfg, approx)
    k = hcfg.shared_attn_every
    G, tail = hcfg.n_layers // k, hcfg.n_layers % k
    assert hmi["layers"].shape == (G, k, S)
    assert hmi["shared"].shape == (G, S)
    assert ("tail" in hmi) == bool(tail)
    if tail:
        assert hmi["tail"].shape == (tail, S)

def test_mask_site_indices_demotes_to_exact():
    # satellite: per-chip fault containment — the fabric router demotes
    # stuck-at-faulted sites to exact (index 0) on a sick replica via a
    # pure index-array rewrite, no recompile
    t = switch_lib.table()
    cfg = ApproxConfig(
        mode=TrainMode.MODEL, site_backends=(("*", "log_mult"),)
    )
    idx = switch_lib.site_indices(cfg)
    masked = switch_lib.mask_site_indices(idx, ("mlp_*",))
    for i, site in enumerate(switch_lib.SITE_ORDER):
        if site.startswith("mlp_"):
            assert masked[i] == 0, site
        else:
            assert masked[i] == idx[i], site
    # the input is never mutated, empty mask is identity, and a matrix
    # of per-slot rows masks every row
    np.testing.assert_array_equal(idx, switch_lib.site_indices(cfg))
    np.testing.assert_array_equal(
        switch_lib.mask_site_indices(idx, ()), idx
    )
    rows = np.stack([idx, idx])
    both = switch_lib.mask_site_indices(rows, ("attn_[qk]",))
    q, k = switch_lib.site_pos("attn_q"), switch_lib.site_pos("attn_k")
    assert both[0][q] == 0 and both[1][k] == 0
    assert both[0][switch_lib.site_pos("attn_v")] == t.index("log_mult")
    with pytest.raises(ValueError, match="SITE_ORDER"):
        switch_lib.mask_site_indices(idx[:3], ("mlp_*",))


def test_model_indices_mask_sites_override():
    # model_indices(mask_sites=...) masks every layout leaf — the
    # per-chip override the router installs for a whole sick replica
    approx = ApproxConfig(site_backends=(("*", "log_mult"),))
    cfg = get_smoke_config("qwen2.5-3b")
    plain = switch_lib.model_indices(cfg, approx)
    masked = switch_lib.model_indices(cfg, approx, mask_sites=("mlp_*",))
    g = switch_lib.site_pos("mlp_gate")
    q = switch_lib.site_pos("attn_q")
    assert masked["head"][g] == 0 and masked["head"][q] == plain["head"][q]
    assert (masked["layers"][:, g] == 0).all()
    np.testing.assert_array_equal(masked["layers"][:, q], plain["layers"][:, q])


def test_engine_demote_sites_zero_retrace():
    # swapping the demotion mask on a serving switch engine rewrites the
    # live slot index rows and recompiles nothing
    from repro.models import build_model as _bm
    from repro.runtime.engine import Engine, Request

    cfg = get_smoke_config("qwen2.5-3b")
    model = _bm(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = Engine(model, params, n_slots=2, max_seq=32, switch=True)
    prompt = tuple(
        int(x) for x in np.random.default_rng(0).integers(0, cfg.vocab_size, 5)
    )
    engine.run([
        Request(rid=0, prompt=prompt, max_new_tokens=12, backend="log_mult"),
        Request(rid=1, prompt=prompt, max_new_tokens=12, backend="log_mult"),
    ])
    traces = engine.fns.stats()["traces"]
    # mid-flight demotion: admit, step once, demote, keep decoding
    engine.submit(Request(rid=2, prompt=prompt, max_new_tokens=8,
                          backend="log_mult"))
    engine.step()
    lane = next(l for l in engine.lanes.values() if l.switch)
    assert lane.site_idx.max() > 0
    assert engine.demote_sites(("*",)) >= 1
    assert lane.site_idx.max() == 0  # every live row now all-exact
    while any(l.n_active() for l in engine.lanes.values()):
        engine.step()
    assert engine.fns.stats()["traces"] == traces, engine.fns.stats()
    assert engine.fns.stats()["retraces"] == 0
    # new admissions under the installed mask also decode exact
    engine.run([Request(rid=3, prompt=prompt, max_new_tokens=4,
                        backend="log_mult")])
    assert engine.metrics()["site_mask"] == ["*"]


# ---------------------------------------------------------------------------
# dense(): switch == static, bitwise, per backend x fused x kernel mode
#
# Both sides run under jax.jit: the contract is between COMPILED graphs
# (training/eval/serving steps are all jitted) — eager op-by-op execution
# rounds reductions differently from a compiled lax.switch branch, which
# is an execution-mode artifact, not a dispatch discrepancy.
# ---------------------------------------------------------------------------


def _operands(seed=0, M=4, K=48, N=40):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = (jax.random.normal(kx, (M, K), jnp.float32) * 0.5).astype(jnp.bfloat16)
    w = (jax.random.normal(kw, (K, N), jnp.float32) * 0.3).astype(jnp.bfloat16)
    return x, w


def _dense_pair(cfg, fused, site_idx, x, w, site="attn_q"):
    """(static, switch) outputs of one jitted dense() per dispatch mode."""
    rng = jax.random.PRNGKey(3)

    @jax.jit
    def static_fn(x, w):
        return dense(x, w, site=site, ctx=ApproxCtx(cfg=cfg, rng=rng, fused=fused))

    @jax.jit
    def switch_fn(x, w, idx):
        ctx = ApproxCtx(cfg=switch_lib.canonical(cfg), rng=rng, fused=fused,
                        site_idx=idx)
        return dense(x, w, site=site, ctx=ctx)

    return (
        np.asarray(static_fn(x, w), np.float32),
        np.asarray(switch_fn(x, w, site_idx), np.float32),
    )


@pytest.mark.parametrize("kernels", ["ref", "pallas"])
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("fused", [False, True])
def test_switch_dense_bitexact_vs_static(monkeypatch, kernels, backend, fused):
    monkeypatch.setenv("REPRO_KERNELS", kernels)
    cfg = ApproxConfig(backend=Backend(backend), mode=TrainMode.MODEL)
    x, w = _operands()
    idx = jnp.asarray(switch_lib.site_indices(cfg))
    static, switched = _dense_pair(cfg, fused, idx, x, w)
    np.testing.assert_array_equal(static, switched)


def test_switch_dense_per_row_select(monkeypatch):
    """The [rows, n_sites] flavor (merged serving lanes): emulated rows
    must equal the full-batch static emulation bitwise (log_mult scales
    per row, so row results are batch-invariant) and exact rows the
    plain matmul."""
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    cfg = ApproxConfig(backend=Backend.LOG_MULT, mode=TrainMode.MODEL)
    x, w = _operands(M=4)
    idx = np.zeros((4, len(switch_lib.SITE_ORDER)), np.int32)
    idx[:2] = switch_lib.backend_index("log_mult")
    static, out = _dense_pair(cfg, False, jnp.asarray(idx), x, w)
    np.testing.assert_array_equal(out[:2], static[:2])
    np.testing.assert_array_equal(
        out[2:], np.asarray(jax.jit(jnp.matmul)(x, w)[2:], np.float32)
    )


def test_dense_static_path_untouched_without_site_idx():
    # site_idx=None keeps the pre-switch behavior byte-for-byte (the
    # static path is the oracle, and calibration always routes there)
    cfg = ApproxConfig(backend=Backend.LOG_MULT, mode=TrainMode.MODEL)
    x, w = _operands()
    a = dense(x, w, site="attn_q", ctx=ApproxCtx(cfg=cfg, rng=jax.random.PRNGKey(3)))
    b = dense(x, w, site="attn_q", ctx=ApproxCtx(cfg=cfg, rng=jax.random.PRNGKey(3)))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # unknown sites (not in SITE_ORDER) fall back to static dispatch even
    # when an index array is present
    idx = jnp.asarray(switch_lib.site_indices(cfg))
    c = dense(
        x, w, site="some_custom_site",
        ctx=ApproxCtx(cfg=cfg, rng=jax.random.PRNGKey(3), site_idx=idx),
    )
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


# ---------------------------------------------------------------------------
# Model level: full forward, heterogeneous + per-layer maps (slow)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def micro_model():
    cfg = dataclasses.replace(
        get_smoke_config("paper-tinyconv"),
        n_layers=2, d_model=32, d_ff=64, n_heads=2, n_kv_heads=2,
        vocab_size=64,
    )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)
    return cfg, model, params, {"tokens": toks}


def _logits(model, params, batch, approx, backend_idx=None):
    # jitted: the dispatch contract is between compiled graphs (see the
    # dense-level section header)
    def f(params, batch, backend_idx):
        out = model.apply(
            params, batch, approx=approx, rng=jax.random.PRNGKey(7),
            remat="none", backend_idx=backend_idx,
        )
        return out.logits

    return np.asarray(jax.jit(f)(params, batch, backend_idx), np.float32)


_BASE = ApproxConfig(
    mode=TrainMode.MODEL,
    analog=AnalogParams(array_size=32),
    sc=SCParams(bits=32),
)


def _ulp_close(got, want, **kw):
    """Model-level contract: float32-ulp agreement, not bitwise.

    Each *projection* is bitwise-identical between the two paths (same
    jaxpr — asserted at the dense level above), but in a whole-model
    graph XLA fuses the statically inlined emulation into surrounding
    ops while a ``lax.switch`` branch is a call boundary it cannot fuse
    across, so reductions round differently at the ~1e-7 level.

    If this ever trips on a new platform with a *localized*
    quant-step-sized diff, that's an ulp shift crossing a per-tensor
    quantizer boundary (analog's ADC grid is set by the activation
    max — see the 1e-3 loss bounds in test_search/bench_dispatch), not
    a dispatch bug."""
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6, **kw)


@pytest.mark.slow
def test_model_switch_matches_static(micro_model):
    cfg, model, params, batch = micro_model
    approx = dataclasses.replace(
        _BASE,
        site_backends=(
            ("attn_*", "log_mult"), ("mlp_*", "analog"), ("lm_head", "sc")
        ),
    )
    want = _logits(model, params, batch, approx)
    got = _logits(
        model, params, batch, switch_lib.canonical(approx),
        backend_idx=switch_lib.site_indices(approx),
    )
    _ulp_close(got, want)


@pytest.mark.slow
def test_model_per_layer_maps(micro_model):
    cfg, model, params, batch = micro_model
    approx = dataclasses.replace(
        _BASE, site_backends=(("attn_*", "log_mult"), ("mlp_*", "analog"))
    )
    ccfg = switch_lib.canonical(approx)
    # all-layers-identical pytree == the flat uniform index array
    uniform = _logits(
        model, params, batch, ccfg,
        backend_idx=switch_lib.site_indices(approx),
    )
    tiled = _logits(
        model, params, batch, ccfg,
        backend_idx=switch_lib.model_indices(cfg, approx),
    )
    _ulp_close(tiled, uniform)
    # genuinely per-layer: layer 0 exact, layer 1 approximated — runs,
    # finite, and distinct from the uniform map
    mi = switch_lib.model_indices(cfg, approx, layer_maps=[(), None])
    assert not mi["layers"][0].any() and mi["layers"][1].any()
    per_layer = _logits(model, params, batch, ccfg, backend_idx=mi)
    assert np.isfinite(per_layer).all()
    assert not np.array_equal(per_layer, uniform)


_PROP_SITES = ("attn_q", "attn_o", "mlp_gate", "mlp_down", "lm_head")


@pytest.mark.slow
@settings(max_examples=6, deadline=None)
@given(code=st.integers(0, 5 ** len(_PROP_SITES) - 1))
def test_switch_matches_static_random_maps(micro_model, code):
    """Property: for ANY site map, switch dispatch matches static
    dispatch to float32 ulp (see ``_ulp_close``) at the model level.
    The map is derived from one integer
    draw (base-len(table) digits, one per site), so one integer strategy
    covers the full map space."""
    cfg, model, params, batch = micro_model
    t = switch_lib.table()
    digits, c = [], code
    for _ in _PROP_SITES:
        digits.append(c % len(t))
        c //= len(t)
    site_backends = tuple(
        (site, t[d]) for site, d in zip(_PROP_SITES, digits) if d
    )
    approx = dataclasses.replace(_BASE, site_backends=site_backends)
    want = _logits(model, params, batch, approx)
    got = _logits(
        model, params, batch, switch_lib.canonical(approx),
        backend_idx=switch_lib.site_indices(approx),
    )
    _ulp_close(got, want, err_msg=f"map={site_backends}")
