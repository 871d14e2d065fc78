"""Every Pallas kernel of the main path compiles for a TPU v5e.

No chip is attached: the TPU compiler is given a described ``v5e:2x2``
topology and compiles each kernel for one of its chips, at the widths of
``qwen2.5-3b`` (d_model 2048, d_ff 11008, vocab 151936, 2 KV heads of
128 with 8 query heads each).  Interpret-mode tests cannot catch what
this does: block shapes the TPU tiling refuses, dynamic lane indexing,
and tiles that overflow VMEM.

The topology is described inside a fixture — never at import time — so
that under several pytest-xdist workers only the worker running this
file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import analog_matmul as A
from repro.kernels import approx_mult as AM
from repro.kernels import flash_decode as F
from repro.kernels import log_matmul as LM
from repro.kernels import sc_matmul as SC

D, FF, VOCAB = 2048, 11008, 151936
DECODE_M, PREFILL_M = 8, 256

# (M, K, N) of the projections the serving and training paths send:
# q/o (D -> D), the MLP up/gate and down, and the LM head
SHAPES = [
    (DECODE_M, D, D),
    (DECODE_M, D, FF),
    (DECODE_M, FF, D),
    (DECODE_M, D, VOCAB),
    (PREFILL_M, D, FF),
    (PREFILL_M, D, VOCAB),
]
IDS = [f"M{m}-K{k}-N{n}" for m, k, n in SHAPES]

# the training cell's rows (batch 2 x 256) at its projections and head
TRAIN_M = 512
SC_SHAPES = SHAPES + [
    (TRAIN_M, D, D),
    (TRAIN_M, D, FF),
    (TRAIN_M, FF, D),
    (TRAIN_M, D, VOCAB),
]
SC_IDS = [f"M{m}-K{k}-N{n}" for m, k, n in SC_SHAPES]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [
        jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("M,K,N", SC_SHAPES, ids=SC_IDS)
def test_sc_compiles(one_chip, M, K, N):
    # split-unipolar: both signed halves are concatenated along K
    xb = ((M, 2 * K, 1), jnp.uint32)
    wb = ((2 * K, N, 1), jnp.uint32)
    plain = _compile(lambda x, w: SC.sc_matmul_packed(x, w, 32), one_chip, xb, wb)
    fused = _compile(
        lambda x, wp, wn: SC.sc_matmul_packed_fused(
            x, wp, wn, 32, jnp.float32(0.5), jnp.bfloat16
        ),
        one_chip, xb, wb, wb,
    )
    # the kernel's name is in the compiled program at every row count
    assert "sc_matmul_mxu" in plain.as_text()
    assert "sc_matmul_fused_mxu" in fused.as_text()


@pytest.mark.parametrize("words", [2, 4])
def test_sc_mxu_compiles_long_streams(one_chip, words):
    # 64- and 128-bit streams: word planes are a grid axis of the
    # kernel, so its VMEM does not grow with the stream length
    xb = ((TRAIN_M, 2 * D, words), jnp.uint32)
    wb = ((2 * D, D, words), jnp.uint32)
    fused = _compile(
        lambda x, wp, wn: SC.sc_matmul_packed_fused(
            x, wp, wn, 32 * words, jnp.float32(0.5), jnp.bfloat16
        ),
        one_chip, xb, wb, wb,
    )
    assert "sc_matmul_fused_mxu" in fused.as_text()


@pytest.mark.parametrize("M,K,N", SHAPES, ids=IDS)
def test_analog_compiles(one_chip, M, K, N):
    x = ((M, 2 * K), jnp.float32)
    w = ((2 * K, N), jnp.float32)
    _compile(lambda x, w: A.analog_matmul(x, w, 128, 4, 4.0), one_chip, x, w)
    _compile(
        lambda x, wp, wn: A.analog_matmul_fused(
            x, wp, wn, 128, 4, 4.0, jnp.float32(0.5), jnp.bfloat16
        ),
        one_chip, x, w, w,
    )


@pytest.mark.parametrize("M,K,N", SHAPES, ids=IDS)
@pytest.mark.parametrize("backend", ["approx_mult", "log_mult"])
def test_multiplier_kernels_compile(one_chip, backend, M, K, N):
    if backend == "approx_mult":
        fn = lambda x, w, **kw: AM.approx_mult_matmul(x, w, 7, 2, **kw)
    else:
        fn = LM.log_matmul
    x = ((M, K), jnp.float32)
    w = ((K, N), jnp.float32)
    _compile(fn, one_chip, x, w)
    _compile(
        lambda x, w, pre: fn(x, w, prescale=pre, out_dtype=jnp.bfloat16),
        one_chip, x, w, ((M, 1), jnp.float32),
    )


@pytest.mark.parametrize("B", [1, DECODE_M])
def test_flash_decode_compiles(one_chip, B):
    KV, G, dh, S = 2, 8, 128, 512
    _compile(
        F.flash_decode, one_chip,
        ((B, KV, G, dh), jnp.bfloat16),
        ((B, S, KV, dh), jnp.bfloat16),
        ((B, S, KV, dh), jnp.bfloat16),
        ((B,), jnp.int32),
    )
