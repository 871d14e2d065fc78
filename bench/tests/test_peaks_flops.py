"""The peaks table refuses unknown devices, and the counts of logical
work are functions of shapes alone."""
import pytest

import flops
import tiny
from peaks import peaks_for


def test_v5e_peaks():
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_unknown_device_is_an_error(kind):
    with pytest.raises(KeyError):
        peaks_for(kind)


def _cfg(name):
    return tiny.load(tiny.BENCH / "configs" / f"{name}.json")


def test_matmul_params_qwen_tied():
    cfg = dict(_cfg("qwen2.5-3b"), num_hidden_layers=2)
    per_layer = 2048 * 2048 * 2 + 2048 * 256 * 2 + 3 * 2048 * 11008
    assert flops.matmul_params(cfg) == 2 * per_layer + 2048 * 151936


def test_roofline_bounds():
    peaks = peaks_for("TPU v5 lite")
    # a large square product is bound by operations, a skinny one by bytes
    t_big = flops.matmul_roofline_s(4096, 4096, 4096, peaks)
    assert t_big == pytest.approx(2 * 4096 ** 3 / 197e12)
    t_row = flops.matmul_roofline_s(1, 4096, 4096, peaks)
    assert t_row == pytest.approx(2 * (4096 + 4096 * 4096 + 4096) / 819e9)


def test_flash_decode_bytes():
    peaks = peaks_for("TPU v5 lite")
    cfg = dict(_cfg("yi-6b"), num_hidden_layers=1)
    t = flops.flash_decode_roofline_s(cfg, [99], peaks)
    kv = 100 * 2 * 4 * 128 * 2
    qo = 2 * 32 * 128 * 2
    assert t == pytest.approx((kv + qo) / 819e9)


def test_train_flops_per_token():
    cfg = dict(_cfg("qwen2.5-3b"), num_hidden_layers=1)
    seq = 256
    att = 4.0 * 16 * 128 * (seq + 1) / 2
    assert flops.train_flops_per_token(cfg, seq) == pytest.approx(
        3 * (2 * flops.matmul_params(cfg) + att))
