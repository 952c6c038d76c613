"""Roofline report math + batching reg-mode resolution + report rendering."""

import pytest

from repro.configs import SHAPES, get_config
from repro.core import RegMode, resolve_reg_mode
from repro.roofline.analysis import (RooflineReport, model_flops_for,
                                     peaks_for)


def _rep(**kw):
    base = dict(arch="a", shape="s", mesh="single", chips=256,
                hlo_flops=197e12, hlo_bytes=819e9, coll_bytes={"all-reduce": 50e9},
                model_flops=197e12 * 256, device_kind="TPU v5 lite")
    base.update(kw)
    return RooflineReport(**base)


def test_roofline_terms_unit():
    r = _rep()
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(1.0)
    assert r.collective_s == pytest.approx(1.0)
    assert r.bound_s == pytest.approx(1.0)
    assert r.roofline_fraction == pytest.approx(1.0)
    assert r.useful_flops_ratio == pytest.approx(1.0)


def test_peaks_unknown_device_kind_raises():
    assert peaks_for("TPU v5 lite").hbm_bw == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks_for("cpu")
    with pytest.raises(ValueError):
        _rep(device_kind="cpu").compute_s


def test_dominant_term():
    assert _rep(hlo_bytes=819e9 * 10).dominant == "memory"
    assert _rep(coll_bytes={"all-to-all": 50e9 * 10}).dominant == "collective"
    assert _rep(hlo_flops=197e12 * 10).dominant == "compute"


def test_model_flops_train_vs_decode():
    cfg = get_config("qwen1.5-0.5b")
    tr = model_flops_for(cfg, SHAPES["train_4k"])
    de = model_flops_for(cfg, SHAPES["decode_32k"])
    assert tr == pytest.approx(6 * cfg.param_count() * 256 * 4096)
    assert de == pytest.approx(2 * cfg.param_count() * 128)


def test_moe_active_params_smaller():
    cfg = get_config("deepseek-v2-lite-16b")
    assert cfg.active_param_count() < cfg.param_count() * 0.35


def test_reg_mode_resolution():
    # kernel space: dynMR always
    assert resolve_reg_mode(RegMode.AUTO, 1, kernel_space=True,
                            crossover_pages=227) == RegMode.DYN_MR
    # user space: threshold switch
    assert resolve_reg_mode(RegMode.AUTO, 10, kernel_space=False,
                            crossover_pages=227) == RegMode.PRE_MR
    assert resolve_reg_mode(RegMode.AUTO, 300, kernel_space=False,
                            crossover_pages=227) == RegMode.DYN_MR
    # explicit modes pass through
    assert resolve_reg_mode(RegMode.PRE_MR, 300, kernel_space=True,
                            crossover_pages=1) == RegMode.PRE_MR

