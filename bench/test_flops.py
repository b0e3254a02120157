"""FLOP and byte counts against hand counts at tiny shapes, and the peak
table's refusal of an unknown device."""
from __future__ import annotations

import importlib.util
import os

import pytest

from bench import spec

HERE = os.path.dirname(os.path.abspath(__file__))


def _flops(backbone):
    path = os.path.join(HERE, "flops", backbone + ".py")
    s = importlib.util.spec_from_file_location("f_" + backbone, path)
    m = importlib.util.module_from_spec(s)
    s.loader.exec_module(m)
    return m


def test_hstu_forward_flops_hand_count():
    # S=4, d=8, D_emb=2, 1 layer: in_proj 2*4*2*8=128; uvqk 2*4*8*32=2048;
    # causal pairs 10 -> QK 2*10*8=160 and AV 160; w_o 2*4*8*8=512;
    # targets 2*3*2*8=96; InfoNCE 2*3*3*8=144
    cfg = {"seq_len": 4, "d_model": 8, "n_layers": 1,
           "tables": [{"dim": 2}, {"dim": 1}]}
    assert _flops("hstu").forward_flops_per_sample(cfg) == (
        128 + 2048 + 160 + 160 + 512 + 96 + 144)


def test_fuxi_forward_flops_hand_count():
    # S=2, d=4, f=6, D_emb=2, 2 layers, 3 orders: in_proj 2*2*2*4=32;
    # per layer: qkvo 4*2*2*4*4=256, pairs 3 -> 2*2*3*4=48, up 2*2*4*6=96,
    # orders 3*2*2*6*6=432, down 96 -> 928; targets 2*1*2*4=16;
    # InfoNCE 2*1*1*4=8
    cfg = {"seq_len": 2, "d_model": 4, "d_ff": 6, "n_layers": 2,
           "fi_orders": 3, "tables": [{"dim": 2}]}
    assert _flops("fuxi").forward_flops_per_sample(cfg) == (
        32 + 2 * 928 + 16 + 8)


def test_training_sample_is_three_forward_passes():
    cfg = {"backbone": "hstu", "seq_len": 4, "d_model": 8, "n_layers": 1,
           "tables": [{"dim": 2}]}
    assert spec.flops_per_sample(cfg) == 3 * _flops(
        "hstu").forward_flops_per_sample(cfg)


def test_kernel_byte_counts_hand_count():
    k = _flops("kernels")
    # 3 rows of 4 f32 read, written as bf16, 3 int32 indices
    assert k.gather_rows(3, 4, 4, 2) == {"bytes": 3 * 4 * 4 + 3 * 4 * 2 + 12,
                                         "flops": 0}
    # 5 bf16 rows of 4 into 2 f32 sums
    assert k.segment_rowsum(5, 2, 4, 2) == {
        "bytes": 5 * 4 * 2 + 5 * 4 + 2 * 4 * 4, "flops": 20}
    peaks = {"hbm_bytes_per_s": 100.0, "bf16_flops": 10.0}
    assert k.least_seconds({"bytes": 50, "flops": 20}, peaks) == 2.0
    assert k.least_seconds({"bytes": 500, "flops": 20}, peaks) == 5.0


def test_unknown_device_kind_is_an_error():
    assert spec.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(spec.SpecError, match="not in bench/peaks.json"):
        spec.peaks("TPU v99 imaginary")
