"""The analytic FLOPs-per-item functions against hand-counted values,
and the table of peaks."""

import json
import os

import pytest

import bench_testlib
from benchmark.lib import cells, device


def _config(name):
    with open(os.path.join(bench_testlib.REPO, "benchmark", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_resnet50_macs_hand_counted():
    r = cells.load_module("flops", "resnet50_v1")
    # Stem: 112 x 112 positions x 7 x 7 x 3 inputs x 64 outputs.
    stem = 112 * 112 * 147 * 64
    # Stage 1 (56 x 56, width 64): the first block has a 64 -> 256
    # projection; every block is 1x1 (cin -> 64), 3x3 (64 -> 64), 1x1 (64 -> 256).
    p = 56 * 56
    stage1 = (
        p * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
        + 2 * p * (256 * 64 + 9 * 64 * 64 + 64 * 256)
    )
    assert stem == 118_013_952
    assert stage1 == 667_942_912
    only_stage1 = stem + stage1
    total = r.forward_macs(224, 1000, "conv2")
    assert total == 4_089_184_256
    assert r.forward_macs(224, 1000, "conv1") == 3_857_973_248
    assert total > only_stage1
    assert r.flops_per_item(224, 1000, "conv2") == 6 * 4_089_184_256


def test_gpt2_medium_flops_hand_counted():
    g = cells.load_module("flops", "gpt2")
    per_layer = 4 * 1024**2 + 2 * 1024 * 4096 + 2 * 1024 * 1024
    assert per_layer == 14_680_064
    macs = 24 * per_layer + 1024 * 50257
    assert macs == 403_784_704
    assert g.forward_macs_per_token(24, 1024, 4096, 50257, 1024) == macs
    assert g.flops_per_item(24, 1024, 4096, 50257, 1024) == 6 * macs


@pytest.mark.parametrize(
    "name, lo, hi", [("resnet50", 24.0e9, 25.0e9), ("gpt2m", 2.40e9, 2.45e9)]
)
def test_config_files_name_their_flops(name, lo, hi):
    assert lo < cells.flops_per_item(_config(name)) < hi


def test_peaks_table():
    v5e = device.load_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert v5e["ici_bits_per_s"] == 1600e9
    for kind in ("cpu", "TPU v4", "source"):
        with pytest.raises(device.UnknownDevice):
            device.load_peaks(kind)


def test_memory_peak_is_labelled_as_derived():
    """``memory_peak_bytes`` is a sum of two readings, and says so."""
    import types

    def chip(peak):
        return types.SimpleNamespace(
            platform="tpu", device_kind="TPU v5 lite",
            memory_stats=lambda: {"peak_bytes_in_use": peak},
        )

    dev = device.device_object([chip(100), chip(300)], temp_bytes=50)
    assert dev["memory_peak_bytes"] == 350 and dev["count"] == 2
    assert dev["memory_peak_derived_from"] == {
        "allocator_peak_bytes": 300, "largest_program_temp_bytes": 50,
    }
    # A backend without memory statistics (the CPU) reports 0, not the scratch alone.
    cpu = types.SimpleNamespace(platform="cpu", device_kind="cpu", memory_stats=lambda: None)
    assert device.device_object([cpu], temp_bytes=50)["memory_peak_bytes"] == 0
