"""Operation and byte counts of the yardstick, against hand counts."""
import json
from pathlib import Path

import pytest

from benchlib import layers, peaks

CONFIGS = Path(__file__).resolve().parent / "configs"


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_cnn_forward_macs_by_hand():
    # conv1: 14·14 positions × 5·5·1 taps × 16 filters; pool to 7×7
    conv1 = 14 * 14 * 5 * 5 * 1 * 16
    # conv2: 7·7 × 5·5·16 × 64; pool to 4×4 ('SAME'), so fc is 1024 wide
    conv2 = 7 * 7 * 5 * 5 * 16 * 64
    fc, head = 4 * 4 * 64 * 128, 128 * 10
    assert layers.forward_macs(_cfg("paper-cnn")) == conv1 + conv2 + fc + head
    assert conv1 + conv2 + fc + head == 1_465_152


def test_mlp_forward_macs_by_hand():
    assert layers.forward_macs(_cfg("paper-mlp")) == \
        196 * 128 + 128 * 128 + 128 * 10 == 42_752


@pytest.mark.parametrize("name", ["paper-cnn", "paper-mlp"])
def test_train_flops_are_six_per_mac(name):
    cfg = _cfg(name)
    assert layers.train_flops_per_sample(cfg) == 6 * layers.forward_macs(cfg)


def test_gram_strip_counts_by_hand():
    n, k, c = 2000, 10, 10
    got = peaks.gram_strip_counts(n, k, c)
    # read Δb (N·C) and the K rows (K·C), stats [norm, Ĥ] of both,
    # write the K×N strip; all float32
    assert got["bytes"] == 4 * (n * c + k * c + 2 * n + 2 * k + k * n)
    assert got["flops"] == 2 * k * n * c + 2 * k * n


def test_strip_is_memory_bound_on_v5e():
    least = peaks.roofline_seconds(peaks.gram_strip_counts(2000, 10, 10),
                                   peaks.peak("TPU v5 lite"))
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(
        4 * (20000 + 100 + 4000 + 20 + 20000) / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peak"):
        peaks.peak("cpu")
