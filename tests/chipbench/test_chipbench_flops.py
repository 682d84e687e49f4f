"""The FLOP functions against hand counts from the published shapes."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import manifest  # noqa: E402


def _load(config):
    sizes = json.load(open(os.path.join(REPO, "chipbench", "configs", config + ".json")))["sizes"]
    return manifest.load_module(os.path.join(REPO, "chipbench", "flops", config + ".py")), sizes


def test_resnet50_is_about_4_1_gmac_forward_per_224_image():
    flops, sizes = _load("resnet50")
    # He et al. table 1 gives 3.8 GFLOPs (multiply-adds) for v1; the stride on
    # the 3x3 (v1.5) moves it to 4.09
    assert flops.forward_macs(sizes) == pytest.approx(4.09e9, rel=0.005)
    # stem by hand: 112*112 outputs, 7*7*3 inputs, 64 filters
    stem = 112 * 112 * 7 * 7 * 3 * 64
    head = 2048 * 1000
    assert stem == 118013952
    first_block = 56 * 56 * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
    one = dict(sizes, stage_sizes=[1])
    assert flops.forward_macs(one) == stem + first_block + 256 * 1000
    assert head < 0.001 * flops.forward_macs(sizes)
    assert flops.train_flops_per_sample(sizes) == 6 * flops.forward_macs(sizes)


def test_bert_base_from_its_shapes():
    flops, sizes = _load("bert-base")
    d, ff, s, layers = 768, 3072, 128, 12
    per_token = 4 * d * d + 2 * d * ff + 2 * s * d   # projections, ffn, scores+values
    by_hand = layers * s * per_token + d * d + d * 2
    assert flops.forward_macs(sizes) == by_hand
    # 85M matmul parameters in the blocks: 2 * 85M * 128 tokens forward, to 3 %
    # (attention's scores and values are the rest)
    assert flops.forward_macs(sizes) == pytest.approx(85e6 * s, rel=0.03)
    assert flops.train_flops_per_sample(sizes) == pytest.approx(67.0e9, rel=0.01)


def test_peaks_table_is_keyed_by_device_kind_and_refuses_others():
    bench = os.path.join(REPO, "chipbench")
    v5e = manifest.peaks(bench, "TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(manifest.ManifestError, match="not in"):
        manifest.peaks(bench, "cpu")
