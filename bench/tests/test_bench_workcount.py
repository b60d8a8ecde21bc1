"""The benchmark's work counts against hand counts at small shapes, and
the table of peaks."""
from __future__ import annotations

import pytest

import small_cells  # noqa: F401  (puts the benchmark on the path)
import peaks
import workcount as wc

TINY = {"d_model": 4, "head_dim": 2, "num_heads": 2, "num_kv_heads": 1,
        "d_ff": 8, "vocab_size": 10, "num_layers": 3, "mlp_act": "relu2"}


def test_decide_split():
    # 4 users x 3 splits x 9 operations; 4 x (7 f32 columns in, split
    # and cost out) bytes plus three 3-long f32 rows
    assert wc.decide_split(4, 3) == (108.0, 4 * 36 + 3 * 3 * 4)


def test_tree_predict():
    # 2 rows x 2 trees x (3 + 1) visits; rows in, 5 node arrays, out
    assert wc.tree_predict(2, 3, 2, 5, 3) == (16.0, 24 + 5 * 2 * 5 * 4 + 8)


def test_decoder_params():
    # attention 4 x 2 x (2 + 2 + 1 + 1) = 48, squared-ReLU MLP 2 x 4 x 8
    assert wc.decoder_params(TINY) == {"layer": 112, "layers": 336,
                                       "head": 40}
    gated = dict(TINY, mlp_act="silu")
    assert wc.decoder_params(gated)["layer"] == 48 + 3 * 4 * 8


def test_decoder_token_flops():
    # 2 x 336 + QK and PV over 5 positions in 3 layers + 2 x head
    assert wc.decoder_token_flops(TINY, 5, True) == 672 + 4 * 2 * 2 * 5 * 3 \
        + 80
    assert wc.decoder_token_flops(TINY, 5, False) == 672 + 240


def test_decoder_prefill_flops():
    # 3 tokens; causal pairs 1 + 2 + 3 = 6; one head at the last token
    assert wc.decoder_prefill_flops(TINY, 3) == 2 * 336 * 3 \
        + 4 * 2 * 2 * 3 * 6 + 80


def test_placement():
    assert wc.placement(3, 4) == (60.0, 3 * (48 + 64))


def test_peaks_are_keyed_by_device_kind():
    p = peaks.peaks("TPU v5 lite")
    assert (p["flops"], p["bytes_per_s"]) == (197e12, 819e9)
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def test_roofline_names_its_bound():
    p = peaks.peaks("TPU v5 lite")
    assert peaks.roofline_s(197e12, 1.0, p) == (1.0, "flops")
    assert peaks.roofline_s(1.0, 819e9, p) == (1.0, "bytes")
