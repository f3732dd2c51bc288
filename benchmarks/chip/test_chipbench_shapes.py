"""The benchmark's operation and byte counts against hand counts, at the
paper's widths and at a toy size."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import shapes  # noqa: E402


def full():
    with open(os.path.join(HERE, "configs", "sdim_paper_fp32.json")) as f:
        return json.load(f)


SMOKE = {"arch": "din", "embed_dim": 8, "m": 12, "tau": 2, "short_len": 8,
         "ctx_dim": 4, "mlp_hidden": [32, 16]}


def test_full_widths_match_the_paper():
    w = shapes.widths(full())
    assert (w["e"], w["m"], w["G"], w["U"], w["s"]) == (128, 48, 16, 8, 50)


@pytest.mark.parametrize("cfg,B,C,flops", [
    # C·2·(m·e + G·U·e) per user + 3·G·U·e per user
    (None, 1, 128, 128 * 2 * (48 * 128 + 16 * 8 * 128) + 3 * 16 * 8 * 128),
    (SMOKE, 2, 3, 2 * 3 * 2 * (12 * 16 + 6 * 4 * 16) + 2 * 3 * 6 * 4 * 16),
])
def test_sdim_read_flops(cfg, B, C, flops):
    cfg = full() if cfg is None else cfg
    assert shapes.sdim_read_flops(B, C, cfg) == flops


def test_dequant_adds_one_product_per_stored_element():
    cfg = full()
    extra = (shapes.sdim_read_flops(16, 128, cfg, dequant=True)
             - shapes.sdim_read_flops(16, 128, cfg))
    assert extra == 16 * 16 * 8 * 128


@pytest.mark.parametrize("itemsize,scales,nbytes", [
    # bf16 wire tables of 16 users, fp32 q in and out, R once
    (2, False, 16 * 128 * 128 * 2 + 2 * 16 * 128 * 128 * 4 + 48 * 128 * 4),
    # int8 rows with one fp32 scale per bucket row
    (1, True, 16 * 128 * 128 + 16 * 128 * 4 + 2 * 16 * 128 * 128 * 4
     + 48 * 128 * 4),
])
def test_sdim_read_bytes(itemsize, scales, nbytes):
    assert shapes.sdim_read_bytes(16, 128, full(), itemsize,
                                  scales=scales) == nbytes


@pytest.mark.parametrize("cfg,C,flops", [
    # attention 2·2·C·s·e; head 2·C·(388·1024 + 1024·512 + 512·256
    # + 256·1)
    (None, 128, 4 * 128 * 50 * 128
     + 2 * 128 * (388 * 1024 + 1024 * 512 + 512 * 256 + 256)),
    (SMOKE, 3, 4 * 3 * 8 * 16 + 2 * 3 * (52 * 32 + 32 * 16 + 16)),
])
def test_tower_flops(cfg, C, flops):
    cfg = full() if cfg is None else cfg
    assert shapes.tower_flops(C, cfg) == flops


def test_request_flops_is_about_a_quarter_gflop_at_full_width():
    cfg = full()
    f = shapes.request_flops(128, cfg)
    assert f == shapes.tower_flops(128, cfg) + shapes.sdim_read_flops(
        1, 128, cfg)
    assert 0.27e9 < f < 0.29e9
