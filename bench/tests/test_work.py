"""The op and byte functions against hand counts for one pruned and one
dense block."""
import numpy as np
import pytest

from benchlib import layout, work

BASE = {"gcn_joints": 25, "gcn_frames": 300, "gcn_in_channels": 3,
        "gcn_num_classes": 60, "gcn_kv": 3, "gcn_tkernel": 9}


def test_dense_block_hand_count():
    model = dict(BASE, gcn_channels=[64], gcn_strides=[1],
                 prune_channel_fracs=[], cavity_pattern="", input_skip=1)
    V, K, T = 25, 3, 300
    agg = 2 * T * K * V * V * 3          # G_k x over 3 input channels
    spatial = 2 * T * K * V * 3 * 64     # 1x1 per subset, 3 -> 64
    tconv = 2 * T * V * 64 * (64 * 9)    # every filter, every tap
    down = 2 * T * V * 3 * 64            # cin != cout
    short = 2 * T * V * 3 * 64
    ops = work.per_row(model)
    assert ops["sconv"] == agg + spatial
    assert ops["tconv"] == tconv
    assert ops["proj"] == down + short
    assert ops["fc"] == 2 * 64 * 60
    b = work.act_bytes_per_row(model)
    assert b["sconv"] == 4 * T * V * (3 + 64)
    assert b["tconv"] == 4 * V * (T * 64 + T * 64)
    w = work.weight_bytes(model)
    assert w["sconv"] == 4 * K * (3 * 64 + V * V)
    assert w["tconv"] == 4 * 64 * 64 * 9


def test_pruned_block_hand_count():
    # block 1 of a two-block model: 64 -> 128, stride 2, half its input
    # channels kept, input skip 2, cav-70-1 (22 of 72 taps kept per loop
    # of 8 filters); the last block keeps all 128 filters
    model = dict(BASE, gcn_channels=[64, 128], gcn_strides=[1, 2],
                 prune_channel_fracs=[1.0, 0.5], cavity_pattern="cav-70-1",
                 input_skip=2)
    blocks = layout.blocks(model)
    b = blocks[1]
    assert (b.cin, b.cout, b.n_in, b.n_filters) == (64, 128, 32, 128)
    assert (b.t_in, b.t_out) == (150, 75)
    assert blocks[0].n_filters == 32          # next block's kept inputs
    assert layout.cavity_mask("cav-70-1", 9).sum() == 72 - 50
    assert b.kept_taps == 128 // 8 * 22
    V, K = 25, 3
    sconv1 = 2 * 150 * K * V * 32 * (V + 128)
    tconv1 = 2 * 75 * V * 128 * (16 * 22)
    ops = work.per_row(model)
    b0 = blocks[0]
    sconv0 = 2 * 150 * K * V * 3 * (V + 64)
    tconv0 = 2 * 150 * V * 64 * b0.kept_taps
    assert ops["sconv"] == sconv0 + sconv1
    assert ops["tconv"] == tconv0 + tconv1
    proj = (2 * 150 * V * 3 * 64 * 2          # block 0 down + shortcut
            + 2 * 150 * V * 64 * 128          # block 1 down
            + 2 * 75 * V * 64 * 128)          # block 1 shortcut (stride 2)
    assert ops["proj"] == proj


def test_window_work_scales_rows_and_dispatches():
    model = dict(BASE, gcn_channels=[64], gcn_strides=[1],
                 prune_channel_fracs=[], cavity_pattern="", input_skip=1)
    one = work.window_work(model, 1, 1.0, 1)
    two = work.window_work(model, 2, 3.0, 5)
    w = work.weight_bytes(model)
    a = work.act_bytes_per_row(model)
    for f in ("sconv", "tconv"):
        assert two[f]["ops"] == pytest.approx(6 * one[f]["ops"])
        assert two[f]["bytes"] == pytest.approx(2 * (3 * a[f] + 5 * w[f]))


def test_published_pruned_model_is_lighter():
    conf = {"gcn_channels": [64, 64, 64, 64, 128, 128, 128, 256, 256, 256],
            "gcn_strides": [1, 1, 1, 1, 2, 1, 1, 2, 1, 1]}
    dense = dict(BASE, **conf, prune_channel_fracs=[], cavity_pattern="",
                 input_skip=1)
    pruned = dict(BASE, **conf, prune_channel_fracs=[
        1.0, 0.6, 0.6, 0.55, 0.5, 0.5, 0.45, 0.4, 0.35, 0.3],
        cavity_pattern="cav-70-1", input_skip=2)
    ratio = work.model_ops_per_row(dense) / work.model_ops_per_row(pruned)
    assert 5 < ratio < 12
    assert np.isclose(layout.first_logit_frames(pruned), 153)
