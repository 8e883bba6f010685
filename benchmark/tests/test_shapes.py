"""FLOPs from shapes against counts made by hand."""

import pytest

from benchmark.families import dqn, r2d2
from benchmark.harness import shapes


def test_nature_cnn_forward_hand_count():
    # 84x84x4 in.  conv1 8x8/4 -> 20x20x32, conv2 4x4/2 -> 9x9x64,
    # conv3 3x3/1 -> 7x7x64, fc 3136 -> 512.  Multiply-adds:
    conv1 = 20 * 20 * 32 * (8 * 8 * 4)       # 3,276,800
    conv2 = 9 * 9 * 64 * (4 * 4 * 32)        # 2,654,208
    conv3 = 7 * 7 * 64 * (3 * 3 * 64)        # 1,806,336
    fc = 3136 * 512                          # 1,605,632
    assert (conv1, conv2, conv3, fc) == (3276800, 2654208, 1806336, 1605632)
    macs = conv1 + conv2 + conv3 + fc
    assert shapes.nature_cnn_forward_flops((4, 84, 84)) == 2 * macs
    # with the 6-action head: 18.7 MFLOP forward per frame stack
    assert dqn.forward_flops((4, 84, 84), 6) == 2 * (macs + 512 * 6) \
        == 18_692_096


def test_lstm_step_hand_count():
    # four gates, each 512x512 from the input and 512x512 from the state
    assert shapes.lstm_step_flops(512, 512) == 2 * 4 * 2 * 512 * 512 \
        == 4_194_304


def test_dqn_update_is_four_forwards_per_row():
    fwd = dqn.forward_flops((4, 84, 84), 6)
    plain = {"batch_size": 128, "double": False}
    assert dqn.update_flops(plain, (4, 84, 84), 6) == 128 * 4 * fwd  # 9.57 G
    assert dqn.update_flops(dict(plain, double=True), (4, 84, 84), 6) \
        == 128 * 5 * fwd
    assert dqn.update_flops(dict(plain, batch_size=512), (4, 84, 84), 6) \
        == 4 * 128 * 4 * fwd


def test_r2d2_update_hand_count():
    # per segment: target 81 steps; online 40 burn-in + 41 x (fwd + bwd = 3)
    fwd = r2d2.forward_flops((4, 84, 84), 6, 512)
    assert fwd == shapes.nature_cnn_forward_flops((4, 84, 84)) \
        + 4_194_304 + 2 * 512 * 6
    group = {"batch_size": 64, "seq_len": 80, "burn_in": 40, "lstm_dim": 512}
    assert r2d2.update_flops(group, (4, 84, 84), 6) \
        == 64 * (81 + 40 + 3 * 41) * fwd      # 357 GFLOP


@pytest.mark.parametrize("config", ["apex_pong", "r2d2_pong",
                                    "apex_pong_dp4"])
def test_each_shipped_config_counts_through_its_family(config):
    """``Ctx.flops_per_update`` as ``mfu`` calls it: the family named by the
    configuration's file, on that file's ``shapes`` group."""
    import types

    from benchmark.harness import cell, manifest

    c = manifest.load_cell(f"{config}.learner_only")
    ctx = cell.Ctx(cell=c, result=types.SimpleNamespace(notes={
        "state_shape": [4, 84, 84], "num_actions": 6}), phases={},
        device_count=c.chips, peaks=None, trace=None)
    want = {"apex_pong": 128 * 4 * 18_692_096,
            "apex_pong_dp4": 512 * 4 * 18_692_096,
            "r2d2_pong": 64 * 244 * r2d2.forward_flops((4, 84, 84), 6, 512)}
    assert ctx.flops_per_update() == want[config]
