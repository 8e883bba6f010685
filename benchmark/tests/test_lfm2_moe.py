"""CPU rehearsal of the ``lfm2_moe`` family's cell at the tiny preset of
models/hybrid.py: the family's files drive the runner, its own ``agrees``
decides ``correct``, its readers are called; the FLOPs count of the shipped
configuration against a hand count; each control of the check fails the
limit it is there to tell; the new readers on the recorded scoped trace."""

import dataclasses
import json
import os

import numpy as np
import pytest

from conftest import REPO, add_cell, rehearse
from test_cells import detail_of, last_line

CELL = "lfm2_moe_pong.learner_only"
TINY_SHAPES = {
    "batch_size": 2, "seq_len": 15, "burn_in": 4, "state_shape": [4, 84, 84],
    "layer_pattern": "CF*ECE", "hidden_size": 32, "conv_L_cache": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "rope_theta": 1000000, "intermediate_size": 48,
    "num_experts_published": 16, "num_experts": 4, "first_expert": 0,
    "num_experts_per_tok": 4, "moe_intermediate_size": 16,
    "norm_topk_prob": True, "routed_scaling_factor": 1, "router_eps": 1e-6,
    "norm_eps": 1e-5}
TINY = {
    "row": 23, "family": "lfm2_moe", "fill_chunk": 8,
    "overrides": {"hybrid_preset": "tiny-lfm2", "batch_size": 2,
                  "seq_len": 15, "seq_overlap": 7, "burn_in": 4, "nstep": 2,
                  "memory_size": 128, "steps_per_dispatch": 1},
    "shapes": TINY_SHAPES, "reference": "lfm2_moe",
    "reference_hyper": {"burn_in": 4, "nstep": 2, "gamma": 0.99, "eta": 0.9,
                        "double": True, "value_rescale": True,
                        "pack_frames": 4},
    # bf16 matmuls against float32 at width 32: these only have to pass
    "tolerance": {"loss_rel": 0.2, "td_p50_over_mean": 0.5,
                  "td_p90_over_mean": None,
                  "grad_cosine": 0.9, "grad_cosine_leaf": 0.0,
                  "grad_norm_leaf_rel": 1.0, "moe_rows_rel": 0.2,
                  "sconv_out_rel": 0.05, "attn_out_rel": 0.05,
                  "moe_out_rel": 0.05, "route_weight_rel": 0.01,
                  "why": "CPU rehearsal"},
}


def shipped():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "lfm2_moe_pong.json")) as f:
        return json.load(f)


def test_the_cell_resolves_to_its_files():
    from benchmark.harness import manifest

    cell = manifest.load_cell(CELL)
    assert cell.chips == 1 and cell.config["family"] == "lfm2_moe"
    names = {m["name"] for m in cell.per_layer}
    assert {"phase_sconv_ms", "phase_sconv_mix_ms", "mfu", "step_device_ms",
            "phase_online_ms"} <= names
    # a benchmark PR's to extend: the closed lists of the earlier trunks
    assert not {"phase_ssm_ms", "phase_moe_ms", "phase_attn_ms",
                "phase_gdn_ms", "phase_kda_ms", "phase_mlp_ms",
                "gmm_roofline_share", "phase_embed_ms",
                "moe_rows_computed_over_routed"} & names
    for m in cell.per_layer:
        reader = manifest.load_module("layer_metrics", m["name"])
        assert callable(reader.read)
        if m["name"].startswith("phase_sconv"):
            assert m["workloads"] == [CELL]


def test_the_family_runs_its_cell_at_the_tiny_preset(tiny_root):
    add_cell(tiny_root, "tiny_lfm2_moe.tiny_learner_only", "tiny_lfm2_moe",
             "tiny_learner_only", 1, like=CELL, config_body=TINY)
    proc = rehearse(tiny_root, "tiny_lfm2_moe.tiny_learner_only", trace=1,
                    seconds=2.0)
    line, detail = last_line(proc), detail_of(proc)
    assert line["correct"] is True, detail["check"]
    check = detail["check"]
    assert check["failed"] == []
    # bf16 and float32 route a few boundary tokens differently
    assert all(abs(a - b) <= 3 for a, b in zip(
        check["moe"]["rows_here"], check["moe"]["rows_here_reference"]))
    assert check["grad"]["leaves"] > 30
    assert list(check["sconv_out"]["rel_err_by_layer"]) == ["0", "4"]
    assert list(check["attn_out"]["rel_err_by_layer"]) == ["2"]
    assert list(check["moe_out"]["rel_err_by_layer"]) == ["3", "5"] == list(
        check["route_weight"]["rel_err_by_layer"])
    assert check["moe"]["rows_computed"] >= check["moe"]["rows_here_mean"]
    # the two phase_sconv_*_ms read a device trace: nothing on the CPU
    assert not {"phase_sconv_ms", "phase_sconv_mix_ms"} & set(
        detail["rehearsal_metric_names"])


def test_the_walk_is_the_models_own_pass():
    import jax
    import jax.numpy as jnp

    from benchmark.families import lfm2_moe
    from pytorch_distributed_tpu.models.hybrid import PRESETS, HybridQModel

    c = PRESETS["tiny-lfm2"]
    model = HybridQModel(action_space=6, state_shape=(4, 12, 12), window=64,
                         preset=c, norm_val=255.0)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 12, 12),
                                                         jnp.uint8))
    frames = jax.random.bits(jax.random.PRNGKey(1), (4, 64, 12, 12),
                             jnp.uint8)
    _, load, _ = model.apply(params, frames, method=model.window_pass)
    blocks = lfm2_moe.walk(model, params, frames)
    assert list(blocks) == [0, 2, 3, 4, 5]
    for i, (u, out, *routed) in blocks.items():
        assert u.shape == out.shape == (4, 64, c.d_model)
        assert len(routed) == (2 if c.pattern[i] == "E" else 0)
    # the expert blocks' choices are the window pass's load
    for i in (3, 5):
        chosen, w = blocks[i][2:]
        assert chosen.shape == w.shape == (4, 64, c.top_k)
        counts = jnp.sum(jax.nn.one_hot(chosen, c.n_experts,
                                        dtype=jnp.int32), axis=(0, 1, 2))
        assert jnp.array_equal(counts, load[i])
        np.testing.assert_allclose(jnp.sum(w, -1), 1.0, rtol=1e-4)


def test_the_flops_count_of_the_shipped_configuration():
    """Against a hand count from the published widths (about
    184.6 M multiply-adds a position forward, frame embed included: the
    four conv mixers 67.1 M, the dense block 44.0 M, the held experts at
    their expected load 44.3 M with the routers, attention 14.7 M with the
    causal half of a 2,048 window)."""
    from benchmark.families import lfm2_moe

    cfg = shipped()
    per = lfm2_moe.forward_flops(cfg["shapes"], 2048, 84 * 84, 6)
    d = 2048
    assert per["embed"] == 2 * 7056 * d
    assert per["sconv"] == 4 * (2 * d * 3 * d + 2 * 3 * d + 2 * d
                                + 2 * d * d)
    assert per["attn"] == (2 * 2 * d * 2048 + 2 * 2 * d * 512
                           + 2 * 2 * 2048 * 1024)
    assert per["mlp"] == 3 * 2 * d * 7168
    # 4 choices of 32, 8 held: one expert a position in expectation
    assert per["moe"] == 4 * (2 * d * 32 + 1.0 * 3 * 2 * d * 1792)
    total = sum(per.values())
    assert 0.365e9 < total < 0.372e9
    assert 0.35 < per["sconv"] / total < 0.38
    assert 0.23 < per["mlp"] / total < 0.25
    assert 0.23 < per["moe"] / total < 0.25
    assert 0.07 < per["attn"] / total < 0.09
    flops = lfm2_moe.update_flops(cfg["shapes"], (4, 84, 84), 6)
    assert flops == int(4 * 4 * 2048 * total)
    # every published size under its own name, equal at the file's top level
    for key in lfm2_moe.MODEL_KEYS:
        if key in cfg:
            assert cfg["shapes"][key] == cfg[key], key
    assert cfg["shapes"]["num_experts_published"] == cfg["published"][
        "num_experts"]
    assert cfg["tolerance"]["td_p90_over_mean"] is None


# -- the check's controls, at a small size ------------------------------------

@pytest.fixture(scope="module")
def side(tmp_path_factory):
    """One program side at the tiny preset in float32, and the shipped
    configuration's limits around the tiny shapes."""
    import jax

    from benchmark.families import lfm2_moe
    from benchmark.harness import manifest, program

    cfg = dict(TINY, overrides=dict(TINY["overrides"], batch_size=4,
                                    compute_dtype="float32"),
               shapes=dict(TINY_SHAPES, batch_size=4),
               tolerance=shipped()["tolerance"])
    opt = program.build_opt(cfg, 5, str(tmp_path_factory.mktemp("l")), "t",
                            num_actors=0, evaluator_nepisodes=0)
    lrn = program.build_learner(opt)
    # a zero head hides the trunk
    params = lrn.state.params
    params["params"]["head_w"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(1), params["params"]["head_w"].shape)
    program.fill_ring(lrn, 5, 8, lfm2_moe)
    reference = manifest.load_module("reference", "lfm2_moe")
    return lfm2_moe.program_side(lrn, 5, reference), cfg, reference


def test_the_sound_comparison_is_inside_the_shipped_limits(side):
    from benchmark.families import lfm2_moe

    got = lfm2_moe.compare(*side)
    assert got["ok"] and got["failed"] == [], got


@pytest.mark.parametrize("control", [
    "no_conv_gate", "conv_shift", "b_sel_in_weights", "no_qk_norm",
    "half_rotary", "no_topk_renorm", "bf16_trunk"])
def test_each_control_fails_the_limit_it_is_there_to_tell(side, control):
    from benchmark.families import lfm2_moe

    program_side, cfg, reference = side
    got = lfm2_moe.compare(program_side, cfg, reference,
                           **lfm2_moe.CONTROLS[control])
    assert not got["ok"], (control, got)
    tells = {"no_conv_gate": "sconv_out_rel", "conv_shift": "sconv_out_rel",
             "no_qk_norm": "attn_out_rel", "half_rotary": "attn_out_rel",
             "b_sel_in_weights": "route_weight_rel",
             "no_topk_renorm": "route_weight_rel",
             "bf16_trunk": "route_weight_rel"}
    assert tells[control] in got["failed"], (control, got["failed"])


# -- the new readers ----------------------------------------------------------

def _scoped_planes():
    from benchmark.harness import phases

    return phases.load(os.path.join(REPO, "benchmark", "testdata",
                                    "tiny_tpu_scoped.xplane.pb"))


def test_the_readers_on_the_recorded_scoped_trace():
    """Without the trunk's scopes on any path: nothing.  With the recorded
    ``train.online`` paths rewritten to stand under them: the ops' self
    time, the part under ``sconv.mix`` no more than the whole."""
    from benchmark.harness import phases, sconv_scopes

    devices, window = _scoped_planes()
    step = ["jit_tiny_scoped_step"]
    assert sconv_scopes.per_update_ms(devices, window, step, 1) == {}
    online = phases.per_update_ms(devices, window, step, 1)["online"]

    def under(meta, inner):
        tf_op = meta.tf_op
        if tf_op and "train.online" in tf_op:
            tf_op = tf_op.replace(
                "train.online", "train.online/" + inner(tf_op), 1)
        return dataclasses.replace(meta, tf_op=tf_op)

    def rewritten(inner):
        return [dataclasses.replace(d, meta={
            k: under(m, inner) for k, m in d.meta.items()}) for d in devices]

    whole = sconv_scopes.per_update_ms(
        rewritten(lambda _: "model.sconv/sconv.mix"), window, step, 1)
    assert whole["sconv"] == pytest.approx(online) == pytest.approx(
        whole["sconv_mix"])
    assert set(whole) == {"sconv", "sconv_mix"}
    # only the backward's ops under sconv.mix, the forward's outside it
    part = sconv_scopes.per_update_ms(rewritten(
        lambda path: "model.sconv/sconv.mix" if "transpose(" in path
        else "model.sconv"), window, step, 1)
    assert part["sconv"] == pytest.approx(online)
    assert 0.0 < part["sconv_mix"] < online
    # another model scope inside: not this trunk's
    assert sconv_scopes.per_update_ms(
        rewritten(lambda _: "model.sconv/model.moe"), window, step, 1) == {}


def test_an_op_is_filed_by_its_innermost_model_scope():
    from benchmark.harness.sconv_scopes import parts_of

    path = ("jit(one)/train.online/transpose(jvp(HybridQModel.window_pass))"
            "/checkpoint/rematted_computation/model.sconv/sconv.mix/mul:")
    assert parts_of(path) == ("sconv", "sconv_mix")
    assert parts_of("jit(one)/train.target/model.sconv/dot_general:") == (
        "sconv",)
    assert parts_of("jit(one)/model.sconv/x/model.attn/y") == ()
    assert parts_of("jit(one)/model.attn/sconv.mix/y") == ()
    assert parts_of("jit(one)/my_sconv.mixer/model.sconv_x/add") == ()
    assert parts_of(None) == ()
