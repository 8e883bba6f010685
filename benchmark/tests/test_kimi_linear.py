"""CPU rehearsal of the ``kimi_linear`` family's cell at the tiny preset of
models/hybrid.py: the family's files drive the runner, its own ``agrees``
decides ``correct``, its readers are called; the FLOPs count of the shipped
configuration against a hand count; each control of the check fails the
limit it is there to tell; the new readers on the recorded scoped trace."""

import dataclasses
import json
import os

import pytest

from conftest import REPO, add_cell, rehearse
from test_cells import detail_of, last_line

CELL = "kimi_linear_pong.learner_only"
TINY_SHAPES = {
    "batch_size": 2, "seq_len": 15, "burn_in": 4, "state_shape": [4, 84, 84],
    "layer_pattern": "KFLE", "hidden_size": 32,
    "kda_num_heads": 4, "kda_head_dim": 8, "short_conv_kernel_size": 4,
    "kda_gate_rank": 8, "kda_chunk": 4, "kda_sub_block": 2,
    "num_attention_heads": 4, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "v_head_dim": 8, "kv_lora_rank": 16, "intermediate_size": 48,
    "num_experts_published": 16, "num_experts": 4, "first_expert": 0,
    "num_experts_per_token": 3, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16, "moe_renormalize": True,
    "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5}
TINY = {
    "row": 22, "family": "kimi_linear", "fill_chunk": 8,
    "overrides": {"hybrid_preset": "tiny-kimi", "batch_size": 2,
                  "seq_len": 15, "seq_overlap": 7, "burn_in": 4, "nstep": 2,
                  "memory_size": 128, "steps_per_dispatch": 1},
    "shapes": TINY_SHAPES, "reference": "kimi_linear",
    "reference_hyper": {"burn_in": 4, "nstep": 2, "gamma": 0.99, "eta": 0.9,
                        "double": True, "value_rescale": True,
                        "pack_frames": 4, "scan_state_dtype": "float32"},
    # bf16 matmuls against float32 at width 32: these only have to pass
    "tolerance": {"loss_rel": 0.2, "td_p50_over_mean": 0.5,
                  "td_p90_over_mean": None,
                  "grad_cosine": 0.9, "grad_cosine_leaf": 0.0,
                  "grad_norm_leaf_rel": 1.0, "moe_rows_rel": 0.2,
                  "kda_state_rel": 0.05, "kda_state_slow_rel": 0.05,
                  "mla_out_rel": 0.05, "why": "CPU rehearsal"},
}


def shipped():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "kimi_linear_pong.json")) as f:
        return json.load(f)


def test_the_cell_resolves_to_its_files():
    from benchmark.harness import manifest

    cell = manifest.load_cell(CELL)
    assert cell.chips == 1 and cell.config["family"] == "kimi_linear"
    names = {m["name"] for m in cell.per_layer}
    assert {"phase_kda_ms", "phase_kda_chunk_ms", "phase_mla_ms",
            "phase_mlp_ms", "mfu", "step_device_ms",
            "phase_online_ms"} <= names
    # a benchmark PR's to extend: the closed lists of the earlier trunks
    assert not {"phase_ssm_ms", "phase_moe_ms", "phase_attn_ms",
                "phase_gdn_ms", "gmm_roofline_share", "phase_embed_ms",
                "moe_rows_computed_over_routed"} & names
    for m in cell.per_layer:
        reader = manifest.load_module("layer_metrics", m["name"])
        assert callable(reader.read)
        if m["name"].startswith(("phase_kda", "phase_mla", "phase_mlp")):
            assert m["workloads"] == [CELL]


def test_the_family_runs_its_cell_at_the_tiny_preset(tiny_root):
    add_cell(tiny_root, "tiny_kimi_linear.tiny_learner_only",
             "tiny_kimi_linear", "tiny_learner_only", 1, like=CELL,
             config_body=TINY)
    proc = rehearse(tiny_root, "tiny_kimi_linear.tiny_learner_only", trace=1,
                    seconds=2.0)
    line, detail = last_line(proc), detail_of(proc)
    assert line["correct"] is True, detail["check"]
    check = detail["check"]
    assert check["failed"] == []
    # bf16 and float32 route a few boundary tokens differently
    assert all(abs(a - b) <= 3 for a, b in zip(
        check["moe"]["rows_here"], check["moe"]["rows_here_reference"]))
    assert check["grad"]["leaves"] > 30
    assert list(check["kda_state"]["rel_err_by_layer"]) == ["0"] == list(
        check["kda_state_slow"]["rel_err_by_layer"])
    assert list(check["mla_out"]["rel_err_by_layer"]) == ["2"]
    assert 0.0 < check["kda_decay"]["min"] < check["kda_decay"]["mean"] < 1.0
    assert check["moe"]["rows_computed"] >= check["moe"]["rows_here_mean"]
    # the four phase_*_ms read a device trace: nothing on the CPU
    assert not {"phase_kda_ms", "phase_kda_chunk_ms", "phase_mla_ms",
                "phase_mlp_ms"} & set(detail["rehearsal_metric_names"])


def test_the_walk_is_the_models_own_pass():
    import jax
    import jax.numpy as jnp

    from benchmark.families import kimi_linear
    from pytorch_distributed_tpu.models.hybrid import PRESETS, HybridQModel

    c = PRESETS["tiny-kimi"]
    model = HybridQModel(action_space=6, state_shape=(4, 12, 12), window=64,
                         preset=c, norm_val=255.0)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 12, 12),
                                                         jnp.uint8))
    frames = jax.random.bits(jax.random.PRNGKey(1), (4, 64, 12, 12),
                             jnp.uint8)
    _, _, states = model.apply(params, frames, method=model.window_pass)
    walked, attended = kimi_linear.walk(model, params, frames)
    assert list(walked) == list(states) == [0] and list(attended) == [2]
    for i in states:
        u, S, S_slow = walked[i]
        assert jnp.array_equal(states[i], S)
        assert u.shape == (4, 64, c.d_model) and S_slow.shape == S.shape
        # slowed, the state still holds what the window's start wrote
        assert float(jnp.linalg.norm(S_slow)) > float(jnp.linalg.norm(S))
    u, out = attended[2]
    assert u.shape == out.shape == (4, 64, c.d_model)


def test_the_flops_count_of_the_shipped_configuration():
    """Against a hand count from the published widths (the issue: about
    0.66 GFLOP a position, the four KDA mixers 53 %, the latent attention
    12 %, the dense MLP 19 %, the experts 11 %, the embed 5 %)."""
    from benchmark.families import kimi_linear

    cfg = shipped()
    per = kimi_linear.forward_flops(cfg["shapes"], 2048, 84 * 84, 6)
    d = 2304
    assert per["embed"] == 2 * 7056 * d
    kda = (3 * 2 * d * 4096 + 2 * (2 * d * 128 + 2 * 128 * 4096)
           + 2 * d * 32 + 3 * 2 * 4 * 4096                  # b; the convs
           + 32 * (2 * 2 * 32 * 128                         # K K^T, Q K^T
                   + 2 * 32 * 256 + 3 * 2 * 128 * 128 + 2 * 32 * 128)
           + 2 * 4096 * d)
    assert per["kda"] == 4 * kda
    assert per["mla"] == (2 * d * 6144 + 2 * d * 576 + 2 * 512 * 8192
                          + 2 * 4096 * d + 2 * 32 * (192 + 128) * 1024)
    assert per["mlp"] == 3 * 2 * d * 9216
    assert per["moe"] == 4 * (2 * d * 256 + 3 * 2 * d * 1024
                              + 0.25 * 3 * 2 * d * 1024)
    total = sum(per.values())
    assert 0.63e9 < total < 0.67e9
    assert 0.50 < per["kda"] / total < 0.55
    assert 0.11 < per["mla"] / total < 0.13
    assert 0.18 < per["mlp"] / total < 0.21
    assert 0.10 < per["moe"] / total < 0.13
    # the recurrence itself is a small part of a KDA mixer
    assert 32 * (2 * 2 * 32 * 128 + 2 * 32 * 256 + 3 * 2 * 128 * 128
                 + 2 * 32 * 128) / kda < 0.06
    flops = kimi_linear.update_flops(cfg["shapes"], (4, 84, 84), 6)
    assert flops == int(4 * 4 * 2048 * total)
    # every published size under its own name, equal at the file's top level
    for key in kimi_linear.MODEL_KEYS:
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

    from benchmark.families import kimi_linear
    from benchmark.harness import manifest, program

    cfg = dict(TINY, overrides=dict(TINY["overrides"], batch_size=4,
                                    compute_dtype="float32"),
               shapes=dict(TINY_SHAPES, batch_size=4),
               tolerance=shipped()["tolerance"])
    opt = program.build_opt(cfg, 5, str(tmp_path_factory.mktemp("k")), "t",
                            num_actors=0, evaluator_nepisodes=0)
    lrn = program.build_learner(opt)
    # a zero head hides the trunk
    params = lrn.state.params
    params["params"]["head_w"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(1), params["params"]["head_w"].shape)
    program.fill_ring(lrn, 5, 8, kimi_linear)
    reference = manifest.load_module("reference", "kimi_linear")
    return kimi_linear.program_side(lrn, 5, reference), cfg, reference


def test_the_sound_comparison_is_inside_the_shipped_limits(side):
    from benchmark.families import kimi_linear

    got = kimi_linear.compare(*side)
    assert got["ok"] and got["failed"] == [], got


@pytest.mark.parametrize("control", [
    "head_mean_decay", "no_beta", "no_latent_norm", "key_part_a_head",
    "no_topk_renorm", "no_route_scale", "bf16_scan_state"])
def test_each_control_fails_the_limit_it_is_there_to_tell(side, control):
    from benchmark.families import kimi_linear

    program_side, cfg, reference = side
    if control == "bf16_scan_state":
        # the shipped limit stands between the CHIP's readings (bf16
        # matmuls, 2,048 positions); this program side is float32 over 16
        # positions: sound 1e-7, a bfloat16 state 1e-3
        cfg = dict(cfg, tolerance=dict(cfg["tolerance"],
                                       kda_state_slow_rel=5e-4))
    got = kimi_linear.compare(program_side, cfg, reference,
                              **kimi_linear.CONTROLS[control])
    assert not got["ok"], (control, got)
    tells = {"head_mean_decay": "kda_state_rel", "no_beta": "kda_state_rel",
             "bf16_scan_state": "kda_state_slow_rel",
             "no_latent_norm": "mla_out_rel",
             "key_part_a_head": "mla_out_rel"}
    if control in tells:
        assert tells[control] in got["failed"], (control, got["failed"])


# -- the new readers ----------------------------------------------------------

def _scoped_planes():
    from benchmark.harness import phases

    return phases.load(os.path.join(REPO, "benchmark", "testdata",
                                    "tiny_tpu_scoped.xplane.pb"))


def test_the_readers_on_the_recorded_scoped_trace():
    """Without the trunk's scopes on any path: nothing.  With the recorded
    ``train.online`` paths rewritten to stand under them: the ops' self
    time, the part under ``kda.chunk`` no more than the whole."""
    from benchmark.harness import kda_scopes, phases

    devices, window = _scoped_planes()
    step = ["jit_tiny_scoped_step"]
    assert kda_scopes.per_update_ms(devices, window, step, 1) == {}
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

    whole = kda_scopes.per_update_ms(
        rewritten(lambda _: "model.kda/kda.chunk"), window, step, 1)
    assert whole["kda"] == pytest.approx(online) == pytest.approx(
        whole["kda_chunk"])
    assert set(whole) == {"kda", "kda_chunk"}
    # only the backward's ops under kda.chunk, the forward's under model.mla
    part = kda_scopes.per_update_ms(rewritten(
        lambda path: "model.kda/kda.chunk" if "transpose(" in path
        else "model.mla"), window, step, 1)
    assert part["kda"] == pytest.approx(part["kda_chunk"])
    assert part["kda"] + part["mla"] == pytest.approx(online)
    assert 0.0 < part["kda_chunk"] < online
    # model.kda with no chunk under it reads a chunk of zero, not nothing
    bare = kda_scopes.per_update_ms(rewritten(lambda _: "model.kda"),
                                    window, step, 1)
    assert bare == {"kda": pytest.approx(online), "kda_chunk": 0.0}
    # another model scope inside: not this trunk's
    other = kda_scopes.per_update_ms(
        rewritten(lambda _: "model.kda/model.moe"), window, step, 1)
    assert other == {}
    only_mlp = kda_scopes.per_update_ms(
        rewritten(lambda _: "model.mlp"), window, step, 1)
    assert only_mlp == {"mlp": pytest.approx(online)}


def test_an_op_is_filed_by_its_innermost_model_scope():
    from benchmark.harness.kda_scopes import parts_of

    path = ("jit(one)/train.online/transpose(jvp(HybridQModel.window_pass))"
            "/checkpoint/rematted_computation/model.kda/kda.chunk/"
            "dot_general:")
    assert parts_of(path) == ("kda", "kda_chunk")
    assert parts_of("jit(one)/train.target/model.kda/mul:") == ("kda",)
    assert parts_of("jit(one)/model.kda/kda.chunk/while/body/model.kda/"
                    "kda.chunk/add:") == ("kda", "kda_chunk")
    assert parts_of("jit(one)/train.online/model.mla/checkpoint/model.mla/"
                    "exp:") == ("mla",)
    assert parts_of("jit(one)/model.mlp/dot_general:") == ("mlp",)
    assert parts_of("jit(one)/model.kda/x/model.attn/y") == ()
    assert parts_of("jit(one)/model.mla/kda.chunk/y") == ("mla",)
    assert parts_of("jit(one)/my_kda.chunky/model.mlp_x/add") == ()
    assert parts_of(None) == ()
