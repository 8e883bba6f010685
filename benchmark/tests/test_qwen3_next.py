"""CPU rehearsal of the ``qwen3_next`` family's cell at the tiny preset of
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

TINY_SHAPES = {
    "batch_size": 2, "seq_len": 15, "burn_in": 4, "state_shape": [4, 84, 84],
    "layer_pattern": "DE*E", "hidden_size": 32,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    "linear_conv_kernel_dim": 4, "gdn_chunk": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "partial_rotary_factor": 0.5, "rope_theta": 10000000,
    "num_experts_published": 16, "num_experts": 4, "first_expert": 0,
    "num_experts_per_tok": 3, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "router_aux_loss_coef": 0.001}
TINY = {
    "row": 21, "family": "qwen3_next", "fill_chunk": 8,
    "overrides": {"hybrid_preset": "tiny-qwen", "batch_size": 2,
                  "seq_len": 15, "seq_overlap": 7, "burn_in": 4, "nstep": 2,
                  "memory_size": 128, "steps_per_dispatch": 1},
    "shapes": TINY_SHAPES, "reference": "qwen3_next",
    "reference_hyper": {"burn_in": 4, "nstep": 2, "gamma": 0.99, "eta": 0.9,
                        "double": True, "value_rescale": True,
                        "pack_frames": 4, "scan_state_dtype": "float32"},
    # bf16 matmuls against float32 at width 32: these only have to pass
    "tolerance": {"loss_rel": 0.2, "aux_rel": 0.2, "td_p50_over_mean": 0.5,
                  "td_p90_over_mean": None,
                  "grad_cosine": 0.9, "grad_cosine_leaf": 0.0,
                  "grad_norm_leaf_rel": 1.0, "moe_rows_rel": 0.2,
                  "gdn_state_rel": 0.05, "gdn_state_slow_rel": 0.05,
                  "attn_out_rel": 0.05, "why": "CPU rehearsal"},
}


def shipped():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "qwen3_next_pong.json")) as f:
        return json.load(f)


def test_the_cell_resolves_to_its_files():
    from benchmark.harness import manifest

    cell = manifest.load_cell("qwen3_next_pong.learner_only")
    assert cell.chips == 1 and cell.config["family"] == "qwen3_next"
    names = {m["name"] for m in cell.per_layer}
    assert {"phase_gdn_ms", "phase_gdn_chunk_ms",
            "moe_rows_computed_over_routed", "mfu", "step_device_ms",
            "phase_online_ms"} <= names
    # a benchmark PR's to extend: the closed lists of the first trunk
    assert not {"phase_ssm_ms", "phase_moe_ms", "phase_attn_ms",
                "gmm_roofline_share", "phase_embed_ms"} & names
    for m in cell.per_layer:
        reader = manifest.load_module("layer_metrics", m["name"])
        assert callable(reader.read)


def test_the_family_runs_its_cell_at_the_tiny_preset(tiny_root):
    add_cell(tiny_root, "tiny_qwen3_next.tiny_learner_only",
             "tiny_qwen3_next", "tiny_learner_only", 1,
             like="qwen3_next_pong.learner_only", config_body=TINY)
    proc = rehearse(tiny_root, "tiny_qwen3_next.tiny_learner_only", trace=1,
                    seconds=2.0)
    line, detail = last_line(proc), detail_of(proc)
    assert line["correct"] is True, detail["check"]
    check = detail["check"]
    assert check["failed"] == []
    # bf16 and float32 route a few boundary tokens differently
    assert all(abs(a - b) <= 3 for a, b in zip(
        check["moe"]["rows_here"], check["moe"]["rows_here_reference"]))
    assert check["grad"]["leaves"] > 30
    assert list(check["gdn_state"]["rel_err_by_layer"]) == ["0"] == list(
        check["gdn_state_slow"]["rel_err_by_layer"])
    assert list(check["attn_out"]["rel_err_by_layer"]) == ["2"]
    assert check["aux"]["program"] > 2e-3       # two expert blocks, each >= 1
    assert check["moe"]["rows_computed"] >= check["moe"]["rows_here_mean"]
    # the two phase_gdn*_ms read a device trace: nothing on the CPU
    assert "moe_rows_computed_over_routed" in detail["rehearsal_metric_names"]
    assert "phase_gdn_ms" not in detail["rehearsal_metric_names"]


def test_the_walk_is_the_models_own_pass():
    import jax
    import jax.numpy as jnp

    from benchmark.families import qwen3_next
    from pytorch_distributed_tpu.models.hybrid import PRESETS, HybridQModel

    c = PRESETS["tiny-qwen"]
    model = HybridQModel(action_space=6, state_shape=(4, 12, 12), window=64,
                         preset=c, norm_val=255.0)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 12, 12),
                                                         jnp.uint8))
    frames = jax.random.bits(jax.random.PRNGKey(1), (4, 64, 12, 12),
                             jnp.uint8)
    _, _, states = model.apply(params, frames, method=model.window_pass)
    walked, attended = qwen3_next.walk(model, params, frames)
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
    0.37 GFLOP a position, the delta-rule blocks 60 %, attention 19 %,
    experts 13 %, embed 8 %)."""
    from benchmark.families import qwen3_next

    cfg = shipped()
    per = qwen3_next.forward_flops(cfg["shapes"], 2048, 84 * 84, 6)
    d = 2048
    assert per["embed"] == 2 * 7056 * d
    gdn = (2 * d * 12288 + 2 * d * 64 + 2 * 4 * 8192       # projections, conv
           + 16 * 2 * 2 * 32 * 128                         # K K^T, Q K^T
           + 32 * (2 * 32 * 256 + 3 * 2 * 128 * 128 + 2 * 32 * 128)
           + 2 * 4096 * d)
    assert per["gdn"] == 3 * gdn
    assert per["attn"] == (2 * d * 8192 + 2 * 2 * d * 512 + 2 * 4096 * d
                           + 2 * 2 * 4096 * 1024)
    assert per["moe"] == 4 * (2 * d * 512 + 2 * d + 3 * 2 * d * 512
                              + 0.625 * 3 * 2 * d * 512)
    total = sum(per.values())
    assert 0.35e9 < total < 0.39e9
    assert 0.57 < per["gdn"] / total < 0.63
    # the recurrence itself is a small part of a delta-rule block
    assert (gdn - 2 * d * 12288 - 2 * 4096 * d) / gdn < 0.07
    flops = qwen3_next.update_flops(cfg["shapes"], (4, 84, 84), 6)
    assert flops == int(4 * 4 * 2048 * total)
    # every size under its published name, equal at the file's top level
    for key in qwen3_next.MODEL_KEYS:
        if key in cfg and key != "num_experts_published":
            assert cfg["shapes"][key] == cfg[key], key
    assert cfg["shapes"]["num_experts_published"] == cfg["published"][
        "num_experts"]
    assert cfg["tolerance"]["td_p90_over_mean"] is None


# -- the check's controls, at a small size ---------------------------------------

@pytest.fixture(scope="module")
def side(tmp_path_factory):
    """One program side at the tiny preset in float32, and the shipped
    configuration's limits around the tiny shapes."""
    import jax

    from benchmark.families import qwen3_next
    from benchmark.harness import manifest, program

    cfg = dict(TINY, overrides=dict(TINY["overrides"], batch_size=4,
                                    compute_dtype="float32"),
               shapes=dict(TINY_SHAPES, batch_size=4),
               tolerance=shipped()["tolerance"])
    opt = program.build_opt(cfg, 5, str(tmp_path_factory.mktemp("q")), "t",
                            num_actors=0, evaluator_nepisodes=0)
    lrn = program.build_learner(opt)
    # a zero head hides the trunk
    params = lrn.state.params
    params["params"]["head_w"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(1), params["params"]["head_w"].shape)
    program.fill_ring(lrn, 5, 8, qwen3_next)
    reference = manifest.load_module("reference", "qwen3_next")
    return qwen3_next.program_side(lrn, 5, reference), cfg, reference


def test_the_sound_comparison_is_inside_the_shipped_limits(side):
    from benchmark.families import qwen3_next

    got = qwen3_next.compare(*side)
    assert got["ok"] and got["failed"] == [], got


@pytest.mark.parametrize("control", [
    "no_beta", "no_decay", "no_qk_l2norm", "no_attn_gate", "no_rotary",
    "no_topk_renorm", "no_aux", "bf16_scan_state"])
def test_each_control_fails_the_limit_it_is_there_to_tell(side, control):
    from benchmark.families import qwen3_next

    program_side, cfg, reference = side
    if control == "bf16_scan_state":
        # the shipped limit stands between the CHIP's readings (bf16
        # matmuls, 2,048 positions); this program side is float32 over 16
        # positions: sound 2e-7, a bfloat16 state 3e-3
        cfg = dict(cfg, tolerance=dict(cfg["tolerance"],
                                       gdn_state_slow_rel=1e-3))
    got = qwen3_next.compare(program_side, cfg, reference,
                             **qwen3_next.CONTROLS[control])
    assert not got["ok"], (control, got)
    tells = {"no_aux": "aux_rel", "bf16_scan_state": "gdn_state_slow_rel",
             "no_beta": "gdn_state_rel", "no_decay": "gdn_state_rel",
             "no_qk_l2norm": "gdn_state_rel", "no_rotary": "attn_out_rel",
             "no_attn_gate": "attn_out_rel"}
    if control in tells:
        assert tells[control] in got["failed"], (control, got["failed"])
    if control == "no_aux":
        assert got["failed"] == ["aux_rel"] or "grad_cosine_leaf" in got[
            "failed"], got["failed"]


# -- the new readers ---------------------------------------------------------------

def _scoped_planes():
    from benchmark.harness import phases

    return phases.load(os.path.join(REPO, "benchmark", "testdata",
                                    "tiny_tpu_scoped.xplane.pb"))


def test_the_readers_on_the_recorded_scoped_trace():
    """Without ``model.gdn`` on any path: nothing.  With the recorded
    ``train.online`` paths rewritten to stand under the scopes: the ops'
    self time, the part under ``gdn.chunk`` no more than the whole."""
    from benchmark.harness import gdn_scopes, phases

    devices, window = _scoped_planes()
    step = ["jit_tiny_scoped_step"]
    assert gdn_scopes.per_update_ms(devices, window, step, 1) == {}
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

    whole = gdn_scopes.per_update_ms(
        rewritten(lambda _: "model.gdn/gdn.chunk"), window, step, 1)
    assert whole["gdn"] == pytest.approx(online) == pytest.approx(
        whole["gdn_chunk"])
    # only the backward's ops under gdn.chunk
    part = gdn_scopes.per_update_ms(rewritten(
        lambda path: "model.gdn/gdn.chunk" if "transpose(" in path
        else "model.gdn"), window, step, 1)
    assert part["gdn"] == pytest.approx(online)
    assert 0.0 < part["gdn_chunk"] < part["gdn"]
    # another model scope inside: not the delta rule's
    other = gdn_scopes.per_update_ms(
        rewritten(lambda _: "model.gdn/model.moe"), window, step, 1)
    assert other == {}


def test_an_op_is_filed_by_its_innermost_model_scope():
    from benchmark.harness.gdn_scopes import scopes_of

    path = ("jit(one)/train.online/transpose(jvp(HybridQModel.window_pass))"
            "/checkpoint/rematted_computation/model.gdn/gdn.chunk/dot_general:")
    assert scopes_of(path) == (True, True)
    assert scopes_of("jit(one)/train.target/model.gdn/mul:") == (True, False)
    assert scopes_of("jit(one)/model.gdn/gdn.chunk/while/body/model.gdn/"
                     "gdn.chunk/add:") == (True, True)
    assert scopes_of("jit(one)/model.gdn/x/model.attn/y") == (False, False)
    assert scopes_of("jit(one)/my_gdn.chunky/add") == (False, False)
    assert scopes_of(None) == (False, False)


def test_rows_computed_over_routed_reads_the_checks_counters():
    import types

    from benchmark.harness import gdn_scopes

    ctx = lambda check: types.SimpleNamespace(
        result=types.SimpleNamespace(check=check))
    assert gdn_scopes.rows_computed_over_routed(ctx({})) is None
    assert gdn_scopes.rows_computed_over_routed(ctx(
        {"moe": {"rows_here": [1.0]}})) is None
    assert gdn_scopes.rows_computed_over_routed(ctx(
        {"moe": {"rows_computed": 10240.0, "rows_here_mean": 5120.0}})) == 2.0
