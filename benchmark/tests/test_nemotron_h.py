"""CPU rehearsal of the ``nemotron_h`` family's cell at the tiny preset of
models/hybrid.py: the family's files drive the runner, its own ``agrees``
decides ``correct``, its readers are called; and the FLOPs count of the
shipped configuration against the issue's hand count."""

import json
import os

from conftest import REPO, add_cell, rehearse
from test_cells import detail_of, last_line

TINY_SHAPES = {
    "batch_size": 2, "seq_len": 15, "burn_in": 4, "state_shape": [4, 84, 84],
    "hybrid_override_pattern": "ME*E", "hidden_size": 32,
    "mamba_num_heads": 4, "mamba_head_dim": 8, "ssm_state_size": 8,
    "n_groups": 2, "conv_kernel": 4, "chunk_size": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "n_routed_experts_published": 16, "n_routed_experts": 4,
    "first_expert": 0, "num_experts_per_tok": 3,
    "moe_intermediate_size": 16, "moe_shared_expert_intermediate_size": 24,
    "routed_scaling_factor": 2.5, "norm_eps": 1e-5,
    "mlp_hidden_act": "relu2"}
TINY = {
    "row": 20, "family": "nemotron_h", "fill_chunk": 8,
    "overrides": {"hybrid_preset": "tiny", "batch_size": 2, "seq_len": 15,
                  "seq_overlap": 7, "burn_in": 4, "nstep": 2,
                  "memory_size": 128, "steps_per_dispatch": 1},
    "shapes": TINY_SHAPES, "reference": "nemotron_h",
    "reference_hyper": {"burn_in": 4, "nstep": 2, "gamma": 0.99, "eta": 0.9,
                        "double": True, "value_rescale": True,
                        "pack_frames": 4, "scan_state_dtype": "float32"},
    # bf16 matmuls against float32 at width 32: these only have to pass
    "tolerance": {"loss_rel": 0.2, "td_p50_over_mean": 0.5,
                  "td_p90_over_mean": 1.0,
                  "grad_cosine": 0.9, "grad_cosine_leaf": 0.0,
                  "grad_norm_leaf_rel": 1.0, "moe_rows_rel": 0.2,
                  "ssm_state_rel": 0.05, "why": "CPU rehearsal"},
}


def test_the_family_runs_its_cell_at_the_tiny_preset(tiny_root):
    add_cell(tiny_root, "tiny_nemotron_h.tiny_learner_only",
             "tiny_nemotron_h", "tiny_learner_only", 1,
             like="nemotron_h_pong.learner_only", config_body=TINY)
    proc = rehearse(tiny_root, "tiny_nemotron_h.tiny_learner_only", trace=1,
                    seconds=2.0)
    line, detail = last_line(proc), detail_of(proc)
    assert line["correct"] is True, detail["check"]
    check = detail["check"]
    # bf16 and float32 route a few boundary tokens differently
    assert all(abs(a - b) <= 3 for a, b in zip(
        check["moe"]["rows_here"], check["moe"]["rows_here_reference"]))
    assert check["grad"]["leaves"] > 30
    assert list(check["ssm_state"]["rel_err_by_layer"]) == ["0"]
    # the three phase_*_ms read a device trace: nothing on the CPU
    assert "moe_load_max_over_mean" in detail["rehearsal_metric_names"]


def test_the_walk_is_the_models_own_pass():
    """``walk`` reports ``window_pass``'s own states, and each M layer's
    input beside them."""
    import jax
    import jax.numpy as jnp

    from benchmark.families import nemotron_h
    from pytorch_distributed_tpu.models.hybrid import PRESETS, HybridQModel

    c = PRESETS["tiny"]
    model = HybridQModel(action_space=6, state_shape=(4, 12, 12), window=64,
                         preset=c, norm_val=255.0)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 12, 12),
                                                         jnp.uint8))
    frames = jax.random.bits(jax.random.PRNGKey(1), (4, 64, 12, 12),
                             jnp.uint8)
    _, _, states = model.apply(params, frames, method=model.window_pass)
    walked = nemotron_h.walk(model, params, frames)
    assert list(walked) == list(states) == [0]
    for i in states:
        assert jnp.array_equal(states[i], walked[i][1])
        assert walked[i][0].shape == (4, 64, c.d_model)


def test_the_flops_count_of_the_shipped_configuration():
    from benchmark.families import nemotron_h

    with open(os.path.join(REPO, "benchmark", "configs",
                           "nemotron_h_pong.json")) as f:
        cfg = json.load(f)
    per = nemotron_h.forward_flops(cfg["shapes"], 2048, 84 * 84, 6)
    total = sum(per.values())
    assert 0.55e9 < total < 0.65e9              # the issue: about 0.59 GFLOP
    assert per["ssm"] / total > 0.5             # and over half of it there
    assert per["attn"] / total < 0.12
    flops = nemotron_h.update_flops(cfg["shapes"], (4, 84, 84), 6)
    assert flops == int(4 * 4 * 2048 * total)
    # every size under its published name, equal at the file's top level
    for key in nemotron_h.MODEL_KEYS:
        if key in cfg and key != "n_routed_experts_published":
            assert cfg["shapes"][key] == cfg[key], key
    assert cfg["shapes"]["n_routed_experts_published"] == cfg[
        "published"]["n_routed_experts"]


def test_an_op_is_filed_under_the_innermost_model_scope():
    from benchmark.harness.model_scopes import part_of

    path = ("jit(one)/train.online/transpose(jvp(HybridQModel.window_pass))"
            "/checkpoint/rematted_computation/model.moe/moe.shared/dot_general:")
    assert part_of(path) == "moe"
    assert part_of("jit(one)/train.target/model.ssm/while/body/model.ssm/mul:"
                   ) == "ssm"
    assert part_of("jit(one)/train.online/model.moe/x/model.attn/y") == "attn"
    assert part_of("jit(one)/train.optimizer/add") is None
    assert part_of("jit(one)/my_model.ssm_like/add") is None
    # the compiler's grouped matmul keeps no path: filed by its name
    assert part_of(None, "%ragged-dot-none.83 = f32[12288,2688]{1,0} "
                         "custom-call(...)") == "moe"
    assert part_of(None, "%fusion.12 = f32[4] fusion(...)") is None


def test_the_models_parts_of_a_hand_made_trace():
    """Two whole step events of 10 us; per update: 3 us under model.ssm, 2
    us of a grouped matmul without a path (moe's), 1 us of the optimizer
    (no part).  An op outside the steps does not count."""
    from benchmark.harness import model_scopes, phases

    meta = {1: phases.OpMeta(name="jit_one(7)"),
            2: phases.OpMeta(name="%fusion.1 = f32[2] fusion()",
                             tf_op="jit(one)/train.online/model.ssm/mul:"),
            3: phases.OpMeta(name="%ragged-dot-none.4 = f32[8] custom-call()"),
            4: phases.OpMeta(name="%fusion.2 = f32[2] fusion()",
                             tf_op="jit(one)/train.optimizer/add:")}
    ops = []
    for t0 in (0.0, 20e3):
        ops += [(2, t0 + 1e3, t0 + 4e3), (3, t0 + 4e3, t0 + 6e3),
                (4, t0 + 6e3, t0 + 7e3)]
    ops.append((2, 50e3, 51e3))
    plane = phases.DevicePlane(
        name="/device:TPU:0", ops=ops, meta=meta,
        modules=[(1, 0.0, 10e3), (1, 20e3, 30e3)])
    got = model_scopes.per_update_ms([plane], None, ["jit_one"], 1)
    assert got == {"ssm": 3e-3, "moe": 2e-3}
    # a program that names no model scope: nothing, the grouped matmul too
    meta[2] = phases.OpMeta(name="%fusion.1 = f32[2] fusion()",
                            tf_op="jit(one)/train.online/mul:")
    assert model_scopes.per_update_ms([plane], None, ["jit_one"], 1) == {}


def test_a_kernels_time_and_calls_and_its_least_time():
    """Ops named ``gmm`` / ``gmm.<n>`` are the kernel's calls; ``tgmm`` is
    another kernel; the least time of a call comes from shapes alone."""
    from benchmark.harness import kernel_counts, model_scopes, phases

    meta = {1: phases.OpMeta(name="jit_one(7)"),
            2: phases.OpMeta(name="%gmm.2 = f32[8,4] custom-call()",
                             tf_op="jit(one)/model.moe/jit(gmm)/pallas_call"),
            3: phases.OpMeta(name="%tgmm = bf16[2,4,4] custom-call()"),
            4: phases.OpMeta(name="%gmm_like.1 = f32[2] fusion()")}
    ops = [(2, 1e3, 3e3), (2, 3e3, 4e3), (3, 4e3, 8e3), (4, 8e3, 9e3)]
    plane = phases.DevicePlane(name="/device:TPU:0", ops=ops, meta=meta,
                               modules=[(1, 1e3, 9e3)])
    at = lambda k: model_scopes.kernel_per_update([plane], None,
                                                  ["jit_one"], 1, k)
    assert at("gmm") == (3e-3, 2.0) and at("tgmm") == (4e-3, 1.0)
    assert at("mm") is None
    flops, nbytes = kernel_counts.grouped_matmul(3072, 2688, 1856, 8, 4)
    assert flops == 2 * 3072 * 2688 * 1856
    assert nbytes == 2 * 3072 * 2688 + 2 * 8 * 2688 * 1856 + 4 * 3072 * 1856
    with open(os.path.join(REPO, "benchmark", "configs",
                           "nemotron_h_pong.json")) as f:
        mix = kernel_counts.expert_layer_calls(json.load(f)["shapes"])
    assert len(mix["gmm"]) == 8 and len(mix["tgmm"]) == 2
    assert all(f == flops for f, _ in mix["gmm"] + mix["tgmm"])
