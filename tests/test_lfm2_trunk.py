"""The gated short-convolution / grouped-query / sigmoid-routed SwiGLU-expert
trunk (models/hybrid.py PRESETS["lfm2-moe-5"], CONFIGS row 23) against its
plain float32 reference (tests/reference/lfm2_moe.py) at the tiny preset on
the CPU: the short convolution, window and step, the attention with query /
key norms and rotary on the whole head, each block kind, the experts' shares
with no shared expert, the acting carry, the wiring.  The fused update and
the check's power to tell a wrong term: tests/test_lfm2_trunk_update.py."""

import dataclasses
import filecmp
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu import factory
from pytorch_distributed_tpu.config import build_options
from pytorch_distributed_tpu.models import hybrid
from pytorch_distributed_tpu.models.hybrid import PRESETS, HybridQModel
from reference import lfm2_moe as reference
from test_hybrid import build as build_hybrid, frames_of
from test_kimi_trunk import stirred

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = PRESETS["tiny-lfm2"]
HYPER = {"burn_in": 4, "nstep": 2, "gamma": 0.99, "eta": 0.9, "double": True,
         "value_rescale": True, "pack_frames": 4}
# float32 on the CPU against the float32 reference: what is left is the
# order of summation (and the step's einsum against the window's shifted
# sums), 1e-6 of a value; the tolerances leave a hundred times that
RTOL, ATOL = 1e-4, 1e-5


def model_hyper(c=TINY, **changed):
    """The preset under the names the reference reads."""
    return dict(dict(
        pattern=c.pattern, hidden_size=c.d_model,
        conv_L_cache=c.conv_kernel, num_attention_heads=c.attn_heads,
        num_key_value_heads=c.kv_heads, head_dim=c.attn_head_dim,
        rope_theta=c.rope_theta, num_experts_per_tok=c.top_k,
        norm_topk_prob=True, routed_scaling_factor=c.route_scale,
        router_eps=c.route_eps, norm_eps=c.norm_eps,
        first_expert=c.first_expert), **changed)


def build(pattern=TINY.pattern, window=18, **kw):
    """test_hybrid's model of this preset, its norms stirred: a one hides a
    factor."""
    c, model, params = build_hybrid(pattern, window, base=TINY, **kw)
    return c, model, stirred(params)


def _layer(kind, c=TINY, T=14, B=2, seed=5):
    """A block's parameters (norms stirred) and normed inputs."""
    specs = hybrid.layer_param_specs(kind, c)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(specs) + 1)
    p = {name: init(k, shape) for k, (name, (init, shape)) in zip(
        keys, specs.items())}
    return stirred(p), jax.random.normal(keys[-1], (B, T, c.d_model))


def _reference(kind, p, u, c=TINY):
    with jax.default_matmul_precision("highest"):
        return reference.block_outputs(p, u, kind, model_hyper(c))


# -- (a) the gated short convolution ------------------------------------------

def test_a_short_convolution_block_has_three_projections_and_three_taps():
    p, _ = _layer("C")
    assert {k: v.shape for k, v in p.items()} == {
        "w_in": (32, 96), "conv_w": (3, 32), "w_out": (32, 32)}
    c = PRESETS["lfm2-moe-5"]
    shapes = {k: s for k, (_, s) in hybrid.layer_param_specs("C", c).items()}
    assert shapes == {"w_in": (2048, 6144), "conv_w": (3, 2048),
                      "w_out": (2048, 2048)}


@pytest.mark.parametrize("what", ["output", "gradient"])
def test_the_short_convolution_window_is_the_reference(what):
    p, u = _layer("C")
    window = lambda p, u: hybrid.short_conv_window(p, u, TINY, jnp.float32)
    if what == "output":
        np.testing.assert_allclose(jax.jit(window)(p, u),
                                   _reference("C", p, u), rtol=RTOL,
                                   atol=ATOL)
        return
    scalar = lambda f: lambda p, u: jnp.sum(jnp.sin(f(p, u)))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(scalar(lambda p, u: jax.vmap(
            lambda seg: reference.short_conv(p, seg, model_hyper()))(u)),
            argnums=(0, 1)))(p, u)
    got = jax.jit(jax.grad(scalar(window), argnums=(0, 1)))(p, u)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=RTOL, atol=ATOL)


def test_the_convolution_is_causal_and_three_taps_long():
    """A change at position 6 moves the outputs at 6, 7 and 8 and no
    other: zero before t = 0, nothing read from later positions."""
    p, u = _layer("C", B=1)
    out = hybrid.short_conv_window(p, u, TINY, jnp.float32)
    moved = hybrid.short_conv_window(p, u.at[:, 6].add(1.0), TINY,
                                     jnp.float32)
    changed = np.flatnonzero(np.any(np.asarray(out != moved), axis=(0, 2)))
    assert changed.tolist() == [6, 7, 8]


def test_short_conv_step_over_the_positions_is_the_window_pass():
    p, u = _layer("C")
    window = jax.jit(lambda p, u: hybrid.short_conv_window(
        p, u, TINY, jnp.float32))(p, u)

    @jax.jit
    def stepwise(p, u):
        tail = jnp.zeros((u.shape[0], TINY.conv_kernel - 1, TINY.d_model))
        out = []
        for t in range(u.shape[1]):
            o, tail = hybrid.short_conv_step(p, u[:, t], tail, TINY,
                                             jnp.float32)
            out.append(o)
        return jnp.stack(out, axis=1), tail

    got, tail = stepwise(p, u)
    np.testing.assert_allclose(got, window, rtol=RTOL, atol=ATOL)
    # the carry is the last two rows of B * x
    gate_b, _, x = jnp.split(u[:, -2:] @ p["w_in"], 3, axis=-1)
    np.testing.assert_allclose(tail, gate_b * x, rtol=RTOL, atol=ATOL)


# -- (b) attention: query / key norms, rotary on the whole head ---------------

def test_the_attention_window_is_the_references_full_softmax():
    p, u = _layer("*")
    assert TINY.rotary_dim == TINY.attn_head_dim and TINY.qk_norm
    got = jax.jit(lambda p, u: hybrid.attention_window(
        p, u, TINY, jnp.float32))(p, u)
    np.testing.assert_allclose(got, _reference("*", p, u), rtol=RTOL,
                               atol=ATOL)
    # each of the two changes moves the output: both are in
    for wrong in ("no_qk_norm", "half_rotary"):
        with jax.default_matmul_precision("highest"):
            other = reference.block_outputs(p, u, "*", model_hyper(
                wrong=(wrong,)))
        assert float(jnp.max(jnp.abs(other - got))) > 1e-2, wrong


# -- (c) the blocks and the whole model against the reference -----------------

@pytest.mark.parametrize("pattern", ["C", "*", "F", "E", "CF*ECE"])
def test_window_q_is_the_reference(pattern):
    c, model, params = build(pattern)
    frames = frames_of(2, 3, 17)
    q, load, states = jax.jit(lambda p, f: model.apply(
        p, f, method=model.window_pass))(params, frames)
    with jax.default_matmul_precision("highest"):
        q_ref = jax.jit(lambda p, f: reference.window_q(
            p, f, model_hyper(c), 255.0))(params, frames)
    load_ref = reference.window_loads(params, frames, model_hyper(c), 255.0)
    np.testing.assert_allclose(q, q_ref, rtol=RTOL, atol=1e-4)
    assert list(load) == [i for i, kind in enumerate(pattern) if kind == "E"]
    assert states == {}                  # no recurrent state in this trunk
    for n, n_ref in zip(load.values(), load_ref):
        assert np.array_equal(n, n_ref) and int(jnp.sum(n)) == 3 * 17 * c.top_k


def test_acting_step_by_step_is_window_q_with_an_early_reset():
    c, model, params = build()
    T = model.act_window                           # 17 trained positions
    frames = frames_of(7, 2, T)
    stack = lambda f, t: jnp.stack([f[:, max(t - 3 + j, 0)]
                                    for j in range(4)], axis=1)
    window_q = jax.jit(lambda p, f: model.apply(p, f, method=model.window_q))
    q_full, q_late = window_q(params, frames), window_q(params,
                                                        frames[1:, 5:])
    zero = model.zero_carry(1)
    carry = model.zero_carry(2)
    # per C block the last two rows of B * x, per * block keys and values
    # (rotated at their own position), the count
    assert [leaf.shape[1:] for leaf in carry] == [
        (2, 32), (T, 2, 8), (T, 2, 8), (2, 32), ()]
    act = jax.jit(model.apply)
    for t in range(T):
        if t == 5:
            carry = tuple(c_.at[1].set(z[0]) for c_, z in zip(carry, zero))
        obs = stack(frames, t)
        if t >= 5:
            obs = obs.at[1].set(stack(frames[1:, 5:], t - 5)[0])
        q, carry = act(params, obs, carry)
        np.testing.assert_allclose(q[0], q_full[0, t], rtol=RTOL, atol=1e-4)
        want = q_full[1, t] if t < 5 else q_late[0, t - 5]
        np.testing.assert_allclose(q[1], want, rtol=RTOL, atol=1e-4)
    assert all(leaf.shape[0] == 2 for leaf in carry)    # the actor's contract


def test_the_published_carry_is_two_rows_a_conv_block_and_a_key_ring():
    c = PRESETS["lfm2-moe-5"]
    model = HybridQModel(action_space=6, state_shape=(4, 84, 84),
                         window=2048, preset=c)
    carry = jax.eval_shape(lambda: model.zero_carry(1))
    per_block = {"C": [(2, 2048)], "*": [(2047, 8, 64)] * 2, "F": [],
                 "E": []}
    assert [leaf.shape[1:] for leaf in carry] == [
        s for kind in c.pattern for s in per_block[kind]] + [()]
    tails = [leaf for leaf in carry if leaf.shape[1:] == (2, 2048)]
    assert len(tails) == 4 and all(t.dtype == jnp.float32 for t in tails)


# -- (d) the experts ----------------------------------------------------------

def _expert_layer(c, full=None, seed=3):
    if full is None:
        specs = hybrid.layer_param_specs("E", c)
        keys = jax.random.split(jax.random.PRNGKey(seed), len(specs))
        p = {name: init(k, shape) for k, (name, (init, shape)) in zip(
            keys, specs.items())}
        return dict(p, b_sel=10.0 * p["b_sel"])     # so that it decides
    held = slice(c.first_expert, c.first_expert + c.experts_held)
    return dict(full, **{name: full[name][held]
                         for name in ("w_gate", "w_up", "w_down")})


def test_no_shared_expert_puts_no_shared_parameters_in_the_tree():
    p = _expert_layer(TINY)
    assert sorted(p) == ["b_sel", "router", "w_down", "w_gate", "w_up"]
    c = PRESETS["lfm2-moe-5"]
    model = HybridQModel(action_space=6, state_shape=(4, 84, 84),
                         window=2048, preset=c)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4, 84, 84), jnp.uint8)))
    names = {getattr(path[-1], "key", "") for path, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert not {n for n in names if "shared" in n}
    # and the layer's program has no shared expert's work in it
    u = jax.ShapeDtypeStruct((40, TINY.d_model), jnp.float32)
    text = jax.jit(lambda p, u: hybrid.moe_apply(p, u, TINY, jnp.float32)
                   ).lower(p, u).as_text(debug_info=True)
    assert "moe.experts" in text and "moe.shared" not in text


def test_the_expert_block_is_the_reference():
    """Sigmoid scores selected with ``b_sel`` and weighed without it,
    divided by their sum + 1e-6; SwiGLU experts; no shared expert."""
    p = _expert_layer(TINY)
    u = jax.random.normal(jax.random.PRNGKey(4), (40, TINY.d_model))
    out, load = hybrid.moe_apply(p, u, TINY, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, rows, load_ref = reference.experts(p, u, model_hyper())
    np.testing.assert_allclose(out, want, rtol=RTOL, atol=ATOL)
    assert np.array_equal(load, load_ref)
    assert int(jnp.sum(hybrid.held_load(load, TINY))) == int(rows) > 0
    # a selection bias moves the choice and not the weights
    pushed = dict(p, b_sel=p["b_sel"].at[0].add(10.0))
    _, load_pushed = hybrid.moe_apply(pushed, u, TINY, jnp.float32)
    assert int(load_pushed[0]) == 40 > int(load[0])


def test_the_weights_are_divided_by_their_sum_and_the_router_eps():
    """``route_eps`` as the published router has it: the chosen weights sum
    to 1 / (1 + eps / their sum), not to 1."""
    p = _expert_layer(TINY)
    u = jax.random.normal(jax.random.PRNGKey(4), (40, TINY.d_model))
    for eps in (1e-6, 0.5):
        c = dataclasses.replace(TINY, route_eps=eps)
        _, w, _ = hybrid.route(p, u, c)
        s = jax.nn.sigmoid(u @ p["router"])
        chosen = jax.lax.top_k(s + p["b_sel"], c.top_k)[1]
        total = jnp.sum(jnp.take_along_axis(s, chosen, axis=-1), axis=-1)
        np.testing.assert_allclose(jnp.sum(w, axis=-1),
                                   total / (total + eps), rtol=1e-5)


@pytest.mark.parametrize("n_experts,top_k,held", [(16, 4, 4), (32, 4, 8)])
def test_the_shares_add_up_to_the_uncut_layer(n_experts, top_k, held):
    """The guide's share test, with no shared expert to count once: the
    tiny preset's 16 experts as four shares of 4, and the published counts
    (32 experts top-4, 8 held a chip: the 4 chips of a layer) at a small
    width.  Every share's routed part, summed, is what the reference gives
    for the layer with every expert held."""
    whole = dataclasses.replace(TINY, n_experts=n_experts, top_k=top_k,
                                experts_held=n_experts)
    full = _expert_layer(whole)
    u = jax.random.normal(jax.random.PRNGKey(4), (64, TINY.d_model))
    with jax.default_matmul_precision("highest"):
        want, rows_whole, _ = reference.experts(full, u, model_hyper(whole))
    share = jax.jit(lambda p, c: hybrid.moe_apply(p, u, c, jnp.float32),
                    static_argnums=1)
    total, rows = 0.0, 0
    for first in range(0, n_experts, held):
        c = dataclasses.replace(whole, experts_held=held, first_expert=first)
        out, load = share(_expert_layer(c, full), c)
        total = total + out
        rows += int(jnp.sum(hybrid.held_load(load, c)))
    np.testing.assert_allclose(total, want, rtol=RTOL, atol=ATOL)
    assert rows == int(rows_whole) == 64 * top_k


# -- wiring -------------------------------------------------------------------

def test_row_23_builds_through_the_factory():
    opt = build_options(23)
    assert opt.model_params.hybrid_preset == "lfm2-moe-5"
    opt = build_options(23, hybrid_preset="tiny-lfm2")
    assert (opt.agent_type, opt.memory_type, opt.model_type) == (
        "r2d2", "device-sequence", "dtqn-hybrid")
    assert factory.sequence_pack_frames(opt) == 4
    assert factory.lstm_dim_of(opt) == 1
    assert factory.resolve_steps_per_dispatch(opt) == 1
    model = factory.build_model(opt, factory.probe_env(opt))
    assert model.preset is PRESETS["tiny-lfm2"]
    # the sigmoid router steps its selection bias after the optimizer
    assert model.train_parts(4)[2] is not None


def test_the_published_preset_is_the_configuration_file_and_the_catalog():
    """Widths live in ONE place in the program; the benchmark's file states
    the same numbers under their published names, at its top level and in
    ``shapes``, and the layers it keeps are the published list's 1-5."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "lfm2_moe_pong.json")) as f:
        cfg = json.load(f)
    c = PRESETS[cfg["overrides"]["hybrid_preset"]]
    want = model_hyper(c)
    want.update(layer_pattern=want.pop("pattern"),
                intermediate_size=c.mlp_width, num_experts=c.experts_held,
                moe_intermediate_size=c.expert_width)
    for key, value in want.items():
        assert cfg["shapes"][key] == value, key
        if key in cfg:
            assert cfg[key] == value, key
    assert c.attn_head_dim * c.attn_heads == c.d_model == 2048
    assert c.rotary_dim == c.attn_head_dim and c.qk_norm and not c.attn_gate
    assert c.shared_width == 0 and c.router == "sigmoid" and c.gated_experts
    assert cfg["published"]["num_experts"] == c.n_experts == 32 \
        == cfg["shapes"]["num_experts_published"]
    # a published layer is a mixer block and a feed-forward block: layers
    # 1-5 of the published list, the second of them dense
    assert 2 * cfg["num_hidden_layers"] == len(c.pattern) == 10
    mixers, ffns = c.pattern[::2], c.pattern[1::2]
    kind = {"conv": "C", "full_attention": "*"}
    assert mixers == "".join(kind[t] for t in cfg["layer_types"])
    assert ffns == "F" + "E" * 4
    assert cfg["published"]["num_dense_layers"] == cfg["num_dense_layers"] \
        == 2
    assert cfg["published"]["layer_types"][1:6] == cfg["layer_types"]
    assert len(cfg["published"]["layer_types"]) == cfg["published"][
        "num_hidden_layers"] == 24
    # 488.7 M parameters, 14 bytes of train state each
    shapes = jax.eval_shape(lambda: HybridQModel(
        action_space=6, state_shape=(4, 84, 84), window=2048, preset=c).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4, 84, 84), jnp.uint8)))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert round(n / 1e5) == cfg["parameters_1e5"]


def test_the_two_copies_of_the_reference_are_identical():
    assert filecmp.cmp(
        os.path.join(REPO, "tests", "reference", "lfm2_moe.py"),
        os.path.join(REPO, "benchmark", "reference", "lfm2_moe.py"),
        shallow=False)
    with open(os.path.join(REPO, "tests", "reference", "lfm2_moe.py")) as f:
        assert "pytorch_distributed_tpu" not in f.read().split('"""')[2]
