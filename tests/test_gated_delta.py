"""The gated-delta-rule / softmax-routed gated-expert / gated-attention trunk
(models/hybrid.py PRESETS["qwen3-next-4"], CONFIGS row 21) against its plain
float32 reference (tests/reference/qwen3_next.py) at the tiny preset on the
CPU: the chunked delta rule, each layer kind, the experts' shares, the
acting carry, the wiring.  The fused update with its balance loss and the
check's power to tell a wrong term: tests/test_gated_delta_update.py."""

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
from pytorch_distributed_tpu.models import gated_delta, hybrid
from pytorch_distributed_tpu.models.hybrid import PRESETS, HybridQModel
from reference import qwen3_next as reference
from test_hybrid import build as build_hybrid, frames_of

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = PRESETS["tiny-qwen"]
FRAME = (4, 12, 12)
HYPER = {"burn_in": 4, "nstep": 2, "gamma": 0.99, "eta": 0.9, "double": True,
         "value_rescale": True, "pack_frames": 4}


def model_hyper(c=TINY, **changed):
    """The preset under the names the reference reads."""
    return dict(dict(
        pattern=c.pattern, linear_num_key_heads=c.gdn_k_heads,
        linear_num_value_heads=c.gdn_v_heads,
        linear_key_head_dim=c.gdn_head_dim,
        linear_value_head_dim=c.gdn_head_dim,
        num_attention_heads=c.attn_heads, num_key_value_heads=c.kv_heads,
        head_dim=c.attn_head_dim,
        partial_rotary_factor=c.rotary_dim / c.attn_head_dim,
        rope_theta=c.rope_theta, num_experts_per_tok=c.top_k,
        norm_topk_prob=True, rms_norm_eps=c.norm_eps,
        router_aux_loss_coef=c.aux_weight, first_expert=c.first_expert),
        **changed)


def build(pattern=TINY.pattern, window=18, **kw):
    """test_hybrid's model of this preset, its norm parameters stirred: they
    start at zero, and a zero hides a scale that is not 1 + w."""
    c, model, params = build_hybrid(pattern, window, base=TINY, **kw)
    key = jax.random.PRNGKey(1)

    def stir(path, leaf):
        name = getattr(path[-1], "key", "")
        if name.endswith("norm") and name != "gate_norm":
            return 0.2 * jax.random.normal(
                jax.random.fold_in(key, hash(str(path)) % 2 ** 31),
                leaf.shape)
        return leaf

    return c, model, jax.tree_util.tree_map_with_path(stir, params)


# -- (a) the chunked delta rule ---------------------------------------------------

def _delta_layer(T=14, B=2, seed=5):
    """A D layer's parameters and normed inputs, T = 3.5 chunks of 4."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 9)
    p = {name: init(k, shape) for k, (name, (init, shape)) in zip(
        keys, hybrid.layer_param_specs("D", TINY).items())}
    return p, jax.random.normal(keys[8], (B, T, TINY.d_model))


def _stepwise(p, u):
    """The acting path's one-position step over the window."""
    B = u.shape[0]
    tail = jnp.zeros((B, TINY.conv_kernel - 1, TINY.gdn_conv_dim))
    S = jnp.zeros((B, TINY.gdn_v_heads, TINY.gdn_head_dim,
                   TINY.gdn_head_dim))
    out = []
    for t in range(u.shape[1]):
        o, tail, S = hybrid.gdn_step(p, u[:, t], tail, S, TINY, jnp.float32)
        out.append(o)
    return jnp.stack(out, axis=1), S


@pytest.mark.parametrize("what", ["values", "final_state", "gradients"])
def test_chunked_delta_rule_is_the_recurrence_and_the_reference(what):
    p, u = _delta_layer()
    chunked = lambda p, u: hybrid.gdn_window(p, u, TINY, jnp.float32)[:2]
    stepwise = jax.jit(_stepwise)

    def ref(p, u):
        with jax.default_matmul_precision("highest"):
            out = [reference.delta_rule(p, seg, model_hyper()) for seg in u]
        return (jnp.stack([o for o, _ in out]),
                jnp.stack([S for _, S in out]))

    if what == "gradients":
        scalar = lambda f: lambda p, u: (
            jnp.sum(jnp.sin(f(p, u)[0])) + jnp.sum(jnp.square(f(p, u)[1])))
        grads = [jax.jit(jax.grad(scalar(f), argnums=(0, 1)))(p, u)
                 for f in (chunked, _stepwise, ref)]
        for other in grads[1:]:
            for a, b in zip(jax.tree_util.tree_leaves(grads[0]),
                            jax.tree_util.tree_leaves(other)):
                np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
    else:
        at = 0 if what == "values" else 1
        got = jax.jit(chunked)(p, u)[at]
        for other in (stepwise, jax.jit(ref)):
            np.testing.assert_allclose(got, other(p, u)[at], rtol=2e-4,
                                       atol=2e-5)


def test_the_inverse_of_a_unit_lower_triangle_and_its_cotangent():
    A = 0.3 * jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 16, 16)),
                       -1)
    inv = gated_delta.unit_lower_inverse(A)
    np.testing.assert_allclose(
        jnp.matmul(jnp.eye(16) + A, inv, precision="highest"),
        jnp.broadcast_to(jnp.eye(16), A.shape), atol=2e-4)
    # its own cotangent against differentiating through the products, where
    # A lives: below the diagonal (off it the products are no inverse)
    f = lambda inverse: jnp.tril(jax.grad(
        lambda A: jnp.sum(jnp.sin(inverse(A))))(A), -1)
    np.testing.assert_allclose(f(gated_delta.unit_lower_inverse),
                               f(gated_delta._inverse_by_products),
                               rtol=1e-3, atol=1e-3)


# -- window_q against the reference -----------------------------------------------

@pytest.mark.parametrize("pattern", ["D", "E", "*", "DE*E"])
def test_window_q_is_the_reference(pattern):
    c, model, params = build(pattern)
    frames = frames_of(2, 3, 17)                   # 4.25 chunks of 4
    q, load, states = jax.jit(lambda p, f: model.apply(
        p, f, method=model.window_pass))(params, frames)
    with jax.default_matmul_precision("highest"):
        q_ref = jax.jit(lambda p, f: reference.window_q(
            p, f, model_hyper(c), 255.0))(params, frames)
    load_ref, states_ref = reference.window_states(params, frames,
                                                   model_hyper(c), 255.0)
    np.testing.assert_allclose(q, q_ref, rtol=1e-4, atol=1e-4)
    assert list(load) == [i for i, kind in enumerate(pattern) if kind == "E"]
    assert list(states) == [i for i, kind in enumerate(pattern)
                            if kind == "D"]
    for n, n_ref in zip(load.values(), load_ref):
        assert np.array_equal(n, n_ref) and int(jnp.sum(n)) == 3 * 17 * c.top_k
    for S, S_ref in zip(states.values(), states_ref):
        np.testing.assert_allclose(S, S_ref, rtol=1e-4, atol=1e-5)


# -- (b) acting through the carry ---------------------------------------------------

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
    # per D layer a conv tail and a float32 state, per * layer keys and
    # values, the count
    assert [leaf.shape[1:] for leaf in carry] == [
        (3, TINY.gdn_conv_dim), (4, 8, 8), (T, 2, 8), (T, 2, 8), ()]
    act = jax.jit(model.apply)
    for t in range(T):
        if t == 5:
            carry = tuple(c_.at[1].set(z[0]) for c_, z in zip(carry, zero))
        obs = stack(frames, t)
        if t >= 5:
            obs = obs.at[1].set(stack(frames[1:, 5:], t - 5)[0])
        q, carry = act(params, obs, carry)
        np.testing.assert_allclose(q[0], q_full[0, t], rtol=1e-4, atol=1e-4)
        want = q_full[1, t] if t < 5 else q_late[0, t - 5]
        np.testing.assert_allclose(q[1], want, rtol=1e-4, atol=1e-4)
    assert all(leaf.shape[0] == 2 for leaf in carry)    # the actor's contract


def test_the_key_ring_holds_keys_rotated_at_their_own_position():
    """Past the ring's length the carry's count keeps counting: a key
    written at position 20 into slot 20 % W is rotated by 20, so what a
    query sees depends on the distance alone."""
    c, model, params = build("*")
    p = params["params"]["layers_0"]
    u = jax.random.normal(jax.random.PRNGKey(3), (1, TINY.d_model))
    q0, k0, _, _ = hybrid._qkv(p, u, jnp.array([0]), c, jnp.float32)
    q9, k9, _, _ = hybrid._qkv(p, u, jnp.array([9]), c, jnp.float32)
    assert not np.allclose(k0, k9)
    np.testing.assert_allclose(jnp.einsum("bhd,bhd->bh", q0[:, :2], k0),
                               jnp.einsum("bhd,bhd->bh", q9[:, :2], k9),
                               rtol=1e-4, atol=1e-5)
    # the rotation leaves the dimensions past rotary_dim alone
    np.testing.assert_allclose(k0[..., c.rotary_dim:], k9[..., c.rotary_dim:])


# -- (d), (e), (f) the experts ----------------------------------------------------------

def _expert_layer(n_held, first, full=None, seed=3):
    c = dataclasses.replace(TINY, experts_held=n_held, first_expert=first)
    if full is None:
        keys = jax.random.split(jax.random.PRNGKey(seed), 8)
        return c, {name: init(k, shape) for k, (name, (init, shape)) in zip(
            keys, hybrid.layer_param_specs("E", c).items())}
    return c, dict(full, **{name: full[name][first:first + n_held]
                            for name in ("w_gate", "w_up", "w_down")})


def test_the_shares_add_up_to_the_uncut_layer():
    """Sum over all shares of the routed part + the gated shared expert
    ONCE = what the reference gives for the layer with every expert held."""
    _, full = _expert_layer(TINY.n_experts, 0)
    u = jax.random.normal(jax.random.PRNGKey(4), (40, TINY.d_model))
    with jax.default_matmul_precision("highest"):
        whole, rows_whole, _, _ = reference.experts(full, u, model_hyper())
    shared = _shared_part(full, u)     # what every chip computes alike
    total, rows = shared, 0
    for first in range(0, TINY.n_experts, TINY.experts_held):
        c, p = _expert_layer(TINY.experts_held, first, full)
        out, load = hybrid.moe_apply(p, u, c, jnp.float32)
        total = total + (out - shared)
        rows += int(jnp.sum(hybrid.held_load(load, c)))
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-4)
    assert rows == int(rows_whole) == 40 * TINY.top_k


def _shared_part(p, u):
    mm = lambda a, b: hybrid._mm(a, b, jnp.float32)
    return mm(jax.nn.silu(mm(u, p["w_shared_gate"]))
              * mm(u, p["w_shared_up"]), p["w_shared_down"]) \
        * jax.nn.sigmoid(u @ p["shared_gate"])


@pytest.mark.parametrize("skew", ["all_here", "none_here"])
def test_no_row_is_dropped_at_any_skew(skew):
    """Every token choosing held experts fills every run of the grouped
    matmuls; none choosing them skips every run."""
    assert hybrid.expert_runs(TINY, 40 * TINY.top_k) == (64, 64)
    assert hybrid.expert_runs(PRESETS["qwen3-next-4"], 8192 * 10) == (
        10240, 10240, 20480, 40960)
    c, p = _expert_layer(TINY.experts_held, 0)
    # all-positive tokens, a router that adds or takes the same from each
    # held expert: every token's top choices are all held, or none is
    u = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (40, TINY.d_model)))
    push = jnp.where(jnp.arange(TINY.n_experts) < TINY.experts_held, 1.0,
                     -1.0) * (1.0 if skew == "all_here" else -1.0)
    p = dict(p, router=p["router"] + push)
    out, load = hybrid.moe_apply(p, u, c, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, rows, load_ref, _ = reference.experts(p, u, model_hyper())
    assert np.array_equal(load, load_ref)
    assert int(jnp.sum(hybrid.held_load(load, c))) == int(rows) == (
        40 * TINY.top_k if skew == "all_here" else 0)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)


def test_the_pallas_grouped_matmul_is_the_xla_one_for_gated_experts():
    """``megablox.gmm`` under the interpreter against ``jax.lax.ragged_dot``
    through the three grouped matmuls of a gated expert layer: its output
    and gradients, with rows in more than one run."""
    c, p = _expert_layer(TINY.experts_held, 0)
    u = jnp.abs(jax.random.normal(jax.random.PRNGKey(8), (40, TINY.d_model)))
    p = dict(p, router=p["router"] + jnp.where(
        jnp.arange(TINY.n_experts) < 6, 0.5, 0.0))

    def loss(kernel):
        def f(p, u):
            out, load = hybrid.moe_apply(p, u, c, jnp.float32, kernel)
            return jnp.sum(jnp.sin(out)), hybrid.held_load(load, c)
        return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(p, u)

    (a, sizes), ga = loss("xla")
    (b, _), gb = loss("interpret")
    assert int(jnp.sum(sizes)) > 64                             # two runs
    np.testing.assert_allclose(a, b, rtol=1e-5)
    for x, y in zip(jax.tree_util.tree_leaves(ga),
                    jax.tree_util.tree_leaves(gb)):
        np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-5)


# -- wiring ---------------------------------------------------------------------------

def test_row_21_builds_through_the_factory():
    opt = build_options(21)
    assert opt.model_params.hybrid_preset == "qwen3-next-4"
    assert build_options(20).model_params.hybrid_preset == "nemotron-h-9"
    opt = build_options(21, hybrid_preset="tiny-qwen")
    assert (opt.agent_type, opt.memory_type, opt.model_type) == (
        "r2d2", "device-sequence", "dtqn-hybrid")
    assert factory.sequence_pack_frames(opt) == 4
    assert factory.lstm_dim_of(opt) == 1
    assert factory.resolve_steps_per_dispatch(opt) == 1
    model = factory.build_model(opt, factory.probe_env(opt))
    assert model.preset is PRESETS["tiny-qwen"]


def test_the_published_preset_is_the_configuration_file():
    """(g) Widths live in ONE place in the program; the benchmark's file
    states the same numbers under their published names, at its top level
    and in ``shapes``."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "qwen3_next_pong.json")) as f:
        cfg = json.load(f)
    c = PRESETS[cfg["overrides"]["hybrid_preset"]]
    want = model_hyper(c)
    want.update(layer_pattern=want.pop("pattern"), hidden_size=c.d_model,
                linear_conv_kernel_dim=c.conv_kernel,
                num_experts=c.experts_held,
                moe_intermediate_size=c.expert_width,
                shared_expert_intermediate_size=c.shared_width)
    want.pop("router_aux_loss_coef")
    for key, value in want.items():
        if key in cfg:
            assert cfg[key] == value, key
        assert cfg["shapes"][key] == value, key
    assert cfg["shapes"]["router_aux_loss_coef"] == c.aux_weight
    assert cfg["shapes"]["gdn_chunk"] == c.gdn_chunk
    assert cfg["published"]["num_experts"] == c.n_experts == 512 \
        == cfg["shapes"]["num_experts_published"]
    # a published layer is a mixer block and an expert block
    assert 2 * cfg["num_hidden_layers"] == len(c.pattern) == 8
    assert [i for i, kind in enumerate(c.pattern[::2]) if kind == "*"] == [
        cfg["full_attention_interval"] - 1]
    # 562.3 M parameters, 14 bytes of train state each
    shapes = jax.eval_shape(lambda: HybridQModel(
        action_space=6, state_shape=(4, 84, 84), window=2048, preset=c).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4, 84, 84), jnp.uint8)))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert round(n / 1e5) == 5623


def test_the_two_copies_of_the_reference_are_identical():
    assert filecmp.cmp(
        os.path.join(REPO, "tests", "reference", "qwen3_next.py"),
        os.path.join(REPO, "benchmark", "reference", "qwen3_next.py"),
        shallow=False)
    with open(os.path.join(REPO, "tests", "reference",
                           "qwen3_next.py")) as f:
        assert "pytorch_distributed_tpu" not in f.read().split('"""')[2]
