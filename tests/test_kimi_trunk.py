"""The channel-gated delta-rule / latent-attention / sigmoid-routed
SwiGLU-expert trunk (models/hybrid.py PRESETS["kimi-linear-5"], CONFIGS row
22) against its plain float32 reference (tests/reference/kimi_linear.py) at
the tiny preset on the CPU: the chunked recurrence against the sequential
one, each block kind, the experts' shares, the acting carry with its latent
ring, the wiring, and the lowered steps of rows 20 and 21 pinned.  The fused
update and the check's power to tell a wrong term:
tests/test_kimi_trunk_update.py."""

import dataclasses
import filecmp
import hashlib
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
from reference import kimi_linear as reference
from test_hybrid import build as build_hybrid, frames_of

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = PRESETS["tiny-kimi"]
HYPER = {"burn_in": 4, "nstep": 2, "gamma": 0.99, "eta": 0.9, "double": True,
         "value_rescale": True, "pack_frames": 4}


def model_hyper(c=TINY, **changed):
    """The preset under the names the reference reads."""
    return dict(dict(
        pattern=c.pattern, kda_num_heads=c.kda_heads,
        kda_head_dim=c.kda_head_dim, num_attention_heads=c.attn_heads,
        qk_nope_head_dim=c.mla_nope, qk_rope_head_dim=c.mla_rope,
        v_head_dim=c.mla_v, kv_lora_rank=c.mla_latent,
        num_experts_per_token=c.top_k, moe_renormalize=True,
        routed_scaling_factor=c.route_scale, rms_norm_eps=c.norm_eps,
        first_expert=c.first_expert), **changed)


def build(pattern=TINY.pattern, window=18, **kw):
    """test_hybrid's model of this preset, with the parameters that start at
    a constant stirred: a one or a zero hides a factor or a term."""
    c, model, params = build_hybrid(pattern, window, base=TINY, **kw)
    return c, model, stirred(params)


def stirred(tree, seed=1):
    key = jax.random.PRNGKey(seed)

    def stir(path, leaf):
        name = getattr(path[-1], "key", "")
        noise = jax.random.normal(
            jax.random.fold_in(key, hash(str(path)) % 2 ** 31), leaf.shape)
        if name.endswith("norm"):
            return 1.0 + 0.2 * noise
        return 0.5 * noise if name == "gate_bias" else leaf

    return jax.tree_util.tree_map_with_path(stir, tree)


# -- (a) the chunked channel-gated recurrence ---------------------------------

def _recurrence_inputs(T, h=3, d=8, decay="init", b=2, seed=0):
    """q, k, v, g, beta of a window.  ``decay``: "init" = a seeded block's
    (exp(g) from 0.2 to 0.999 a position), "strong" = every channel keeps
    1e-4 a position, "mixed" = every other channel 1e-4 and the rest
    0.999."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, T, h, d))) / d ** 0.5
    k = unit(jax.random.normal(ks[1], (b, T, h, d)))
    v = jax.random.normal(ks[2], (b, T, h, d))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, T, h)))
    g = {"init": -0.3 * jax.nn.softplus(jax.random.normal(ks[3],
                                                          (b, T, h, d))),
         "strong": jnp.full((b, T, h, d), np.log(1e-4)),
         "mixed": jnp.broadcast_to(jnp.where(
             jnp.arange(d) % 2 == 0, np.log(1e-4), -1e-3), (b, T, h, d)),
         }[decay]
    return q, k, v, g.astype(jnp.float32), beta


def _sequential(q, k, v, g, beta):
    """The recurrence, a position at a time (the actor's step)."""
    def position(S, inp):
        o, S = gated_delta.gated_delta_step(*inp, S)
        return S, o

    tm = lambda t: jnp.moveaxis(t, 1, 0)
    S, o = jax.lax.scan(
        position, jnp.zeros((q.shape[0], q.shape[2], q.shape[3],
                             v.shape[3])),
        tuple(tm(t) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), S


def _agree(chunked, args, what):
    if what == "gradients":
        scalar = lambda f: lambda *a: (jnp.sum(jnp.sin(f(*a)[0]))
                                       + jnp.sum(jnp.square(f(*a)[1])))
        ga, gb = (jax.jit(jax.grad(scalar(f), argnums=(0, 1, 2, 3, 4)))(*args)
                  for f in (chunked, _sequential))
        for a, b in zip(ga, gb):
            assert bool(jnp.all(jnp.isfinite(a)))
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
    else:
        at = 0 if what == "values" else 1
        got = jax.jit(chunked)(*args)[at]
        assert bool(jnp.all(jnp.isfinite(got)))
        np.testing.assert_allclose(got, jax.jit(_sequential)(*args)[at],
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("what", ["values", "final_state", "gradients"])
@pytest.mark.parametrize("chunk,sub", [(4, 2), (64, 16)])
def test_chunked_kda_is_the_sequential_recurrence(chunk, sub, what):
    args = _recurrence_inputs(T=3 * chunk, h=2 if chunk == 64 else 3)
    _agree(lambda *a: gated_delta.kda_chunked(*a, chunk, sub, jnp.float32),
           args, what)


@pytest.mark.parametrize("what", ["values", "final_state", "gradients"])
@pytest.mark.parametrize("decay", ["strong", "mixed"])
def test_chunked_kda_at_a_decay_that_would_overflow_a_split_product(decay,
                                                                    what):
    """Over a chunk of 64 a channel that keeps 1e-4 a position decays by
    e^-580: ``(K exp(gamma)) (K exp(-gamma))^T`` is inf x 0 there.  The
    sub-blocked form stays finite and equal to the recurrence, beside
    channels that hardly decay at all."""
    args = _recurrence_inputs(T=128, h=2, decay=decay)
    assert float(jnp.sum(args[3][0, :64, 0, 0])) < -500.0
    _agree(lambda *a: gated_delta.kda_chunked(*a, 64, 16, jnp.float32),
           args, what)


def test_the_inverse_by_blocks_is_the_inverse_where_the_products_lose_it():
    """A small ``A``: both forms and their shared cotangent agree.  ``A_ij`` =
    0.9 below the diagonal (keys that resemble each other under a slow
    decay): the true inverse's entries stay below 1, the powers of ``A`` the
    ten products pass through reach 1e16, and float32 loses the inverse to
    them."""
    A = 0.3 * jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 16, 16)),
                       -1)
    np.testing.assert_allclose(gated_delta.unit_lower_inverse_by_blocks(A),
                               gated_delta.unit_lower_inverse(A), atol=2e-4)
    f = lambda inverse: jnp.tril(jax.grad(
        lambda A: jnp.sum(jnp.sin(inverse(A))))(A), -1)
    np.testing.assert_allclose(f(gated_delta.unit_lower_inverse_by_blocks),
                               f(gated_delta._inverse_by_blocks),
                               rtol=1e-3, atol=1e-3)
    A = jnp.tril(jnp.full((64, 64), 0.9), -1)
    off = lambda T: float(jnp.max(jnp.abs(
        jnp.matmul(jnp.eye(64) + A, T, precision="highest") - jnp.eye(64))))
    T = gated_delta.unit_lower_inverse_by_blocks(A)
    assert off(T) < 1e-5 and float(jnp.max(jnp.abs(T))) <= 1.0
    assert not off(gated_delta.unit_lower_inverse(A)) < 1.0


@pytest.mark.parametrize("what", ["values", "final_state", "gradients"])
def test_chunked_kda_with_keys_that_resemble_each_other(what):
    """Every key within 0.1 of one direction, beta 0.9, a channel keeps 0.99
    a position.  The scalar-gated rule's chunked form (its inverse by
    products) returns inf there or something far from the recurrence; its own
    cell's seeded decay forgets within a position and never meets this.  This
    one is the recurrence."""
    q, k, v, g, beta = _recurrence_inputs(T=128, h=2)
    k = 0.9 * k[:1, :1] + 0.1 * k
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g, beta = jnp.full_like(g, -0.01), jnp.full_like(beta, 0.9)
    if what == "values":
        broken = gated_delta.gated_delta_chunked(q, k, v, g[..., 0], beta,
                                                 64, jnp.float32)[0]
        want = _sequential(q, k, v, g, beta)[0]
        assert not float(jnp.linalg.norm(broken - want)
                         / jnp.linalg.norm(want)) < 1.0
    _agree(lambda *a: gated_delta.kda_chunked(*a, 64, 16, jnp.float32),
           (q, k, v, g, beta), what)


def test_a_gate_constant_over_a_heads_channels_is_the_scalar_gated_rule():
    q, k, v, g, beta = _recurrence_inputs(T=16)
    g = g[..., 0]                                          # a head's
    want = gated_delta.gated_delta_chunked(q, k, v, g, beta, 4, jnp.float32)
    got = gated_delta.kda_chunked(
        q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta, 4, 2,
        jnp.float32)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def _layer(kind, c=TINY, T=14, B=2, seed=5):
    """A block's parameters (constants stirred) and normed inputs."""
    specs = hybrid.layer_param_specs(kind, c)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(specs) + 1)
    p = {name: init(k, shape) for k, (name, (init, shape)) in zip(
        keys, specs.items())}
    return stirred(p), jax.random.normal(keys[-1], (B, T, c.d_model))


def _by_segment(f, p, u, at=None):
    with jax.default_matmul_precision("highest"):
        out = [f(p, seg, model_hyper()) for seg in u]
    return jnp.stack(out) if at is None else jnp.stack([o[at] for o in out])


@pytest.mark.parametrize("what", ["output", "final_state"])
def test_kda_step_over_the_positions_is_the_window_pass_and_the_reference(
        what):
    p, u = _layer("K")                                     # 3.5 chunks of 4
    at = 0 if what == "output" else 1
    window = jax.jit(lambda p, u: hybrid.kda_window(p, u, TINY,
                                                    jnp.float32))(p, u)[at]

    @jax.jit
    def stepwise(p, u):
        B = u.shape[0]
        tails = [jnp.zeros((B, TINY.conv_kernel - 1, TINY.kda_dim))] * 3
        S = jnp.zeros((B, TINY.kda_heads, TINY.kda_head_dim,
                       TINY.kda_head_dim))
        out = []
        for t in range(u.shape[1]):
            o, tails, S = hybrid.kda_step(p, u[:, t], tails, S, TINY,
                                          jnp.float32)
            out.append(o)
        return jnp.stack(out, axis=1), S

    np.testing.assert_allclose(window, stepwise(p, u)[at], rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(
        window, _by_segment(reference.delta_attention, p, u, at), rtol=2e-4,
        atol=2e-5)


# -- (b) latent attention -----------------------------------------------------

def test_the_latent_attention_window_is_the_references_full_softmax():
    p, u = _layer("L")
    got = jax.jit(lambda p, u: hybrid.mla_window(p, u, TINY,
                                                 jnp.float32))(p, u)
    np.testing.assert_allclose(
        got, _by_segment(reference.latent_attention, p, u), rtol=1e-4,
        atol=1e-5)


def test_the_absorbed_step_over_a_ring_of_latents_is_the_window_pass():
    p, u = _layer("L", T=9)
    window = hybrid.mla_window(p, u, TINY, jnp.float32)
    W = 6                                      # the ring wraps: the last W
    ring = jnp.zeros((2, W, TINY.mla_latent + TINY.mla_rope))
    step = jax.jit(lambda p, x, ring, n: hybrid.mla_step(
        p, x, ring, n, TINY, jnp.float32))
    for t in range(W):
        o, ring = step(p, u[:, t], ring, jnp.full((2,), t))
        np.testing.assert_allclose(o, window[:, t], rtol=1e-4, atol=1e-5)
    assert ring.shape == (2, W, 20)
    # past the ring's length a position attends the last W and no more
    late = hybrid.mla_window(p, u[:, 3:], TINY, jnp.float32)
    for t in range(W, 9):
        o, ring = step(p, u[:, t], ring, jnp.full((2,), t))
    np.testing.assert_allclose(o, late[:, -1], rtol=1e-4, atol=1e-5)


def test_the_published_carry_is_a_ring_of_latents_and_a_float32_state():
    c = PRESETS["kimi-linear-5"]
    model = HybridQModel(action_space=6, state_shape=(4, 84, 84),
                         window=2048, preset=c)
    carry = jax.eval_shape(lambda: model.zero_carry(1))
    per_block = {"K": [(3, 4096)] * 3 + [(32, 128, 128)], "L": [(2047, 576)],
                 "F": [], "E": []}
    assert [leaf.shape[1:] for leaf in carry] == [
        s for kind in c.pattern for s in per_block[kind]] + [()]
    states = [leaf for leaf in carry if leaf.shape[1:] == (32, 128, 128)]
    assert len(states) == 4 and all(s.dtype == jnp.float32 for s in states)
    # 2.4 MB an env where expanded keys and values would be 42 MB
    ring = next(leaf for leaf in carry if leaf.shape[1:] == (2047, 576))
    assert ring.dtype == jnp.bfloat16 and ring.size * 2 < 2.4e6
    assert 2047 * 32 * (192 + 128) * 2 > 41e6


# -- (c) the blocks and the whole model against the reference -----------------

@pytest.mark.parametrize("pattern", ["K", "L", "F", "E", "KFLE"])
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
                            if kind == "K"]
    for n, n_ref in zip(load.values(), load_ref):
        assert np.array_equal(n, n_ref) and int(jnp.sum(n)) == 3 * 17 * c.top_k
    for S, S_ref in zip(states.values(), states_ref):
        np.testing.assert_allclose(S, S_ref, rtol=1e-4, atol=1e-5)


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
    # per K block three conv tails and a float32 state, per L block a ring of
    # latents, the count
    assert [leaf.shape[1:] for leaf in carry] == [
        (3, TINY.kda_dim)] * 3 + [(4, 8, 8), (T, 20), ()]
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


# -- (d) the experts ----------------------------------------------------------

def _expert_layer(c, full=None, seed=3):
    if full is None:
        specs = hybrid.layer_param_specs("E", c)
        keys = jax.random.split(jax.random.PRNGKey(seed), len(specs))
        return {name: init(k, shape) for k, (name, (init, shape)) in zip(
            keys, specs.items())}
    held = slice(c.first_expert, c.first_expert + c.experts_held)
    return dict(full, **{name: full[name][held]
                         for name in ("w_gate", "w_up", "w_down")})


def test_the_expert_block_is_the_reference():
    """Sigmoid scores selected with ``b_sel`` and weighed without it,
    renormalised, x 2.446; SwiGLU experts; an ungated SwiGLU shared
    expert."""
    p = _expert_layer(TINY)
    assert "shared_gate" not in p and "b_sel" in p and "w_gate" in p
    u = jax.random.normal(jax.random.PRNGKey(4), (40, TINY.d_model))
    out, load = hybrid.moe_apply(p, u, TINY, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, rows, load_ref = reference.experts(p, u, model_hyper())
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)
    assert np.array_equal(load, load_ref)
    assert int(jnp.sum(hybrid.held_load(load, TINY))) == int(rows) > 0
    # a selection bias moves the choice and not the weights
    pushed = dict(p, b_sel=p["b_sel"].at[0].add(10.0))
    _, load_pushed = hybrid.moe_apply(pushed, u, TINY, jnp.float32)
    assert int(load_pushed[0]) == 40 > int(load[0])


def test_the_32_shares_add_up_to_the_uncut_layer():
    """The guide's share test at the published counts and a small width:
    256 experts top-8, 8 held a chip.  The routed parts of the 32 shares
    (``first_expert`` 0, 8, ..., 248) + the shared expert ONCE = what the
    reference gives for the layer with every expert held."""
    whole = dataclasses.replace(TINY, n_experts=256, top_k=8,
                                experts_held=256)
    full = _expert_layer(whole)
    u = jax.random.normal(jax.random.PRNGKey(4), (64, TINY.d_model))
    with jax.default_matmul_precision("highest"):
        want, rows_whole, _ = reference.experts(
            full, u, model_hyper(whole))
    mm = lambda a, b: hybrid._mm(a, b, jnp.float32)
    shared = mm(jax.nn.silu(mm(u, full["w_shared_gate"]))
                * mm(u, full["w_shared_up"]), full["w_shared_down"])
    share = jax.jit(lambda p, c: hybrid.moe_apply(p, u, c, jnp.float32),
                    static_argnums=1)
    total, rows = shared, 0
    for first in range(0, 256, 8):
        c = dataclasses.replace(whole, experts_held=8, first_expert=first)
        out, load = share(_expert_layer(c, full), c)
        total = total + (out - shared)
        rows += int(jnp.sum(hybrid.held_load(load, c)))
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-4)
    assert rows == int(rows_whole) == 64 * 8


# -- wiring -------------------------------------------------------------------

def test_row_22_builds_through_the_factory():
    opt = build_options(22)
    assert opt.model_params.hybrid_preset == "kimi-linear-5"
    opt = build_options(22, hybrid_preset="tiny-kimi")
    assert (opt.agent_type, opt.memory_type, opt.model_type) == (
        "r2d2", "device-sequence", "dtqn-hybrid")
    assert factory.sequence_pack_frames(opt) == 4
    assert factory.lstm_dim_of(opt) == 1
    assert factory.resolve_steps_per_dispatch(opt) == 1
    model = factory.build_model(opt, factory.probe_env(opt))
    assert model.preset is PRESETS["tiny-kimi"]
    # the sigmoid router steps its selection bias after the optimizer
    assert model.train_parts(4)[2] is not None


def test_an_unknown_letter_names_the_letters_the_pattern_knows():
    with pytest.raises(ValueError, match=r"'Q'.*M, D, K, \*, L, F, E"):
        hybrid.layer_param_specs("Q", TINY)


def test_the_published_preset_is_the_configuration_file_and_the_catalog():
    """Widths live in ONE place in the program; the benchmark's file states
    the same numbers under their published names, at its top level (every
    key of the catalog's ``config``) and in ``shapes``."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "kimi_linear_pong.json")) as f:
        cfg = json.load(f)
    c = PRESETS[cfg["overrides"]["hybrid_preset"]]
    want = model_hyper(c)
    want.update(layer_pattern=want.pop("pattern"), hidden_size=c.d_model,
                short_conv_kernel_size=c.conv_kernel, kda_chunk=c.kda_chunk,
                kda_sub_block=c.kda_sub, kda_gate_rank=c.kda_gate_rank,
                intermediate_size=c.mlp_width, num_experts=c.experts_held,
                moe_intermediate_size=c.expert_width,
                shared_expert_intermediate_size=c.shared_width)
    for key, value in want.items():
        assert cfg["shapes"][key] == value, key
        if key in cfg:
            assert cfg[key] == value, key
    linear = cfg["linear_attn_config"]
    assert (linear["num_heads"], linear["head_dim"],
            linear["short_conv_kernel_size"]) == (
                c.kda_heads, c.kda_head_dim, c.conv_kernel)
    assert cfg["published"]["num_experts"] == c.n_experts == 256 \
        == cfg["shapes"]["num_experts_published"]
    assert cfg["num_shared_experts"] * cfg["moe_intermediate_size"] \
        == c.shared_width
    # a published layer is a mixer block and a feed-forward block; layers
    # are numbered from 1 in the published lists
    assert 2 * cfg["num_hidden_layers"] == len(c.pattern) == 10
    mixers, ffns = c.pattern[::2], c.pattern[1::2]
    assert [i + 1 for i, kind in enumerate(mixers) if kind == "K"] == [
        n for n in linear["kda_layers"] if n <= 5]
    assert [i + 1 for i, kind in enumerate(mixers) if kind == "L"] == [
        n for n in linear["full_attn_layers"] if n <= 5]
    assert ffns == "F" * cfg["first_k_dense_replace"] + "E" * 4
    # the catalog's row, where it is installed: every number under its key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == cfg["source"])
        for key, value in row["config"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key
        assert {k: cfg["published"][k] for k in (
            "num_hidden_layers", "num_experts")} == {
                k: row["config"][k] for k in ("num_hidden_layers",
                                              "num_experts")}
    # 524.5 M parameters, 14 bytes of train state each
    shapes = jax.eval_shape(lambda: HybridQModel(
        action_space=6, state_shape=(4, 84, 84), window=2048, preset=c).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4, 84, 84), jnp.uint8)))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert round(n / 1e5) == cfg["parameters_1e5"]


def test_the_two_copies_of_the_reference_are_identical():
    assert filecmp.cmp(
        os.path.join(REPO, "tests", "reference", "kimi_linear.py"),
        os.path.join(REPO, "benchmark", "reference", "kimi_linear.py"),
        shallow=False)
    with open(os.path.join(REPO, "tests", "reference",
                           "kimi_linear.py")) as f:
        assert "pytorch_distributed_tpu" not in f.read().split('"""')[2]


# -- rows 20, 21 and 22 stay the parent's programs --------------------------------

# sha256 of the K = 1 fused step's CPU lowering (``as_text()`` without debug
# info) at the cell's sizes on an abstract train state, recorded on commit
# bba29e9 (PR 32; row 22's on 0230cdd, PR 34): the three hybrid cells run
# these programs, and their ``setup_s`` is within 10 % of refusal from
# anything that changes them (row 22: 60 s against a bound of 6.0).  On a TPU
# row 21's step holds the delta rule's Pallas kernels instead of the XLA
# chunk (PR 35: chosen in Python by the backend, so this CPU lowering does
# not see it; tests/test_gdn_kernel.py holds the TPU lowering's kernels)
PINNED = {
    "nemotron_h_pong":
        "eb13a71116c83bc2591d7f783ffb6ad8224666c4c4ec3af7a600d130e279ac22",
    "qwen3_next_pong":
        "34f7c692600a8987ef4067bdfabe536914ec697545e26541b64e5cef0f05ac00",
    "kimi_linear_pong":
        "f7c19725a4ddb2467a5a39ba186f031825ce3ea10c0b50e885e51ff6bce4a962",
}


@pytest.mark.parametrize("config", list(PINNED))
def test_the_lowered_step_of_an_accepted_hybrid_cell_is_the_parents(
        config, tmp_path):
    with open(os.path.join(REPO, "benchmark", "configs",
                           f"{config}.json")) as f:
        cfg = json.load(f)
    # the ring at a small capacity (its size is not the model's), the model
    # at its published widths as shapes: nothing of it is allocated
    opt = build_options(
        int(cfg["row"]), seed=0, root_dir=str(tmp_path), refs="t",
        resume="never", visualize=False, num_actors=0,
        evaluator_nepisodes=0, **dict(cfg["overrides"], memory_size=4096))
    spec = factory.probe_env(opt)
    model = factory.build_model(opt, spec)
    built = {}

    def make(seed):
        params = factory.init_params(opt, spec, model, seed)
        state, built["step"] = factory.build_train_state_and_step(
            opt, spec, model, params)
        return state

    state = jax.eval_shape(make, 0)
    replay = factory.build_memory(opt, spec).learner_side.attach(mesh=None)
    fused = replay.build_fused_step(
        built["step"], opt.agent_params.batch_size,
        donate=opt.parallel_params.donate, steps_per_call=1)
    text = fused.lower(state, replay.state,
                       jax.ShapeDtypeStruct((2,), jnp.uint32),
                       jnp.float32(0.5)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PINNED[config]
