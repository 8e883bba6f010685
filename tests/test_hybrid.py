"""The hybrid state-space / sparse-expert / grouped-query trunk
(models/hybrid.py, CONFIGS row 20) against its plain float32 reference
(tests/reference/nemotron_h.py) at the tiny preset on the CPU: the chunked
scan, each layer kind, the fused update, the experts' shares, the acting
carry, the masked tail, and the check's power to tell a wrong term."""

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
from pytorch_distributed_tpu.memory.device_sequence import SegmentChunk
from pytorch_distributed_tpu.models import hybrid
from pytorch_distributed_tpu.models.hybrid import PRESETS, HybridQModel
from pytorch_distributed_tpu.utils import profiling
from reference import nemotron_h as reference

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = PRESETS["tiny"]
FRAME = (4, 12, 12)          # the env's stack; one 12 x 12 frame a position
HYPER = {"burn_in": 4, "nstep": 2, "gamma": 0.99, "eta": 0.9, "double": True,
         "value_rescale": True, "pack_frames": 4}


def model_hyper(c=TINY, **changed):
    """The preset under the names the reference reads."""
    return dict(dict(
        pattern=c.pattern, mamba_num_heads=c.ssm_heads,
        mamba_head_dim=c.ssm_head_dim, n_groups=c.ssm_groups,
        ssm_state_size=c.ssm_state, num_attention_heads=c.attn_heads,
        num_key_value_heads=c.kv_heads, head_dim=c.attn_head_dim,
        num_experts_per_tok=c.top_k, routed_scaling_factor=c.route_scale,
        norm_eps=c.norm_eps, first_expert=c.first_expert), **changed)


def build(pattern=TINY.pattern, window=17, dtype=jnp.float32, seed=0,
          base=TINY, **kw):
    c = dataclasses.replace(base, pattern=pattern, **kw)
    model = HybridQModel(action_space=6, state_shape=FRAME, window=window,
                         preset=c, norm_val=255.0, compute_dtype=dtype)
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, *FRAME),
                                                           jnp.uint8))
    params = {"params": dict(params["params"])}
    # the head starts at zero, and a zero head hides the trunk
    params["params"]["head_w"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), params["params"]["head_w"].shape)
    return c, model, params


def frames_of(key, B, T):
    return jax.random.randint(jax.random.PRNGKey(key), (B, T, *FRAME[1:]), 0,
                              256).astype(jnp.uint8)


# -- the chunked scan ----------------------------------------------------------

def _sequential(x, dt, A, Bm, Cm):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t; y_t = S_t C_t."""
    k = x.shape[2] // Bm.shape[2]
    Bh, Ch = jnp.repeat(Bm, k, axis=2), jnp.repeat(Cm, k, axis=2)

    def step(S, inp):
        x_t, dt_t, B_t, C_t = inp
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, C_t)

    tm = lambda t: jnp.moveaxis(t, 1, 0)
    S, y = jax.lax.scan(step, jnp.zeros((*x.shape[::2][:1], x.shape[2],
                                         x.shape[3], Bm.shape[3])),
                        (tm(x), tm(dt), tm(Bh), tm(Ch)))
    return jnp.moveaxis(y, 0, 1), S


def _scan_inputs(T, h=4, p=8, g=2, n=8):
    k = jax.random.split(jax.random.PRNGKey(5), 5)
    return (jax.random.normal(k[0], (2, T, h, p)),
            jax.nn.softplus(jax.random.normal(k[1], (2, T, h)) - 1.0),
            -jnp.exp(jax.random.normal(k[2], (h,))),
            jax.random.normal(k[3], (2, T, g, n)),
            jax.random.normal(k[4], (2, T, g, n)))


@pytest.mark.parametrize("what", ["values", "final_state", "gradients"])
def test_chunked_scan_is_the_sequential_recurrence(what):
    args = _scan_inputs(T=12)                      # three chunks of 4
    chunked = lambda *a: hybrid.ssd_chunked(*a, chunk=4, cd=jnp.float32)
    if what == "gradients":
        w = jax.random.normal(jax.random.PRNGKey(9), (2, 12, 4, 8))
        grads = [jax.grad(lambda *a: jnp.sum(f(*a)[0] * w),
                          argnums=(0, 1, 2, 3, 4))(*args)
                 for f in (chunked, _sequential)]
        for a, b in zip(*grads):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
    else:
        at = 0 if what == "values" else 1
        got, want = chunked(*args)[at], _sequential(*args)[at]
        np.testing.assert_allclose(got, want.reshape(got.shape), rtol=2e-4,
                                   atol=2e-5)


# -- window_q against the reference --------------------------------------------

@pytest.mark.parametrize("pattern", ["M", "E", "*", "ME*E"])
def test_window_q_is_the_reference(pattern):
    c, model, params = build(pattern)
    frames = frames_of(2, 3, 17)
    q, load, states = model.apply(params, frames, method=model.window_pass)
    with jax.default_matmul_precision("highest"):
        q_ref, rows = reference.window_q(params, frames, model_hyper(c),
                                         255.0)
    load_ref, states_ref = reference.window_states(params, frames,
                                                   model_hyper(c), 255.0)
    np.testing.assert_allclose(q, q_ref, rtol=1e-4, atol=1e-4)
    assert [int(jnp.sum(hybrid.held_load(n, c))) for n in load.values()] \
        == list(np.asarray(rows).sum(axis=0))
    assert list(load) == [i for i, kind in enumerate(pattern) if kind == "E"]
    assert list(states) == [i for i, kind in enumerate(pattern)
                            if kind == "M"]
    for n, n_ref in zip(load.values(), load_ref):
        assert np.array_equal(n, n_ref) and int(jnp.sum(n)) == 3 * 17 * c.top_k
    for S, S_ref in zip(states.values(), states_ref):
        np.testing.assert_allclose(S, S_ref, rtol=1e-4, atol=1e-5)


def test_window_q_pads_a_window_that_is_no_whole_number_of_chunks():
    c, model, params = build("M")
    frames = frames_of(3, 2, 14)                   # 3.5 chunks of 4
    with jax.default_matmul_precision("highest"):
        q_ref, _ = reference.window_q(params, frames, model_hyper(c), 255.0)
    np.testing.assert_allclose(model.apply(params, frames,
                                           method=model.window_q),
                               q_ref, rtol=1e-4, atol=1e-4)


# -- the experts' shares ---------------------------------------------------------

def _expert_layer(n_held, first, full=None, seed=3):
    """An E layer's parameters for a share of ``n_held`` experts from
    ``first``; cut out of ``full`` (the uncut layer's) when given."""
    c = dataclasses.replace(TINY, experts_held=n_held, first_expert=first)
    if full is None:
        keys = jax.random.split(jax.random.PRNGKey(seed), 8)
        p = {name: init(k, shape) for k, (name, (init, shape)) in zip(
            keys, hybrid.layer_param_specs("E", c).items())}
        p["b_sel"] = 10.0 * p["b_sel"]             # so that it decides
        return c, p
    return c, dict(full, w_up=full["w_up"][first:first + n_held],
                   w_down=full["w_down"][first:first + n_held])


def test_the_shares_add_up_to_the_uncut_layer():
    """Sum over all shares of the routed part + the shared expert ONCE =
    what the reference gives for the layer with every expert held."""
    _, full = _expert_layer(TINY.n_experts, 0)
    u = jax.random.normal(jax.random.PRNGKey(4), (40, TINY.d_model))
    with jax.default_matmul_precision("highest"):
        whole, rows_whole, _ = reference.experts(full, u, model_hyper())
    shared = hybrid._mm(hybrid.relu2(hybrid._mm(
        u, full["w_shared_up"], jnp.float32)), full["w_shared_down"],
        jnp.float32)
    total, rows = shared, 0
    for first in range(0, TINY.n_experts, TINY.experts_held):
        c, p = _expert_layer(TINY.experts_held, first, full)
        out, load = hybrid.moe_apply(p, u, c, jnp.float32)
        total = total + (out - shared)
        rows += int(jnp.sum(hybrid.held_load(load, c)))
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-4)
    assert rows == int(rows_whole) == 40 * TINY.top_k


@pytest.mark.parametrize("skew", ["all_here", "none_here"])
def test_no_row_is_dropped_at_any_skew(skew):
    """Every token choosing held experts fills every run of the grouped
    matmuls (twice the balanced load, then as much again: ``expert_runs``);
    none choosing them skips every run."""
    assert hybrid.expert_runs(TINY, 40 * TINY.top_k) == (64, 64)
    assert hybrid.expert_runs(PRESETS["nemotron-h-9"], 8192 * 6) == (
        6144, 6144, 12288, 24576)
    c, p = _expert_layer(TINY.experts_held, 0)
    push = jnp.where(jnp.arange(TINY.n_experts) < TINY.experts_held, 1.0,
                     -1.0) * (50.0 if skew == "all_here" else -50.0)
    p = dict(p, b_sel=push)
    u = jax.random.normal(jax.random.PRNGKey(6), (40, TINY.d_model))
    out, load = hybrid.moe_apply(p, u, c, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, rows, load_ref = reference.experts(p, u, model_hyper())
    assert np.array_equal(load, load_ref)
    assert int(jnp.sum(hybrid.held_load(load, c))) == int(rows) == (
        40 * TINY.top_k if skew == "all_here" else 0)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)


def test_the_pallas_grouped_matmul_is_the_xla_one():
    """What the chip runs (``megablox.gmm``, here under the interpreter)
    against ``jax.lax.ragged_dot``: an expert layer's output and its
    gradients, with rows in more than one run."""
    c, p = _expert_layer(TINY.experts_held, 0)
    p = dict(p, b_sel=jnp.where(jnp.arange(TINY.n_experts) < 6, 5.0, 0.0))
    u = jax.random.normal(jax.random.PRNGKey(8), (40, TINY.d_model))

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


# -- the Pallas kernels are handed each expert's true rows -----------------------

def _whole_runs(real):
    """``grouped_dot`` handed a run's tail past the last routed row as rows
    of the last expert: the Pallas path's form before true counts."""
    def dot(x, w, sizes, kernel="auto"):
        return real(x, w, sizes.at[-1].add(x.shape[0] - jnp.sum(sizes)),
                    kernel)
    return dot


def _nan_outside(real):
    """``grouped_dot`` with NaN in every row outside the groups, in its
    output and in its rows' cotangent (the backward's ``gmm``): what the
    chip may leave in memory the kernels never write."""
    def outside(t, sizes):
        inside = jnp.arange(t.shape[0]) < jnp.sum(sizes)
        return jnp.where(inside[:, None], t, jnp.nan)

    def dot(x, w, sizes, kernel="auto"):
        @jax.custom_vjp
        def f(x, w, sizes):
            return outside(real(x, w, sizes, kernel), sizes)

        def fwd(x, w, sizes):
            out, back = jax.vjp(lambda x, w: real(x, w, sizes, kernel), x, w)
            return outside(out, sizes), (back, sizes)

        def bwd(res, g):
            back, sizes = res
            dx, dw = back(g)
            return outside(dx, sizes), dw, None

        f.defvjp(fwd, bwd)
        return f(x, w, sizes)
    return dot


# b_sel over the 16 experts (4 held, top-3 of 16) and the tokens: a level
# load inside the first run, one that needs a second run, a held expert with
# no rows, no rows here, rows that fill the first run exactly (12 tokens:
# 24 rows, two held experts chosen by every token)
_LOADS = {
    "level": (np.zeros(16), 40),
    "second_run": (np.where(np.arange(16) < 6, 5.0, 0.0), 40),
    "empty_expert": (np.where(np.arange(16) == 1, -50.0, 0.0), 40),
    "none_here": (np.where(np.arange(16) < 4, -50.0, 0.0), 40),
    "first_run_filled": (np.select([np.arange(16) < 2, np.arange(16) < 4],
                                   [50.0, -50.0], 0.0), 12),
}


@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "swiglu"])
@pytest.mark.parametrize("load", list(_LOADS))
def test_true_counts_are_the_whole_runs(load, gated, monkeypatch):
    """The Pallas path (here under the interpreter) hands the kernels each
    held expert's true rows of a run; the output and the gradients with
    respect to the tokens, the router and the expert weights are those of
    the run computed whole, and stay so with NaN in every row outside the
    groups."""
    b_sel, N = _LOADS[load]
    c = dataclasses.replace(TINY, gated_experts=gated)
    keys = jax.random.split(jax.random.PRNGKey(3), 16)
    p = {name: init(k, shape) for k, (name, (init, shape)) in zip(
        keys, hybrid.layer_param_specs("E", c).items())}
    p["b_sel"] = jnp.asarray(b_sel, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(8), (N, TINY.d_model))

    def loss(p, u):
        out, load = hybrid.moe_apply(p, u, c, jnp.float32, "interpret")
        return jnp.sum(jnp.sin(out)), hybrid.held_load(load, c)

    def run(dot):
        monkeypatch.setattr(hybrid, "grouped_dot", dot)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))(p, u)

    real = hybrid.grouped_dot
    (want, sizes), g_want = run(_whole_runs(real))
    first = hybrid.expert_runs(c, N * c.top_k)[0]
    rows = int(jnp.sum(sizes))
    assert {"level": 0 < rows < first and int(jnp.min(sizes)) > 0,
            "second_run": rows > first,
            "empty_expert": rows > 0 and int(jnp.min(sizes)) == 0,
            "none_here": rows == 0,
            "first_run_filled": rows == first}[load]
    for dot in (real, _nan_outside(real)):
        (got, _), g_got = run(dot)
        np.testing.assert_allclose(got, want, rtol=1e-6)
        assert set(g_got[0]) == set(p)
        for x, y in zip(jax.tree_util.tree_leaves(g_got),
                        jax.tree_util.tree_leaves(g_want)):
            assert np.all(np.isfinite(x))
            np.testing.assert_allclose(x, y, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("kernel", ["xla", "interpret"])
def test_rows_computed_counts_what_the_kernels_were_handed(kernel,
                                                           monkeypatch):
    """``learner/moe_rows_computed`` at a skew that needs a second run: the
    rows routed here where the Pallas kernels run (here the interpreter,
    chosen as the chip's backend would choose "pallas"), both runs whole
    where ``jax.lax.ragged_dot`` runs; the rows inside the groups each
    grouped matmul was handed, either way."""
    c, model, params = build("E")
    params["params"]["layers_0"] = dict(
        params["params"]["layers_0"],
        b_sel=jnp.where(jnp.arange(TINY.n_experts) < 6, 5.0, 0.0))
    if kernel == "interpret":
        monkeypatch.setattr(hybrid, "expert_kernel", lambda kernel="auto": (
            "interpret" if kernel == "auto" else kernel))
    handed, real = [], hybrid.grouped_dot

    def dot(x, w, sizes, kernel="auto"):
        jax.debug.callback(lambda n: handed.append(int(n)), jnp.sum(sizes))
        return real(x, w, sizes, kernel)

    monkeypatch.setattr(hybrid, "grouped_dot", dot)
    online = hybrid.window_applies(model)[0]
    _, m = online(params, frames_of(2, 3, 17))
    runs = hybrid.expert_runs(c, 3 * 17 * c.top_k)
    here = float(m["learner/moe_rows_here"])
    assert len(runs) == 2 and runs[0] < here <= sum(runs)
    computed = float(m["learner/moe_rows_computed"])
    assert computed == (here if kernel == "interpret" else sum(runs))
    assert computed == sum(handed) / 2             # w_up, w_down a run


# -- acting through the carry ----------------------------------------------------

def test_acting_step_by_step_is_window_q_with_an_early_reset():
    c, model, params = build()
    T = model.act_window                           # 16 trained positions
    frames = frames_of(7, 2, T)
    stack = lambda f, t: jnp.stack([f[:, max(t - 3 + j, 0)]
                                    for j in range(4)], axis=1)
    q_full = model.apply(params, frames, method=model.window_q)
    # row 1's episode ends after 5 steps: its carry is zeroed, as
    # build_recurrent_packed_act zeroes it, and it starts a second window
    q_late = model.apply(params, frames[1:, 5:], method=model.window_q)
    zero = model.zero_carry(1)
    carry = model.zero_carry(2)
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
    assert model.state_for_segment(carry, 0)[0].shape == (1,)


# -- the fused update -------------------------------------------------------------

OVERRIDES = dict(hybrid_preset="tiny", batch_size=4, seq_len=15,
                 seq_overlap=7, burn_in=4, nstep=2, memory_size=128,
                 compute_dtype="float32", steps_per_dispatch=1)


def tiny_learner(tmp_path, row=20, **extra):
    opt = build_options(row, seed=3, root_dir=str(tmp_path), refs="t",
                        resume="never", visualize=False,
                        **dict(OVERRIDES, **extra))
    spec = factory.probe_env(opt)
    model = factory.build_model(opt, spec)
    params = factory.init_params(opt, spec, model, 3)
    params["params"]["head_w"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(1), params["params"]["head_w"].shape)
    state, step = factory.build_train_state_and_step(opt, spec, model,
                                                     params)
    replay = factory.build_memory(opt, spec).learner_side.attach(mesh=None)
    return opt, spec, model, state, step, replay


def seeded_chunk(replay, n, num_actions=6, early=()):
    """``n`` random frame-packed segments; those in ``early`` end after 9
    valid steps (a masked tail, a terminal at its last valid step)."""
    T = replay.T
    k = jax.random.split(jax.random.PRNGKey(11), 4)
    length = np.full(n, T)
    length[list(early)] = 9
    t = np.arange(T)[None, :]
    mask = (t < length[:, None]).astype(np.float32)
    return SegmentChunk(
        obs=np.asarray(jax.random.bits(k[0], (n, *replay.obs_shape),
                                       jnp.uint8)),
        action=np.asarray(jax.random.randint(k[1], (n, T), 0, num_actions)),
        reward=np.asarray(jax.random.normal(k[2], (n, T))) * mask,
        terminal=((t == length[:, None] - 1) & (length[:, None] < T)
                  ).astype(np.float32),
        mask=mask, c0=np.zeros((n, 1), np.float32),
        h0=np.zeros((n, 1), np.float32))


def fused_update(tmp_path, **extra):
    """One K=1 fused update on a seeded ring, and what the reference needs
    to repeat it."""
    opt, spec, model, state, step, replay = tiny_learner(tmp_path, **extra)
    replay.feed_chunk(seeded_chunk(replay, 16, early=(1, 6, 11)))
    key = jax.random.PRNGKey(2)
    beta = jnp.float32(replay.beta(0))
    sample = replay.sample(4, key, beta=float(beta))
    fused = replay.build_fused_step(step, 4, donate=False, steps_per_call=1)
    state1, ring1, metrics = fused(state, replay.state, key, beta)
    grads = jax.tree_util.tree_map(       # Adam's first moment, from zero
        lambda mu: mu / 0.1, state1.opt_state[-1][0].mu)
    return dict(opt=opt, model=model, state=state, state1=state1,
                sample=sample, metrics=metrics, grads=grads, ring=ring1,
                replay=replay, batch=reference.batch_of(sample))


def agreement(run, hyper, params=None):
    """The comparisons of benchmark/families/nemotron_h.py ``agrees``."""
    state = run["state"]
    loss, signal, grads, rows = reference.update_rows(
        params or state.params, state.target_params, run["batch"], hyper,
        255.0)
    frames = run["batch"]["obs"][:, HYPER["pack_frames"] - 1:]
    states = run["model"].apply(state.params, frames,
                                method=run["model"].window_pass)[2]
    states_ref = reference.window_states(params or state.params, frames,
                                         hyper["model"], 255.0)[1]
    rel = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    flat = lambda t: np.concatenate([np.asarray(x, np.float64).ravel()
                                     for x in jax.tree_util.tree_leaves(t)])
    g, r = flat(run["grads"]), flat(grads)
    heavy = [(a, b) for a, b in zip(
        map(lambda x: np.asarray(x, np.float64).ravel(),
            jax.tree_util.tree_leaves(run["grads"])),
        map(lambda x: np.asarray(x, np.float64).ravel(),
            jax.tree_util.tree_leaves(grads)))
        if np.vdot(b, b) > 1e-6 * np.vdot(r, r)]
    leaf_cos = [float(np.vdot(a, b) / max(np.linalg.norm(a)
                                          * np.linalg.norm(b), 1e-300))
                for a, b in heavy]
    leaf_norm = [abs(np.linalg.norm(a) / np.linalg.norm(b) - 1.0)
                 for a, b in heavy]
    index = np.asarray(run["sample"].index)
    got = np.asarray(run["ring"].priority)[index].astype(np.float64) ** (
        1.0 / run["replay"].alpha) - reference.PRIORITY_EPS
    signal = np.asarray(signal, np.float64)
    here = np.array([float(v) for k, v in sorted(run["metrics"].items())
                     if k.startswith("learner/moe_rows_here/")])
    here_ref = np.asarray(rows, np.float64).sum(axis=0)
    return {
        "loss_rel": abs(float(run["metrics"]["learner/critic_loss"])
                        - float(loss)) / abs(float(loss)),
        "grad_cosine": float(g @ r / (np.linalg.norm(g)
                                      * np.linalg.norm(r))),
        "grad_cosine_leaf": min(leaf_cos),
        "grad_norm_leaf_rel": max(leaf_norm),
        "ssm_state_rel": max(rel(a, b) for a, b in zip(states.values(),
                                                       states_ref)),
        "td_p50_over_mean": float(np.median(np.abs(got - signal))
                                  / np.mean(np.abs(signal))),
        "moe_rows_rel": float(np.max(np.abs(here - here_ref)
                                     / np.maximum(here_ref, 1.0)))}


def shipped_tolerance():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "nemotron_h_pong.json")) as f:
        return json.load(f)["tolerance"]


def within(got, tol):
    return (got["loss_rel"] <= tol["loss_rel"]
            and got["grad_cosine"] >= tol["grad_cosine"]
            and got["grad_cosine_leaf"] >= tol["grad_cosine_leaf"]
            and got["grad_norm_leaf_rel"] <= tol["grad_norm_leaf_rel"]
            and got["td_p50_over_mean"] <= tol["td_p50_over_mean"]
            and got["moe_rows_rel"] <= tol["moe_rows_rel"]
            and got["ssm_state_rel"] <= tol["ssm_state_rel"])


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    return fused_update(tmp_path_factory.mktemp("hybrid"))


def test_fused_update_is_the_reference_update(run):
    got = agreement(run, dict(HYPER, model=model_hyper()))
    assert got["loss_rel"] < 1e-4 and got["td_p50_over_mean"] < 1e-3, got
    assert got["grad_cosine"] > 0.9999 and got["grad_cosine_leaf"] > 0.999
    assert got["moe_rows_rel"] == 0.0
    assert got["ssm_state_rel"] < 1e-4 and got["grad_norm_leaf_rel"] < 1e-2
    assert within(got, shipped_tolerance())
    # the target copy is bfloat16 exactly where the trunk reads bfloat16
    target = run["state"].target_params["params"]
    assert target["layers_0"]["w_in"].dtype == jnp.bfloat16
    assert target["layers_0"]["A_log"].dtype == jnp.float32
    assert target["layers_1"]["router"].dtype == jnp.float32
    assert target["head_w"].dtype == jnp.float32
    m = run["metrics"]
    pairs = 4 * 16 * TINY.top_k
    assert float(m["learner/moe_rows_absent_share"]) == pytest.approx(
        1.0 - float(m["learner/moe_rows_here"]) / pairs)
    assert float(m["learner/moe_load_max_over_mean"]) > 0.0
    assert all(jnp.ndim(v) == 0 for v in m.values())   # loads are no metric


def test_the_selection_bias_steps_against_the_load(run):
    """``b_sel`` has no gradient: the optimizer leaves it alone, and after
    the update it has moved by ``bias_rate`` against each expert's load of
    that update, as the reference's rule says."""
    before = run["state"].params["params"]
    after = run["state1"].params["params"]
    frames = run["batch"]["obs"][:, HYPER["pack_frames"] - 1:]
    load = reference.window_states(run["state"].params, frames,
                                   model_hyper(), 255.0)[0]
    layers = [i for i, kind in enumerate(TINY.pattern) if kind == "E"]
    assert len(load) == len(layers) == 2
    for i, n in zip(layers, load):
        b0, b1 = (t[f"layers_{i}"]["b_sel"] for t in (before, after))
        np.testing.assert_allclose(
            b1, reference.balanced_bias(b0, n, TINY.bias_rate), atol=1e-7)
        assert float(jnp.max(jnp.abs(b1 - b0))) == pytest.approx(
            TINY.bias_rate, rel=1e-3)
        grad = run["grads"]["params"][f"layers_{i}"]["b_sel"]
        assert float(jnp.max(jnp.abs(grad))) == 0.0


def test_the_rule_spreads_tokens_that_all_choose_alike():
    """Tokens that share most of their vector all choose the same experts;
    a few hundred steps of the rule on their own load bring every expert
    close to the mean."""
    c, p = _expert_layer(TINY.experts_held, 0)
    u = (jax.random.normal(jax.random.PRNGKey(1), (1, TINY.d_model))
         + 0.2 * jax.random.normal(jax.random.PRNGKey(2),
                                   (512, TINY.d_model)))
    load = lambda b: hybrid.route(dict(p, b_sel=b), u, c)[2]
    mean = 512 * TINY.top_k / TINY.n_experts
    assert float(jnp.max(load(p["b_sel"]))) > 4 * mean
    b = jax.lax.fori_loop(0, 800, lambda _, b: hybrid.bias_step(
        b, load(b), c.bias_rate), p["b_sel"])
    assert float(jnp.max(load(b))) < 1.25 * mean
    assert float(jnp.min(load(b))) > 0.75 * mean


def _zeroed(params, name):
    p = jax.tree_util.tree_map(lambda x: x, params)
    for layer in p["params"].values():
        if isinstance(layer, dict) and name in layer:
            layer[name] = jnp.zeros_like(layer[name])
    return p


WRONG = {
    "no_D_term": ({}, "D"),
    "relu_not_relu2": (dict(mlp_hidden_act="relu"), None),
    "weights_without_2.5": (dict(routed_scaling_factor=1.0), None),
    "selection_without_b_sel": ({}, "b_sel"),
}


@pytest.mark.parametrize("wrong", list(WRONG))
def test_a_wrong_term_falls_outside_the_shipped_tolerances(run, wrong):
    """The program against a reference with one term of the mathematics
    changed: what the cell's check would read if the PROGRAM had it wrong."""
    changed, zero = WRONG[wrong]
    params = _zeroed(run["state"].params, zero) if zero else None
    got = agreement(run, dict(HYPER, model=model_hyper(**changed)), params)
    assert not within(got, shipped_tolerance()), got


def test_a_bfloat16_scan_state_falls_outside_the_shipped_tolerance():
    """The fifth wrong term shows in the scan's last state and nowhere
    downstream (the bfloat16 matmuls that read the state round it anyway),
    and only over a window long enough for the roundings to add up: 2,048
    positions with the published step sizes, as the cell has.  There the
    sequential recurrence with a bfloat16 state (the reference's lower-
    precision control) leaves the float32 one by more than the cell's
    ``ssm_state_rel``; the program's chunked scan, bfloat16 matmuls and a
    float32 state, stays inside it.  (A state rounded only where the
    program's chunks meet, 16 times in the window, stays inside it too, and
    moves nothing else either.)"""
    k = jax.random.split(jax.random.PRNGKey(5), 5)
    T, h, p, g, n = 2048, 16, 8, 2, 8
    # x and B as the conv's silu leaves them: not centred, so a state grows
    # large against what one position adds to it
    x = jax.nn.silu(1.0 + jax.random.normal(k[0], (1, T, h, p)))
    # step sizes and decay rates over the published ranges, head by head
    dt = jnp.exp(jnp.linspace(np.log(TINY.dt_min), np.log(TINY.dt_max), h))
    dt = dt * jnp.exp(0.3 * jax.random.normal(k[1], (1, T, h)))
    A = -jax.random.permutation(k[2], jnp.linspace(1.0, 16.0, h))
    Bm, Cm = (jax.nn.silu(1.0 + jax.random.normal(kk, (1, T, g, n)))
              for kk in k[3:])
    want = _sequential(x, dt, A, Bm, Cm)[1]
    rel = lambda S: float(jnp.linalg.norm(S.reshape(want.shape) - want)
                          / jnp.linalg.norm(want))
    limit = shipped_tolerance()["ssm_state_rel"]
    Bh = jnp.repeat(Bm, h // g, axis=2)

    def step(S, inp):
        x_t, dt_t, B_t = inp
        S = (jnp.exp(dt_t * A)[..., None, None] * S.astype(jnp.float32)
             + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        return S.astype(jnp.bfloat16), None
    control = jax.lax.scan(step, jnp.zeros(want.shape, jnp.bfloat16), tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, Bh)))[0].astype(jnp.float32)
    chunked = lambda state: hybrid.ssd_chunked(
        x, dt, A, Bm, Cm, chunk=128, cd=jnp.bfloat16, state_dtype=state)[1]
    err = {"control": rel(control), "program": rel(chunked(jnp.float32)),
           "rounded_between_chunks": rel(chunked(jnp.bfloat16))}
    assert err["program"] < err["rounded_between_chunks"] < limit \
        < err["control"], err


def test_a_masked_tail_changes_no_valid_positions_loss(tmp_path):
    """Segments 1, 6, 11 end after 9 steps: what lies in their tails
    (frames, actions, rewards) must not reach the loss or the priorities."""
    opt, spec, model, state, step, replay = tiny_learner(tmp_path)
    chunk = seeded_chunk(replay, 16, early=(1, 6, 11))
    noise = np.random.RandomState(0)
    tail = chunk.mask == 0.0
    other = chunk._replace(
        action=np.where(tail, noise.randint(0, 6, tail.shape), chunk.action),
        reward=np.where(tail, noise.randn(*tail.shape), chunk.reward
                        ).astype(np.float32),
        # frame t + 4 is position t + 1's: past the successor of the last
        # valid step nothing is read
        obs=np.where((np.arange(chunk.obs.shape[1])[None, :]
                      > chunk.mask.sum(1)[:, None] + 4)[..., None, None],
                     noise.randint(0, 256, chunk.obs.shape), chunk.obs
                     ).astype(np.uint8))
    out, step = [], jax.jit(step)
    for ch in (chunk, other):
        replay.state = replay._init_state()
        replay.feed_chunk(ch)
        batch = replay.sample(16, jax.random.PRNGKey(0))
        # every segment once, the early ones among them
        batch = batch._replace(**{
            f: getattr(replay.state, f)[:16] for f in
            ("obs", "action", "reward", "terminal", "mask")})
        _, metrics, seq_pr = step(state, batch)
        out.append((float(metrics["learner/critic_loss"]),
                    np.asarray(seq_pr)))
    assert out[0][0] == pytest.approx(out[1][0], rel=1e-5)
    np.testing.assert_allclose(out[0][1], out[1][1], rtol=1e-4, atol=1e-6)


# -- wiring --------------------------------------------------------------------

def test_row_20_builds_through_the_factory():
    opt = build_options(20, hybrid_preset="tiny")
    assert (opt.agent_type, opt.memory_type, opt.model_type) == (
        "r2d2", "device-sequence", "dtqn-hybrid")
    assert opt.memory_params.state_dtype == "uint8"
    assert factory.sequence_pack_frames(opt) == 4
    assert factory.lstm_dim_of(opt) == 1
    assert factory.resolve_steps_per_dispatch(opt) == 1
    opt.agent_params.steps_per_dispatch = 2
    assert factory.resolve_steps_per_dispatch(opt) == 2
    spec = factory.probe_env(opt)
    model = factory.build_model(opt, spec)
    assert model.preset is PRESETS["tiny"]
    assert model.window == opt.agent_params.seq_len + 1
    assert "dtqn-hybrid" in factory.ModelTypes


def test_the_published_preset_is_the_configuration_file():
    """Widths live in ONE place in the program; the benchmark's file states
    the same numbers under their published names."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "nemotron_h_pong.json")) as f:
        cfg = json.load(f)
    c = PRESETS[cfg["overrides"]["hybrid_preset"]]
    want = model_hyper(c)
    want.update(hybrid_override_pattern=want.pop("pattern"),
                hidden_size=c.d_model, conv_kernel=c.conv_kernel,
                chunk_size=c.chunk, n_routed_experts=c.experts_held,
                moe_intermediate_size=c.expert_width,
                moe_shared_expert_intermediate_size=c.shared_width,
                time_step_min=c.dt_min, time_step_max=c.dt_max,
                time_step_floor=c.dt_floor)
    for key, value in want.items():
        if key in cfg:
            assert cfg[key] == value, key
        assert cfg["shapes"].get(key, value) == value, key
    assert cfg["published"]["n_routed_experts"] == c.n_experts == 128
    assert cfg["num_hidden_layers"] == len(c.pattern) == 9
    assert cfg["published"]["hybrid_override_pattern"].startswith(c.pattern)
    # 597.9 M parameters, 14 bytes of train state each
    shapes = jax.eval_shape(lambda: HybridQModel(
        action_space=6, state_shape=(4, 84, 84), window=2048, preset=c).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4, 84, 84), jnp.uint8)))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert round(n / 1e5) == 5979


def test_the_two_copies_of_the_reference_are_identical():
    assert filecmp.cmp(
        os.path.join(REPO, "tests", "reference", "nemotron_h.py"),
        os.path.join(REPO, "benchmark", "reference", "nemotron_h.py"),
        shallow=False)


def test_target_updates_keep_the_targets_dtype():
    from pytorch_distributed_tpu.utils.helpers import update_target

    target = {"w": jnp.zeros((3,), jnp.bfloat16), "b": jnp.zeros((3,))}
    online = {"w": jnp.full((3,), 1.2345678), "b": jnp.ones((3,))}
    for period, step in ((4, 8), (0.5, 1)):
        new = update_target(target, online, jnp.asarray(step), period)
        assert new["w"].dtype == jnp.bfloat16 and new["b"].dtype == jnp.float32
        assert float(new["b"][0]) == (1.0 if period == 4 else 0.5)


def test_the_models_parts_are_named_inside_checkpoint_and_scan(tmp_path):
    """Every scope of the vocabulary stands in the fused step's lowered
    program, on the forward's path and on the backward's."""
    opt, spec, model, state, step, replay = tiny_learner(tmp_path)
    fused = replay.build_fused_step(step, 4, donate=False, steps_per_call=1)
    text = fused.lower(state, replay.state, jax.random.PRNGKey(0),
                       jnp.float32(0.6)).as_text(debug_info=True)
    # model.gdn is the second trunk's (tests/test_gated_delta_update.py),
    # model.kda, model.mla and model.mlp the third's
    # (tests/test_kimi_trunk_update.py), model.sconv the fourth's
    # (tests/test_lfm2_trunk_update.py)
    later = (profiling.SCOPE_GDN, profiling.SCOPE_KDA, profiling.SCOPE_MLA,
             profiling.SCOPE_MLP, profiling.SCOPE_SCONV)
    for scope in tuple(s for s in profiling.MODEL_SCOPES
                       if s not in later) + (
            profiling.SCOPE_MOE_ROUTE, profiling.SCOPE_MOE_EXPERTS,
            profiling.SCOPE_MOE_SHARED):
        assert scope in text, scope
    assert not any(scope in text for scope in later)
    lines = [ln for ln in text.splitlines() if "loc(" in ln]
    for scope in (profiling.SCOPE_SSM, profiling.SCOPE_ATTN,
                  profiling.SCOPE_MOE):
        assert any(scope in ln and "transpose(" in ln for ln in lines), scope
        assert any(scope in ln and profiling.PHASE_TARGET in ln
                   for ln in lines), scope
