"""The delta rule's Pallas kernel pairs under the interpreter on the CPU:
row 21's gated rule (ops/pallas_gated_delta.py) first, row 22's
channel-gated rule (ops/pallas_kda.py) at the end of this file.  Row 21's at
the published head widths (chunk 64, d_k = d_v = 128, 2 key / 4 value
heads): the window against the XLA form of ``gated_delta_chunked`` (its
oracle) and against the recurrence a position at a time, the gradients
against the XLA form's, a padded window, the float32 carried state, keys that
resemble each other under a slow decay, and that a program holds each
kernel's body ONCE however many blocks and passes launch it (PERF.md section
6: the cell's set-up).  Row 22's likewise, with slow and underflowing
channel decays."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.models import gated_delta, hybrid
from pytorch_distributed_tpu.models.hybrid import PRESETS
from pytorch_distributed_tpu.ops import pallas_gated_delta as kernels
from pytorch_distributed_tpu.ops import pallas_kda

L, D = 64, 128


def _inputs(b, chunks, seed=0, keep=None, G=2, r=2):
    """q, k, v, g, beta of a window of ``chunks`` chunks.  ``keep``: what a
    position keeps of the state (None: 0.5 to 0.99, drawn)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    T = chunks * L
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, T, G, D))) / D ** 0.5
    k = unit(jax.random.normal(ks[1], (b, T, G, D)))
    v = jax.random.normal(ks[2], (b, T, G * r, D))
    g = -0.3 * jax.nn.softplus(jax.random.normal(ks[3], (b, T, G * r))) \
        if keep is None else jnp.full((b, T, G * r), np.log(keep), jnp.float32)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, T, G * r)))
    return q, k, v, g, beta


def _sequential(q, k, v, g, beta):
    """The recurrence, a position at a time (the actor's step)."""
    def position(S, inp):
        o, S = gated_delta.gated_delta_step(*inp, S)
        return S, o

    tm = lambda t: jnp.moveaxis(t, 1, 0)
    S, o = jax.lax.scan(
        position,
        jnp.zeros((q.shape[0], v.shape[2], q.shape[3], v.shape[3])),
        tuple(tm(t) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), S


def _window(kernel, cd=jnp.float32):
    return lambda *a: gated_delta.gated_delta_chunked(*a, L, cd, kernel=kernel)


_rel = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("oracle", ["xla_form", "recurrence"])
@pytest.mark.parametrize("what", ["values", "final_state"])
@pytest.mark.parametrize("b,chunks", [(1, 2), (2, 4)])
def test_the_kernel_window_is_the_xla_form_and_the_recurrence(b, chunks, what,
                                                              oracle):
    args = _inputs(b, chunks)
    at = 0 if what == "values" else 1
    got = _window("interpret")(*args)[at]
    want = (_window("xla") if oracle == "xla_form" else _sequential)(*args)[at]
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)   # 7e-7


def test_the_backward_kernel_gives_the_xla_forms_gradients(b=2, chunks=3):
    """In q, k, v, g and beta, through the outputs AND the last state."""
    args = _inputs(b, chunks, seed=3)
    scalar = lambda f: lambda *a: (jnp.sum(jnp.sin(f(*a)[0]))
                                   + jnp.sum(jnp.square(f(*a)[1])))
    got, want = (jax.grad(scalar(_window(kernel)), argnums=(0, 1, 2, 3, 4))(
        *args) for kernel in ("interpret", "xla"))
    for name, a, b_ in zip("q k v g beta".split(), got, want):
        assert a.shape == b_.shape and bool(jnp.all(jnp.isfinite(a))), name
        assert _rel(a, b_) < 5e-6, (name, _rel(a, b_))      # <= 1.7e-6


def test_a_padded_position_decays_nothing_and_writes_nothing():
    """A window of 100 positions padded to two chunks with g = beta = 0, as
    ``gdn_window`` pads it."""
    q, k, v, g, beta = _inputs(1, 2, seed=4)
    real = 100
    g, beta = g.at[:, real:].set(0.0), beta.at[:, real:].set(0.0)
    o, S = _window("interpret")(q, k, v, g, beta)
    o_want, S_want = _sequential(*(t[:, :real] for t in (q, k, v, g, beta)))
    np.testing.assert_allclose(o[:, :real], o_want, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(S, S_want, rtol=2e-5, atol=2e-6)


def test_the_state_is_carried_in_float32(monkeypatch):
    """bfloat16 operands, a memory that keeps 0.995 a position, four chunks:
    the kernel stays with the XLA form (whose scan carries float32); the same
    kernel with its carried state rounded to bfloat16 after every chunk does
    not."""
    q, k, v, g, beta = _inputs(1, 4, seed=1, keep=0.995)
    want = _window("xla", jnp.bfloat16)(q, k, v, g, beta)[1]
    limit = 1.3e-3
    assert _rel(_window("interpret", jnp.bfloat16)(q, k, v, g, beta)[1],
                want) < limit                     # 6.1e-4 as written

    sound = kernels._fwd_kernel

    def rounded(*refs, **static):
        sound(*refs, **static)
        S_ref = refs[6]
        S_ref[...] = S_ref[...].astype(jnp.bfloat16).astype(jnp.float32)

    monkeypatch.setattr(kernels, "_fwd_kernel", rounded)
    rows = lambda t: jnp.moveaxis(t.reshape(1, 4, L, 2, 2), (3, 1, 4, 2),
                                  (1, 2, 3, 4)).reshape(1, 2, 4, 2 * L)
    S = kernels._fwd_call(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v,
                          rows(g), rows(beta), interpret=True)[1]
    assert _rel(S, want) > limit                  # 2.6e-3


def _three_passes(a, b, ca=1, cb=0):
    """A float32 product from bf16 hi + lo parts with lo x lo dropped (16 bits
    of mantissa): what this kernel's first version formed its inverse with."""
    def parts(x):
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    (ah, al), (bh, bl) = parts(a), parts(b)
    return (kernels._dot(ah, bh, ca, cb) + kernels._dot(ah, bl, ca, cb)
            + kernels._dot(al, bh, ca, cb))


@pytest.mark.parametrize("inverse", ["float32", "three_passes"])
@pytest.mark.parametrize("what", ["values", "final_state", "gradients"])
def test_keys_that_resemble_each_other_under_a_slow_decay(what, inverse,
                                                          monkeypatch):
    """Keys at cosine 0.5, beta 0.9, a position keeps 0.99 (the case of
    ``test_the_inverse_by_blocks_is_the_inverse_where_the_products_lose_it``):
    the XLA form's inverse by products is lost to cancellation there; the
    kernel inverts block by block, in float32 products as the configuration
    states, and is the recurrence.  The same kernel with the inverse's
    products in three bf16 passes is NOT, by these limits."""
    q, k, v, g, beta = _inputs(1, 2, seed=2, keep=0.99)
    k = k + k[:, :1, :1]
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    assert 0.4 < float(jnp.mean(jnp.einsum("btgd,bsgd->bgts", k, k))) < 0.6
    args = (q, k, v, g, jnp.full_like(beta, 0.9))
    if inverse == "three_passes":
        monkeypatch.setattr(kernels, "_dot_f32", _three_passes)
    # (a jit of its own: the launchers' eager traces would hold the product)
    kernel = lambda *a: jax.jit(_window("interpret"))(*a)
    if what == "gradients":
        scalar = lambda f: lambda *a: (jnp.sum(jnp.sin(f(*a)[0]))
                                       + jnp.sum(jnp.square(f(*a)[1])))
        got, want = (jax.grad(scalar(f), argnums=(0, 1, 2, 3, 4))(*args)
                     for f in (kernel, _sequential))
        worst = max(_rel(a, b_) for a, b_ in zip(got, want))
        # 9.7e-7 (v) in float32; 8.2e-6 in three passes
        assert (worst < 3e-6) == (inverse == "float32"), worst
        return
    at = 0 if what == "values" else 1
    want = _sequential(*args)[at]
    read = _rel(kernel(*args)[at], want)
    # 6.5e-7, 6.7e-7 in float32; 4.5e-6, 5.4e-6 in three passes
    assert (read < 2e-6) == (inverse == "float32"), read
    assert not _rel(_window("xla")(*args)[at], want) < 1.0        # 33; 181


# -- the program holds each kernel once ----------------------------------------

def _lowered_for_the_chip(c, blocks, monkeypatch, T=2 * L, b=1, devices=1,
                          kind="D"):
    """The TPU lowering (from the CPU) of a loss over ``blocks`` delta-rule
    mixers of ``kind`` ("D" the gated rule, "K" the channel-gated one), each
    under ``jax.checkpoint`` as ``window_pass`` holds it: a target pass, and
    the online pass with its gradient."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    cd = jnp.bfloat16
    shape = lambda s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt)
    params = [dict({name: shape(s) for name, (_, s) in
                    hybrid.layer_param_specs(kind, c).items()},
                   norm=shape((c.d_model,))) for _ in range(blocks)]
    window = hybrid.gdn_window if kind == "D" else hybrid.kda_window

    def trunk(params, x):
        for p in params:
            @jax.checkpoint
            def mix(p, x):
                out, S, _ = window(
                    p, hybrid.rms_norm(x, p["norm"], c.norm_eps), c, cd)
                return x + out.astype(cd), S
            x, _ = mix(p, x)
        return x.astype(jnp.float32)

    def step(params, target, x):
        y = jax.lax.stop_gradient(trunk(target, x))
        return jax.value_and_grad(
            lambda params: jnp.mean((trunk(params, x) - y) ** 2))(params)

    return jax.jit(step).trace(
        params, params, shape((b, T, c.d_model), cd)).lower(
            lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("blocks", [1, 3])
def test_a_program_holds_each_kernel_body_once(blocks, monkeypatch):
    """Three blocks x (target, online, recomputed) launch the forward kernel
    nine times and the backward three: the lowering holds ONE body of each
    (a body a call site is what cost PR 33 its set-up)."""
    c = dataclasses.replace(PRESETS["tiny-qwen"], gdn_head_dim=D, gdn_chunk=L)
    text = _lowered_for_the_chip(c, blocks, monkeypatch)
    bodies = re.findall(r'kernel_name = "(\w+)"', text)
    assert sorted(bodies) == ["gdn_chunk_bwd", "gdn_chunk_fwd"], bodies
    assert text.count("tpu_custom_call") == 2
    calls = re.findall(r"call @(gdn_chunk_\w+)\(", text)
    assert calls.count("gdn_chunk_fwd") == 3 * blocks
    assert calls.count("gdn_chunk_bwd") == blocks


def test_shapes_the_tiles_do_not_fit_take_the_xla_form(monkeypatch):
    """``tiny-qwen``: heads of 8, chunks of 4."""
    c = PRESETS["tiny-qwen"]
    assert not kernels.fits(c.gdn_chunk, c.gdn_v_heads // c.gdn_k_heads,
                            c.gdn_head_dim, c.gdn_head_dim)
    assert kernels.fits(L, 2, D, D) and not kernels.fits(L, 1, D, D)
    text = _lowered_for_the_chip(c, 1, monkeypatch, T=8)
    assert "tpu_custom_call" not in text and "gdn_chunk" not in text


def test_more_than_one_chip_takes_the_xla_form(monkeypatch):
    """The launchers have no sharding rule: on a mesh an unpartitioned
    kernel call would be replicated or gathered."""
    c = dataclasses.replace(PRESETS["tiny-qwen"], gdn_head_dim=D, gdn_chunk=L)
    text = _lowered_for_the_chip(c, 1, monkeypatch, devices=4)
    assert "tpu_custom_call" not in text and "gdn_chunk" not in text


@pytest.mark.parametrize("kernel", ["pallas", "XLA", "Interpret", ""])
def test_a_kernel_the_window_does_not_know_is_refused(kernel):
    with pytest.raises(AssertionError):
        gated_delta.gated_delta_chunked(*_inputs(1, 1), L, kernel=kernel)


# -- the chip's compiler takes both kernels at the cell's shapes ----------------

@pytest.fixture(scope="module")
def one_chip():
    """A v5e that is described and not attached (only the worker that is
    handed this file loads the TPU's library)."""
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever stops the description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("kernel", ["gdn_chunk_fwd", "gdn_chunk_bwd"])
def test_the_tpu_compiler_takes_the_kernel_at_the_cells_shapes(kernel,
                                                               one_chip):
    """Row 21's published widths, 4 segments of 2,048 positions: what Mosaic
    refuses (a misaligned slice, too much fast memory) it refuses here."""
    import functools

    b, T, G, r = 4, 2048, 16, 2
    of = lambda s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt,
                                                        sharding=one_chip)
    qk, v = of((b, T, G, D), jnp.bfloat16), of((b, T, G * r, D))
    rows = of((b, G, T // L, r * L))
    args = [qk, qk, v, rows, rows]
    if kernel == "gdn_chunk_bwd":
        args += [of((b, T // L, G * r, D, D), jnp.bfloat16),
                 of((b, T // L, G, L, r * L)), v, of((b, G * r, D, D))]
    call = kernels._fwd_call if kernel == "gdn_chunk_fwd" \
        else kernels._bwd_call
    compiled = jax.jit(functools.partial(call, interpret=False)).lower(
        *args).compile()
    assert kernel in compiled.as_text()


# -- the channel-gated rule's pair (ops/pallas_kda.py) ---------------------------

SUB = 16


def _kda_inputs(b, chunks, h=2, seed=0, decay=None):
    """q, k, v, g (a key channel), beta of a window of ``chunks`` chunks.
    ``decay``: None (what a channel keeps a position drawn, 0.74 in the
    mean), "slow" (0.99 a position, keys at cosine 0.5, beta 0.9) or
    "underflow" (half the channels keep e^-2 a position, -32 over a
    sub-block, and one e^-30)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    T = chunks * L
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, T, h, D))) / D ** 0.5
    k = unit(jax.random.normal(ks[1], (b, T, h, D)))
    v = jax.random.normal(ks[2], (b, T, h, D))
    g = -0.3 * jax.nn.softplus(jax.random.normal(ks[3], (b, T, h, D)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, T, h)))
    if decay == "slow":
        k = unit(k + k[:, :1])
        g = jnp.full_like(g, np.log(0.99))
        beta = jnp.full_like(beta, 0.9)
    elif decay == "underflow":
        g = g.at[..., ::2].set(-2.0).at[..., 1].set(-30.0)
    return q, k, v, g, beta


def _kda(kernel, cd=jnp.float32):
    return lambda *a: gated_delta.kda_chunked(*a, L, SUB, cd, kernel=kernel)


def _kda_grads(f, args):
    scalar = lambda *a: (jnp.sum(jnp.sin(f(*a)[0]))
                         + jnp.sum(jnp.square(f(*a)[1])))
    return jax.grad(scalar, argnums=(0, 1, 2, 3, 4))(*args)


@pytest.mark.parametrize("what", ["values", "final_state"])
@pytest.mark.parametrize("b,chunks,h", [(1, 2, 2), (2, 3, 4)])
def test_the_kda_kernel_window_is_the_xla_form(b, chunks, h, what):
    """Two heads stack to one 128-row tile; four are a grid step of two
    tiles."""
    args = _kda_inputs(b, chunks, h)
    at = 0 if what == "values" else 1
    got = _kda("interpret")(*args)[at]
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, _kda("xla")(*args)[at], rtol=2e-5,
                               atol=2e-6)                     # 9e-8 rel


def test_the_kda_backward_kernel_gives_the_xla_forms_gradients():
    """In q, k, v, g (a key channel) and beta, through the outputs AND the
    last state."""
    args = _kda_inputs(1, 3, seed=3)
    got, want = (_kda_grads(_kda(kernel), args)
                 for kernel in ("interpret", "xla"))
    for name, a, b_ in zip("q k v g beta".split(), got, want):
        assert a.shape == b_.shape and bool(jnp.all(jnp.isfinite(a))), name
        assert _rel(a, b_) < 5e-6, (name, _rel(a, b_))      # <= 1.1e-6


@pytest.mark.parametrize("decay", ["slow", "underflow"])
def test_kda_slow_and_underflowing_channel_decays(decay):
    """bfloat16 compute, as the cell's: the kernels' outputs and gradients
    finite, and no farther from the float32 XLA form than the bfloat16 XLA
    form is (the rounding the configuration states), at a slow decay with
    keys at cosine 0.5 and where channels underflow inside a sub-block."""
    args = _kda_inputs(1, 2, seed=7, decay=decay)
    truth = _kda("xla")(*args)
    truth = list(truth) + list(_kda_grads(_kda("xla"), args))
    kernel = _kda("interpret", jnp.bfloat16)
    xla = _kda("xla", jnp.bfloat16)
    got = list(kernel(*args)) + list(_kda_grads(kernel, args))
    ref = list(xla(*args)) + list(_kda_grads(xla, args))
    for name, a, b_, t in zip("o S q k v g beta".split(), got, ref, truth):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert _rel(a, t) < 1.25 * _rel(b_, t) + 1e-6, (
            name, _rel(a, t), _rel(b_, t))


def _kda_sequential(q, k, v, g, beta):
    """The channel-gated recurrence, a position at a time (the actor's
    step)."""
    def position(S, inp):
        o, S = gated_delta.gated_delta_step(*inp, S)
        return S, o

    tm = lambda t: jnp.moveaxis(t, 1, 0)
    S, o = jax.lax.scan(
        position, jnp.zeros((q.shape[0], q.shape[2], D, D)),
        tuple(tm(t) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), S


def test_a_kda_padded_position_decays_nothing_and_writes_nothing():
    """A window of 100 positions padded to two chunks with g = beta = 0, as
    ``kda_window`` pads it: the kernels are the recurrence on the 100."""
    q, k, v, g, beta = _kda_inputs(1, 2, seed=4)
    real = 100
    g, beta = g.at[:, real:].set(0.0), beta.at[:, real:].set(0.0)
    o, S = _kda("interpret")(q, k, v, g, beta)
    o_want, S_want = _kda_sequential(
        *(t[:, :real] for t in (q, k, v, g, beta)))
    np.testing.assert_allclose(o[:, :real], o_want, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(S, S_want, rtol=2e-5, atol=2e-6)


def _kimi(**widths):
    return dataclasses.replace(PRESETS["tiny-kimi"], kda_head_dim=D,
                               kda_chunk=L, kda_sub=SUB, **widths)


@pytest.mark.parametrize("blocks", [1, 4])
def test_a_program_holds_each_kda_kernel_body_once(blocks, monkeypatch):
    """Four K blocks x (target, online, recomputed) launch the forward
    kernel twelve times and the backward four: the lowering holds ONE body
    of each."""
    text = _lowered_for_the_chip(_kimi(), blocks, monkeypatch, kind="K")
    bodies = re.findall(r'kernel_name = "(\w+)"', text)
    assert sorted(bodies) == ["kda_chunk_bwd", "kda_chunk_fwd"], bodies
    assert text.count("tpu_custom_call") == 2
    calls = re.findall(r"call @(kda_chunk_\w+)\(", text)
    assert calls.count("kda_chunk_fwd") == 3 * blocks
    assert calls.count("kda_chunk_bwd") == blocks


def test_kda_shapes_the_tiles_do_not_fit_take_the_xla_form(monkeypatch):
    """``tiny-kimi``: heads of 8, chunks of 4, sub-blocks of 2."""
    c = PRESETS["tiny-kimi"]
    assert not pallas_kda.fits(c.kda_chunk, c.kda_sub, c.kda_head_dim,
                               c.kda_head_dim, c.kda_heads)
    assert pallas_kda.fits(L, SUB, D, D, 32)
    assert not pallas_kda.fits(L, SUB, D, D, 3)       # half a tile of heads
    assert not pallas_kda.fits(L, 4, D, D, 32)        # a sub-block of 4 rows
    assert not pallas_kda.fits(L, 128, D, D, 32)      # more than the chunk
    assert not pallas_kda.fits(256, SUB, D, D, 32)    # a chunk over a tile
    text = _lowered_for_the_chip(c, 1, monkeypatch, T=8, kind="K")
    assert "tpu_custom_call" not in text and "kda_chunk" not in text


def test_kda_on_more_than_one_chip_takes_the_xla_form(monkeypatch):
    text = _lowered_for_the_chip(_kimi(), 1, monkeypatch, devices=4,
                                 kind="K")
    assert "tpu_custom_call" not in text and "kda_chunk" not in text


@pytest.mark.parametrize("kernel", ["pallas", "XLA", ""])
def test_a_kernel_the_kda_window_does_not_know_is_refused(kernel):
    with pytest.raises(AssertionError):
        gated_delta.kda_chunked(*_kda_inputs(1, 1), L, SUB, kernel=kernel)


@pytest.mark.parametrize("kernel", ["kda_chunk_fwd", "kda_chunk_bwd"])
def test_the_tpu_compiler_takes_the_kda_kernel_at_the_cells_shapes(
        kernel, one_chip):
    """Row 22's published widths, 4 segments of 2,048 positions, 32 heads
    of 128, chunks of 64 in sub-blocks of 16."""
    import functools

    b, T, h = 4, 2048, 32
    of = lambda s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt,
                                                        sharding=one_chip)
    x = of((b, T, h, D))
    args = [x, x, x, x, of((b, h // 2, T // L, 2 * L))]
    if kernel == "kda_chunk_bwd":
        args += [of((b, T // L, h, D, D), jnp.bfloat16),
                 of((b, T // L, h // 2, L, 2 * L)), x, of((b, h, D, D))]
    call = pallas_kda._fwd_call if kernel == "kda_chunk_fwd" \
        else pallas_kda._bwd_call
    compiled = jax.jit(functools.partial(
        call, sub=SUB, cd=jnp.dtype(jnp.bfloat16), interpret=False)).lower(
            *args).compile()
    assert kernel in compiled.as_text()
