"""Kimi Linear against its plain reference (`benchmark/models/kimi_linear.py`)
at the rehearsal size of the cell `kimi_linear_ep32_s4096`: what every
decoder suite holds (`tests/decoder_suite.py`: the program's logits mixer
by mixer and for the whole model, one train step's gradients for every
kind of parameter, that a wrong model is caught by the cell's tolerance)
on this model's data, and its own: the chunked KDA against the token
recurrence, the expert layer's shares against the uncut layer,
droplessness under skew, the router's correction, the layers as a user
calls them and the short convolution's forms, the cell's counters.

Run as a script on the attached TPU (`tests/decoder_suite.py` has the
arguments; with none, the gradients at the published widths on one
512-token row):

    python3 tests/test_kimi_linear_reference.py
"""

from __future__ import annotations

import math

import numpy as np
import pytest

from decoder_suite import *  # noqa: F401,F403 — the shared cases, on SUITE
from decoder_suite import (expert_params, highest, main, rel,
                           settled_counters, state)
from kernel_cases import value_and_grads

from benchmark.models import kimi_linear as adapter  # noqa: E402

CELL = "kimi_linear_ep32_s4096"


def _mixer_program(which, model, batch, seq):
    """One mixer or feed-forward alone in a Program: `u` in, `y` out."""
    import paddle_tpu as fluid
    from paddle_tpu.models import kimi_linear as zoo

    cfg = adapter.config(model)
    u = fluid.layers.data("u", [batch, seq, cfg.hidden_size],
                          append_batch_size=False)
    if which == "kda":
        return zoo._kda_mixer(u, cfg, "m")
    if which == "latent":
        return zoo._latent_mixer(u, cfg, "m")
    if which == "dense":
        return zoo._ffn(u, cfg.intermediate_size, "m.mlp", cfg)
    return zoo._expert_ffn(u, cfg, "m")[0]


def _want_mixer(which, p, feeds, model, wrong=()):
    u = feeds["u"]
    if which == "dense":
        return highest(adapter._ffn, p, u, "m.mlp")
    fn = {"kda": adapter.kda_mixer, "latent": adapter.latent_mixer,
          "experts": adapter.expert_ffn}[which]
    return highest(fn, p, u, "m", model)


KINDS = {
    "embedding": ("kimi.embed",), "head": ("kimi.head.w_0",),
    "rms_norm": (".attn_norm.w_0", ".ffn_norm.w_0", "final_norm.w_0",
                 ".kv_a_norm.w_0", ".o_norm.w_0"),
    "kda_projection": (".kda.q.w_0", ".kda.k.w_0", ".kda.v.w_0", ".kda.o.w_0"),
    "kda_conv": ("_conv.w_0",),
    "kda_decay": (".f_a.w_0", ".f_b.w_0", ".A_log", ".dt_bias"),
    "kda_beta": (".kda.b.w_0",),
    "kda_gate": (".g_a.w_0", ".g_b.w_0"),
    "latent": (".mla.q.w_0", ".mla.kv_a.w_0", ".mla.kv_b.w_0", ".mla.o.w_0"),
    "dense_ffn": (".mlp.gate.w_0", ".mlp.up.w_0", ".mlp.down.w_0"),
    "shared_expert": (".shared.gate.w_0", ".shared.up.w_0", ".shared.down.w_0"),
    "router": (".moe.gate",),
    "experts": (".moe.w_gate", ".moe.w_up", ".moe.w_down"),
}

SUITE = Suite(  # noqa: F405
    CELL, adapter, kinds=KINDS,
    mixers=("kda", "latent", "dense", "experts"),
    mixer_program=_mixer_program, want_mixer=_want_mixer,
    # the reference with its last layer left out, or without the delta
    # rule's write (beta = 0): twice the cell's limit and more
    wrong={"drop_layers": caught(amp=2, drop_layers=1),  # noqa: F405
           "no_delta": caught(amp=2, no_delta=True)},  # noqa: F405
    # under AMP the cell's own tolerance holds here, loss and all
    amp_loss_room=1,
    seed=31001, gradient_row=512, checkpointed="kda_recurrence",
    step_counters=("kda_dispatch_pallas", "kda_dispatch_chunked",
                   "short_conv_dispatch_pallas", "attn_dispatch_flash",
                   "moe_dispatch_grouped", "moe_dispatch_gmm"),
    gauges=("moe_block_rows", "moe_experts_held", "moe_experts_total",
            "kda_lockstep_chunks"))


# --------------------------------------------- chunked KDA, the math


@pytest.mark.parametrize("length,g_lo,g_hi", [
    (64, -0.1, -0.001),  # one whole chunk, mild decay
    (128, -1.0, -0.01),  # two whole chunks
    (100, -8.0, -3.0),  # decays near 0: exp(-G) would overflow in a chunk
    (200, -1e-4, -1e-6),  # decays near 1: the state forgets nothing
    (37, -20.0, 0.0),  # shorter than a chunk, both extremes in one row
    (130, -2.0, -0.01),  # two tokens into a third chunk
])
def test_chunked_kda_equals_the_token_recurrence(length, g_lo, g_hi):
    from paddle_tpu.ops.linear_attn_ops import kda_chunked

    r = np.random.RandomState(length)
    b, h, dk, dv = 2, 3, 16, 8

    def unit(t):
        return t / np.linalg.norm(t, axis=-1, keepdims=True)

    args = [np.asarray(t, np.float32) for t in (
        unit(r.randn(b, length, h, dk)), unit(r.randn(b, length, h, dk)),
        r.randn(b, length, h, dv), r.uniform(g_lo, g_hi, (b, length, h, dk)),
        r.uniform(0, 1, (b, length, h)))]
    got, g_got = value_and_grads(kda_chunked, args)
    want, g_want = value_and_grads(adapter.kda_recurrence, args)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-6)
    for a, w in zip(g_got, g_want):
        assert np.isfinite(a).all()
        assert rel(a, w) < 1e-4


# ------------------------------------------ the flash kernel, by name


def test_latent_attention_through_the_flash_kernel(monkeypatch, attn_path):
    """The blocked kernel, interpreted, with values narrower than the
    keys: forced by name, since the CPU's dispatch never chooses it."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    attn_path("flash")
    m = SUITE.mixer("latent", batch=1, seq=160, seed=2)
    assert m.bumped("attn_dispatch_flash") == 1
    assert rel(m.got, m.want()) < 2e-5


# -------------------------------------------------- the expert layer


def _shares(p, u, total, held, k, bias=True):
    """`Out` and `Load` of one `moe_experts` op a share, all in one
    Program, each given its slice of the uncut layer's experts."""
    import paddle_tpu as fluid

    hidden, width = u.shape[-1], p["m.moe.w_gate"].shape[-1]
    x = fluid.layers.data("u", list(u.shape), append_batch_size=False)
    outs = []
    for lo in range(0, total, held):
        outs += fluid.layers.moe_experts(
            x, experts_total=total, experts_held=held, d_ff=width, k=k,
            held_from=lo, scaling=2.446,
            param_attr=fluid.ParamAttr(name=f"share{lo}"))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    for lo in range(0, total, held):
        scope.set(f"share{lo}.gate", p["m.moe.gate"])
        scope.set(f"share{lo}.bias", p["m.moe.bias"] if bias
                  else np.zeros_like(p["m.moe.bias"]))
        for w in ("w_gate", "w_up", "w_down"):
            scope.set(f"share{lo}.{w}", p[f"m.moe.{w}"][lo:lo + held])
    got = exe.run(feed={"u": u}, fetch_list=outs)
    return got[0::2], got[1::2]


def _layer_model(held, k, held_from=0):
    return {"num_experts_per_token": k, "num_experts": held,
            "held_from": held_from, "moe_renormalize": True,
            "routed_scaling_factor": 2.446, "num_shared_experts": 1}


@pytest.mark.parametrize("total,held,k", [(8, 2, 2), (64, 2, 2)])
def test_the_shares_add_up_to_the_uncut_layer(total, held, k):
    """Every share's routed part, and the shared expert counted once,
    equal the reference's layer with all the experts held. With 2 of 64
    held a share's assignments fit the usual branch of the grouped
    product; with 2 of 8 they need the full one."""
    r = np.random.RandomState(total)
    hidden, width = 16, 8
    p = expert_params(r, hidden, width, total)
    u = r.randn(2, 24, hidden).astype(np.float32)
    outs, loads = _shares(p, u, total, held, k)
    assert int(np.sum(loads)) == u.shape[0] * u.shape[1] * k
    shared = highest(adapter._ffn, p, u, "m.shared")
    uncut = highest(adapter.expert_ffn, p, u, "m", _layer_model(total, k))
    assert rel(shared + sum(outs), uncut) < 1e-5
    # and one share alone is the reference's share
    p_share = dict(p, **{f"m.moe.{w}": p[f"m.moe.{w}"][held:2 * held]
                         for w in ("w_gate", "w_up", "w_down")})
    one = highest(adapter.expert_ffn, p_share, u, "m",
                  _layer_model(held, k, held_from=held))
    assert rel(shared + outs[1], one) < 1e-5


def test_dropless_under_skew():
    """Every token routed to the two experts held: 16 times what a
    balanced router would send them, and nothing is dropped."""
    r = np.random.RandomState(5)
    total, held, k, hidden, width = 32, 2, 2, 16, 8
    p = expert_params(r, hidden, width, total)
    p["m.moe.bias"] = np.where(np.arange(total) < held, 10.0, 0.0).astype(
        np.float32)
    u = r.randn(2, 40, hidden).astype(np.float32)
    outs, loads = _shares(p, u, total, held, k)
    tokens = u.shape[0] * u.shape[1]
    assert loads[0].tolist() == [tokens, tokens]  # each token, both experts
    assert all(int(np.sum(load)) == 0 for load in loads[1:])
    want = highest(adapter.expert_ffn, p, u, "m", _layer_model(held, k))
    shared = highest(adapter._ffn, p, u, "m.shared")
    assert rel(shared + outs[0], want) < 1e-5
    assert all(not np.abs(o).any() for o in outs[1:])


def test_router_correction_changes_the_selection_and_not_the_weights():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel.moe import moe_route

    r = np.random.RandomState(9)
    x = jnp.asarray(r.randn(64, 16), jnp.float32)
    gate = jnp.asarray(r.randn(16, 8) * 0.3, jnp.float32)
    bias = jnp.asarray(r.randn(8) * 0.3, jnp.float32)
    idx0, w0 = moe_route(x, gate, jnp.zeros(8), 2, 2.446)
    idx1, w1 = moe_route(x, gate, bias, 2, 2.446)
    changed = np.any(np.sort(idx0, -1) != np.sort(idx1, -1), axis=-1)
    assert 0 < changed.sum() < len(changed)
    scores = jax.nn.sigmoid(jnp.dot(x, gate, precision="highest"))
    picked = np.take_along_axis(np.asarray(scores), np.asarray(idx1), -1)
    np.testing.assert_allclose(
        w1, 2.446 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w1).sum(-1), 2.446, rtol=1e-5)
    # where the selection did not change, neither did the weights
    same = ~changed
    np.testing.assert_allclose(np.sort(w0, -1)[same], np.sort(w1, -1)[same],
                               rtol=1e-6)


# ------------------------------------------- the user's surface, counts


def test_layers_build_the_block_without_the_zoo():
    """rms_norm, short_conv1d, kda_attention and moe_experts as a user
    calls them, with static shapes from the shape functions."""
    import paddle_tpu as fluid
    from tools.verify_bench_programs import compare_static_vs_traced

    L = fluid.layers
    x = L.data("x", [2, 70, 32], append_batch_size=False)
    u = L.rms_norm(x, begin_norm_axis=2)
    q, k, v, g = (L.short_conv1d(L.fc(u, 32, num_flatten_dims=2,
                                      bias_attr=False)) for _ in range(4))
    o = L.kda_attention(q, k, v, g, L.fc(u, 4, num_flatten_dims=2), 4)
    y, load = L.moe_experts(L.elementwise_add(x, o), 16, 4, 24, k=2,
                            held_from=4, scaling=2.0, bias_scale=0.1)
    assert tuple(y.shape) == (2, 70, 32) and tuple(load.shape) == (4,)
    main = fluid.default_main_program()
    feeds = {"x": ((2, 70, 32), "float32")}
    n, mismatches, unknown = compare_static_vs_traced(main, feeds)
    assert n >= 10 and mismatches == [] and unknown == []
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    out, counts = exe.run(feed={"x": np.random.RandomState(0).randn(
        2, 70, 32).astype(np.float32)}, fetch_list=[y, load])
    assert np.isfinite(out).all() and counts.dtype == np.int32
    assert 0 < counts.sum() <= 2 * 70 * 2


@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
def test_short_conv1d_with_and_without_its_bias(bias):
    """The layer with `bias_attr` (a [c] parameter inside the SiLU: Mamba's
    convolution) and without it, value and gradients against four shifted
    products written out; without a bias the op has no `Bias` input and
    its lowering traces what it traced before the bias existed."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.initializer import Uniform
    from paddle_tpu.ops import linear_attn_ops
    from paddle_tpu.param_attr import ParamAttr
    from tools.verify_bench_programs import compare_static_vs_traced

    L = fluid.layers
    x = L.data("x", [2, 21, 6], append_batch_size=False)
    x.stop_gradient = False
    y = L.short_conv1d(
        x, 4, param_attr=ParamAttr(name="f", initializer=Uniform(-0.5, 0.5)),
        bias_attr=ParamAttr(name="b", initializer=Uniform(-0.5, 0.5))
        if bias else None)
    main = fluid.default_main_program()
    (op,) = [o for o in main.global_block().ops if o.type == "short_conv1d"]
    assert sorted(op.inputs) == (["Bias", "Filter", "X"] if bias
                                 else ["Filter", "X"])
    w = np.random.RandomState(1).randn(2, 21, 6).astype(np.float32)
    loss = L.reduce_sum(L.elementwise_mul(y, L.assign(w)))
    params = ["f", "b"] if bias else ["f"]
    grads = fluid.backward.calc_gradient(
        loss, [x] + [main.global_block().var(n) for n in params])
    n, mismatches, unknown = compare_static_vs_traced(
        main, {"x": ((2, 21, 6), "float32")})
    assert n >= 2 and mismatches == [] and unknown == []
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    data = np.random.RandomState(0).randn(2, 21, 6).astype(np.float32)
    got = exe.run(feed={"x": data}, fetch_list=[y, *grads])
    p = state(params)
    assert not bias or np.abs(p["b"]).max() > 0.05

    def by_hand(a, f, b):
        padded = jnp.pad(a, ((0, 0), (3, 0), (0, 0)))
        out = sum(padded[:, i:i + 21] * f[:, i] for i in range(4)) + b
        return out * jax.nn.sigmoid(out)

    b = p["b"] if bias else jnp.zeros(6)
    np.testing.assert_allclose(got[0], by_hand(data, p["f"], b), atol=1e-6)
    want = jax.grad(lambda a, f, b: jnp.sum(by_hand(a, f, b) * w),
                    argnums=(0, 1, 2))(jnp.asarray(data), p["f"], b)
    for g, g_want in zip(got[1:], want):
        np.testing.assert_allclose(g, g_want, atol=1e-5)
    # the bias moved the output, and the first token sees it alone
    if bias:
        first = p["f"][:, 3] * data[:, 0] + p["b"]
        np.testing.assert_allclose(got[0][:, 0], first / (1 + np.exp(-first)),
                                   atol=1e-6)
    else:  # the jaxpr of the call without a bias is the one-argument call's
        a, f = jnp.asarray(data), jnp.asarray(p["f"])
        assert str(jax.make_jaxpr(linear_attn_ops.short_conv)(a, f)) == str(
            jax.make_jaxpr(lambda a, f: linear_attn_ops.short_conv(
                a, f, None))(a, f))


@pytest.mark.parametrize("width,bias", [(3, False), (4, True)],
                         ids=["three_taps", "four_taps_bias"])
def test_short_conv1d_without_its_activation(width, bias):
    """The layer with `act=None` (LFM2's convolution, between two gates):
    the op carries `activation` "none", its value and gradients are those
    of the shifted products written out with nothing after them, and the
    lowering's backward counts itself; with the default `act` the op has
    no such attribute, as before it could."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import profiler
    from paddle_tpu.initializer import Uniform
    from paddle_tpu.param_attr import ParamAttr
    from tools.verify_bench_programs import compare_static_vs_traced

    L = fluid.layers
    x = L.data("x", [2, 21, 6], append_batch_size=False)
    x.stop_gradient = False
    y = L.short_conv1d(
        x, width, param_attr=ParamAttr(name="f", initializer=Uniform(-0.5, 0.5)),
        bias_attr=ParamAttr(name="b", initializer=Uniform(-0.5, 0.5))
        if bias else None, act=None)
    with_silu = L.short_conv1d(x, width, param_attr=ParamAttr(name="f"))
    main = fluid.default_main_program()
    linear, default = [o for o in main.global_block().ops
                       if o.type == "short_conv1d"]
    assert linear.attrs["activation"] == "none"
    assert "activation" not in default.attrs
    with pytest.raises(ValueError, match="act"):
        L.short_conv1d(x, width, act="relu")
    w = np.random.RandomState(1).randn(2, 21, 6).astype(np.float32)
    loss = L.reduce_sum(L.elementwise_mul(y, L.assign(w)))
    params = ["f", "b"] if bias else ["f"]
    grads = fluid.backward.calc_gradient(
        loss, [x] + [main.global_block().var(n) for n in params])
    n, mismatches, unknown = compare_static_vs_traced(
        main, {"x": ((2, 21, 6), "float32")})
    assert n >= 3 and mismatches == [] and unknown == []
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    data = np.random.RandomState(0).randn(2, 21, 6).astype(np.float32)
    before = profiler.counters().get("short_conv_linear_calls", 0)
    got = exe.run(feed={"x": data}, fetch_list=[y, with_silu, *grads])
    assert profiler.counters()["short_conv_linear_calls"] == before + 1
    p = state(params)

    def by_hand(a, f, b):
        padded = jnp.pad(a, ((0, 0), (width - 1, 0), (0, 0)))
        return sum(padded[:, i:i + 21] * f[:, i] for i in range(width)) + b

    b = p["b"] if bias else jnp.zeros(6)
    want = by_hand(data, p["f"], b)
    np.testing.assert_allclose(got[0], want, atol=1e-6)
    if not bias:  # the default's output is the SiLU of this one's
        np.testing.assert_allclose(got[1], want * jax.nn.sigmoid(want),
                                   atol=1e-6)
    want_grads = jax.grad(lambda a, f, b: jnp.sum(by_hand(a, f, b) * w),
                          argnums=(0, 1, 2))(jnp.asarray(data), p["f"], b)
    for g, g_want in zip(got[2:], want_grads):
        np.testing.assert_allclose(g, g_want, atol=1e-5)
    # the first token sees the last tap alone, and nothing squashes it
    np.testing.assert_allclose(
        got[0][:, 0], p["f"][:, width - 1] * data[:, 0] + np.asarray(b),
        atol=1e-6)


@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
def test_short_conv_backward_written_out_is_the_vjp_of_the_taps(bias):
    """`short_conv`'s backward (a `jax.custom_vjp`) against `jax.vjp` of
    the same taps with bf16 rows, as AMP gives them: dx, dw and dbias to
    float32 rounding, each in its operand's dtype. And its shape: the
    cotangent is padded once and sliced, where the transpose of the
    forward's slices pads each tap's float32 product (four arrays of
    `[b, s + 3, c]` that XLA kept in HBM: PERF.md, PR 46)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import linear_attn_ops as ops

    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(2, 37, 256), jnp.bfloat16)
    w = jnp.asarray(rng.randn(256, 4) * 0.5, jnp.float32)
    b = jnp.asarray(rng.randn(256) * 0.5, jnp.float32) if bias else None
    dy = jnp.asarray(rng.randn(2, 37, 256), jnp.bfloat16)

    def plain(x, w, b=None):
        out = ops._conv_taps(x, w, b)[1]
        return (out * jax.nn.sigmoid(out)).astype(x.dtype)

    operands = (x, w, b) if bias else (x, w)
    y, pull = jax.vjp(lambda *a: ops.short_conv(*a), *operands)
    y_want, pull_want = jax.vjp(plain, *operands)
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(y_want, np.float32))
    for got, want, like in zip(pull(dy), pull_want(dy), operands):
        assert got.dtype == like.dtype and got.shape == like.shape
        got, want = (np.asarray(t, np.float32) for t in (got, want))
        tol = 2e-2 if like is x else 1e-4  # dx is rounded to bf16
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    text = str(jax.make_jaxpr(lambda *a: jax.vjp(
        lambda *o: ops.short_conv(*o), *a)[1](dy))(*operands))
    assert text.count(" pad[") == 3  # x forward and again, the cotangent once
    assert "optimization_barrier" in text


def test_counters_and_flops_of_the_cell():
    from paddle_tpu import profiler

    model, traffic = SUITE.cell(rehearse=False)
    assert (traffic["batch"], traffic["seq_len"]) == (1, 4096)
    # ISSUE 31's arithmetic: 335.6M matrix parameters a token, 2.01 GFLOP
    # trained, 602.4M parameters held
    per_token = adapter.matrix_params_per_token(model)
    assert abs(per_token / 1e6 - 335.6) < 0.1
    assert abs(6 * per_token / 1e9 - 2.01) < 0.01
    flops = adapter.flops_per_example(model, traffic)
    assert 8.0e12 < flops < 9.5e12
    c0 = profiler.counters()
    small, small_traffic = SUITE.cell()
    main, eval_prog, built, exe, _ = SUITE.built_model(small, small_traffic)
    batch = SUITE.batch_for(small, small_traffic)
    exe.run(eval_prog, feed=batch, fetch_list=built["check"])
    _, *loads = exe.run(main, feed=batch,
                        fetch_list=[built["loss"]] + built["loads"])
    c1 = settled_counters()
    # a set-up traces 1,010 ops (startup, the `for_test` clone, the train
    # step: `traced_ops` on the chip), and bumps no counter that is another
    # decoder's: no softmax router, no scaled or paired positions, no
    # compressed query, no second loss term, no convolution without a SiLU
    assert c1["program_traced_ops"] - c0.get("program_traced_ops", 0) == 1010
    for other in ("moe_route_softmax", "attn_rope_scaled", "mtp_depth",
                  "attn_latent_q_lora", "rope_interleaved", "loss_terms",
                  "short_conv_linear_calls"):
        assert c1.get(other, 0) == c0.get(other, 0), other
    experts = [op for op in main.global_block().ops
               if op.type == "moe_experts"]
    assert all(op.attr("score_func") == "sigmoid" for op in experts)
    # the rehearsal's shares are 1/4 (2 of 8 experts): a block of 7/16
    tokens = small_traffic["batch"] * small_traffic["seq_len"]
    assert c1["moe_block_rows"] == math.ceil(
        1.75 * 0.25 * tokens * experts[0].attr("k"))
    assert c1["kda_dispatch_chunked"] - c0.get("kda_dispatch_chunked", 0) >= 4
    grouped = c1["moe_dispatch_grouped"] - c0.get("moe_dispatch_grouped", 0)
    assert grouped >= 4
    # the steps' own count of the rows the held experts took: the
    # evaluation clone's and the train step's forward on one batch and one
    # state, and nothing for the gradient ops' replays
    assert c1["moe_rows_live"] - c0.get("moe_rows_live", 0) == 2 * sum(
        int(np.sum(load)) for load in loads)
    # on the plain path: the rehearsal's widths are no lane multiple, and
    # there is no Mosaic here (ops/pallas/grouped_matmul.py)
    assert c1.get("moe_dispatch_gmm", 0) == c0.get("moe_dispatch_gmm", 0)
    assert (c1["moe_experts_held"], c1["moe_experts_total"]) == (2, 8)
    assert len(loads) == 4 and all(x.shape == (2,) for x in loads)


if __name__ == "__main__":
    main(SUITE)
