"""Qwen3-Next against its plain reference (`tests/qwen3_next_reference.py`)
at the rehearsal size of the cell `qwen3_next_ep16_s4096`: every mixer
alone, the Gated DeltaNet mixer through the kernel pair and the attention
layer through `qk_prep` and the flash kernel (interpreted, heads of 128),
the whole model in float32 and under bf16 AMP, one train step's gradients
for every kind of parameter, that each wrong model is caught, the sixteen
shares of an expert layer against the uncut layer with the gated shared
expert counted once, the gauges and counters, and the cell's arithmetic.

Run as a script on the attached TPU, outside any timed window:

    python3 tests/test_qwen3_next_reference.py readings 1 2   # program, wrong models and fp8 reference against the reference
    python3 tests/test_qwen3_next_reference.py loads@3e-6 1 2   # held share by expert layer and the loss over the window's steps at a rate
    python3 tests/test_qwen3_next_reference.py gradients      # at the published widths on one 512-token row
"""

from __future__ import annotations

import inspect
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import qwen3_next_reference as ref  # noqa: E402 — beside this file
from test_kimi_linear_reference import (  # noqa: E402 — the shared helpers
    check_gradients, f32, highest, rel, state)
from test_mellum_reference import _fp8  # noqa: E402 — the matrices in e4m3

CELL = "qwen3_next_ep16_s4096"


def cell(rehearse=True, **config):
    from benchmark.harness import spec

    c = spec.cell(CELL, rehearse=rehearse)
    c["config"].update(config)
    return c["config"], c["traffic"]


def _move_norms(names, seed):
    """The norms' weights off their seeded 1, so that a zero-centred norm
    read as a plain one, a norm left out, or QK-norm after the positions
    (the same model at 1: a rotation of part of a head keeps its length)
    shows."""
    import paddle_tpu as fluid

    scope, r = fluid.global_scope(), np.random.RandomState(seed)
    for n in names:
        if n.endswith("norm.w_0"):
            scope.set(n, r.uniform(0.5, 1.5, np.shape(scope.get(n))).astype(
                np.float32))


def built_model(model, traffic, seed=3):
    """Programs, executor and the seeded state by name, in a scope of its
    own (the caller holds the guards)."""
    import paddle_tpu as fluid
    from benchmark.models import qwen3_next as adapter
    from benchmark.runners import train_loop

    main, startup, built, eval_prog = train_loop.build_programs(
        fluid, adapter, model, traffic, seed)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    names = [p.name for p in main.global_block().all_parameters()]
    _move_norms(names, seed)
    return main, eval_prog, built, exe, names


def batch_for(model, traffic, seed=0):
    from benchmark.models import qwen3_next as adapter

    return adapter.make_batch(np.random.RandomState(seed), model, traffic)


# At 64 wide, seeded as the cell is (matrices Normal(0, 0.02)), a product
# gives 0.16 of its input and a mixer next to nothing of the residual
# stream, so a wrong model does not show in the logits. With the matrices
# at 0.1 the layers weigh in the stream as at the published width
# (0.02 x sqrt(2048) = 0.9 a product; 0.1 x sqrt(64) = 0.8).
AS_AT_WIDTH = {"initializer_range": 0.1}


# ------------------------------------------------- the copy is a copy


def test_reference_copy_is_the_adapters_word_for_word():
    from benchmark.models import qwen3_next as adapter

    for name in ("held_layers", "_rms", "_silu", "_ffn", "_rope", "_conv",
                 "delta_recurrence", "delta_mixer", "attention_mixer",
                 "expert_ffn", "reference"):
        assert inspect.getsource(getattr(ref, name)) == inspect.getsource(
            getattr(adapter, name)), name
    assert (ref.SCORED_EVERY, ref.QUERY_BLOCK) == (
        adapter.SCORED_EVERY, adapter.QUERY_BLOCK)


def test_layer_kinds_follow_the_published_interval():
    from benchmark.models import qwen3_next as adapter
    from paddle_tpu.models.qwen3_next import Qwen3NextConfig

    model, _ = cell(rehearse=False)
    assert adapter.held_layers(model) == [
        (0, "linear_attention"), (1, "linear_attention"),
        (2, "linear_attention"), (3, "full_attention")]
    whole = dict(model, num_hidden_layers=48)
    kinds = [k for _, k in adapter.held_layers(whole)]
    assert kinds.count("full_attention") == 12
    assert [l for l, k in adapter.held_layers(whole)
            if k == "full_attention"] == list(range(3, 48, 4))
    cfg = adapter.config(model)
    assert cfg.layer_kinds() == adapter.held_layers(model)
    assert (cfg.rotary_dim, cfg.head_dim, cfg.rope_theta) == (64, 256, 1e7)
    assert (cfg.linear_num_key_heads, cfg.linear_num_value_heads) == (16, 32)
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_token,
            cfg.num_shared_experts, cfg.score_func) == (512, 32, 10, 1,
                                                        "softmax")
    assert cfg.shared_expert_gate and cfg.moe_renormalize
    assert Qwen3NextConfig().layer_kinds()[-1] == (47, "full_attention")
    with pytest.raises(ValueError, match="multiple"):
        Qwen3NextConfig(shared_expert_intermediate_size=700)


# ------------------------------------------ the program, mixer by mixer


def _mixer_program(which, model, batch=2, seq=80):
    """A mixer or an expert layer alone in a Program: `u` in, `y` out."""
    import paddle_tpu as fluid
    from benchmark.models import qwen3_next as adapter
    from paddle_tpu.models import decoder_parts

    cfg = adapter.config(model)
    u = fluid.layers.data("u", [batch, seq, cfg.hidden_size],
                          append_batch_size=False)
    if which == "delta":
        y = decoder_parts.gated_delta_net(u, cfg, "m")
    elif which == "attention":
        y = decoder_parts.attention(u, cfg, "m", gated=True,
                                    rope_theta=cfg.rope_theta,
                                    rotary_dim=cfg.rotary_dim)
    else:
        y, _ = decoder_parts.expert_ffn(u, cfg, "m")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    names = [p.name for p in
             fluid.default_main_program().global_block().all_parameters()]
    _move_norms(names, 5)
    return exe, y, names


def _want_mixer(which, p, u, model, wrong=()):
    return {"delta": lambda: ref.delta_mixer(p, u, "m", model, wrong),
            "attention": lambda: ref.attention_mixer(p, u, "m", model, wrong),
            "experts": lambda: ref.expert_ffn(p, u, "m", model, wrong)}[which]


WRONG_BY_MIXER = {
    "delta": ("key_head_mod", "one_decay", "no_conv_silu"),
    "attention": ("rope_whole_head", "no_attn_gate", "norm_after_rope"),
    "experts": ("no_shared_gate", "sigmoid_router", "no_renormalize"),
}


def test_every_wrong_model_belongs_to_a_mixer():
    from benchmark.models.qwen3_next import WRONG

    assert sorted(sum(WRONG_BY_MIXER.values(), ())) == sorted(WRONG)


@pytest.mark.parametrize("which", ["delta", "attention", "experts"])
def test_program_mixer_equals_reference(which):
    model, _ = cell(**AS_AT_WIDTH)
    exe, y, names = _mixer_program(which, model)
    u = np.random.RandomState(1).randn(2, 80, model["hidden_size"]).astype(
        np.float32)
    (got,) = exe.run(feed={"u": u}, fetch_list=[y])
    p = state(names)
    want = highest(_want_mixer(which, p, u, model))
    assert np.abs(want).max() > 1e-4  # something was computed
    assert rel(got, want) < 2e-5
    # and a mixer got wrong is no rounding of the right one
    for wrong in WRONG_BY_MIXER[which]:
        other = highest(_want_mixer(which, p, u, model, (wrong,)))
        assert rel(got, other) > 0.02, wrong


# heads of 128 lanes, which the kernels take, at the rehearsal's other sizes
LANES_128 = {"linear_key_head_dim": 128, "linear_value_head_dim": 128,
             "head_dim": 128}


def test_delta_mixer_through_the_kernel_pair(monkeypatch):
    """Heads of 128, two key heads under four value heads, rows of 200
    tokens (a ragged last chunk): the mixer's Program takes `gdn_fwd`
    under the interpreter and agrees with the token-a-step reference."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    from paddle_tpu import profiler

    before = profiler.counters()
    model, _ = cell(**AS_AT_WIDTH, **LANES_128)
    exe, y, names = _mixer_program("delta", model, batch=1, seq=200)
    u = np.random.RandomState(2).randn(1, 200, model["hidden_size"]).astype(
        np.float32)
    (got,) = exe.run(feed={"u": u}, fetch_list=[y])
    after = profiler.counters()
    assert after["kda_dispatch_pallas"] == before.get(
        "kda_dispatch_pallas", 0) + 1
    assert after["kda_decay_per_head"] == before.get(
        "kda_decay_per_head", 0) + 1
    assert after["kda_key_group"] == 2
    want = highest(_want_mixer("delta", state(names), u, model))
    assert rel(got, want) < 2e-5


def test_attention_through_qk_prep_and_the_flash_kernel(monkeypatch,
                                                        attn_path):
    """Heads of 128 lanes of which 32 turn: the blocked kernel and the
    `qk_prep` pair, interpreted, forced by name since the CPU's dispatch
    never chooses them."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    attn_path("flash")
    from paddle_tpu import profiler

    before = profiler.counters()
    model, _ = cell(**AS_AT_WIDTH, **LANES_128)
    exe, y, names = _mixer_program("attention", model, batch=1, seq=160)
    u = np.random.RandomState(2).randn(1, 160, model["hidden_size"]).astype(
        np.float32)
    (got,) = exe.run(feed={"u": u}, fetch_list=[y])
    after = profiler.counters()
    assert after["attn_dispatch_flash"] == before.get(
        "attn_dispatch_flash", 0) + 1
    assert after["attn_qk_prep_fused"] == before.get(
        "attn_qk_prep_fused", 0) + 1
    assert (after["attn_kv_group"], after["attn_rotary_lanes"]) == (2, 32)
    p = state(names)
    want = highest(_want_mixer("attention", p, u, model))
    assert rel(got, want) < 2e-5
    whole = highest(_want_mixer("attention", p, u, model,
                                ("rope_whole_head",)))
    assert rel(got, whole) > 0.02


# ------------------------------------------------------ the whole model


def _run(precision, seq_len=None):
    import paddle_tpu as fluid

    model, traffic = cell(precision=precision, **AS_AT_WIDTH)
    if seq_len:
        traffic = dict(traffic, seq_len=seq_len)
    with fluid.program_guard(fluid.Program(), fluid.Program()), \
            fluid.unique_name.guard(), fluid.scope_guard(fluid.Scope()):
        _, eval_prog, built, exe, names = built_model(model, traffic)
        batch = batch_for(model, traffic)
        got = exe.run(eval_prog, feed=batch, fetch_list=built["check"])
        return model, batch, state(names), got


@pytest.fixture(scope="module")
def amp_run():
    """The cell's program at the rehearsal size in the cell's precision,
    built and run once for the tests below: (model, batch, parameters,
    [loss, scored logits])."""
    return _run("bf16_amp")


@pytest.fixture(scope="module")
def float32_run():
    """The same in float32, on rows of 80 tokens."""
    return _run("float32", seq_len=80)


def _check(got, p, batch, model, **kw):
    from benchmark.models import qwen3_next as adapter
    from benchmark.runners import train_loop

    nll, count, want = highest(adapter.reference, p, batch, model, **kw)
    return train_loop.check_reference(
        got[0], got[1], nll / count, want[:adapter.SCORED_SEQUENCES],
        adapter.TOLERANCE)


# what the float32 program is held to: float32's own rounding through
# four layers reads 1e-6
FLOAT32_LIMITS = {"logits_rel_rms": 5e-5, "loss_abs": 1e-5}


def test_whole_model_logits_and_loss_equal_reference_float32(float32_run):
    from benchmark.models import qwen3_next as adapter

    model, batch, p, got = float32_run
    assert sorted(batch) == ["labels", "tokens"]
    np.testing.assert_array_equal(batch["labels"][:, :-1], batch["tokens"][:, 1:])
    assert np.asarray(got[1]).shape == (
        adapter.SCORED_SEQUENCES, 80 // adapter.SCORED_EVERY,
        model["vocab_size"])
    check = _check(got, p, batch, model)
    assert check["ok"], check
    assert all(check[k] < v for k, v in FLOAT32_LIMITS.items()), check


def test_whole_model_equals_reference_under_bf16_amp(amp_run):
    """The logits within the cell's limit. The loss here is a mean of 96
    bf16 per-token losses where the cell's is one of 4,096, so its
    rounding is sqrt(4096 / 96) = 6.5 times as coarse: held to that many
    times the cell's limit."""
    from benchmark.models.qwen3_next import TOLERANCE

    model, batch, p, got = amp_run
    check = _check(got, p, batch, model)
    assert 1e-4 < check["logits_rel_rms"] <= TOLERANCE["logits_rel_rms"], check
    assert check["loss_abs"] <= 6.5 * TOLERANCE["loss_abs"], check


# QK-norm after the positions moves the logits by what bf16 rounding moves
# them by (with the norms' weights at their seeded 1 it is the same model:
# a rotation keeps a head's length): the float32 program catches it, the
# cell's limit cannot and is not asked to
MILD = (("norm_after_rope",),)


def _wrong_cases():
    from benchmark.models.qwen3_next import WRONG

    return [{"drop_layers": 1}] + [{"wrong": (w,)} for w in WRONG]


@pytest.mark.parametrize("wrong", _wrong_cases(), ids=lambda w: str(
    w.get("wrong", ["drop_layers"])[0]))
def test_a_wrong_model_is_caught(wrong, amp_run, float32_run):
    """The reference with its last layer left out or with one departure
    of `WRONG`: against the float32 program each reads hundreds of times
    its limit, and against the program in the cell's precision each but
    `MILD`'s is refused by the cell's logits' limit (a mean of 96 bf16
    losses is too coarse for the loss's limit to say anything here)."""
    from benchmark.models.qwen3_next import TOLERANCE

    model, batch, p, got = float32_run
    check = _check(got, p, batch, model, **wrong)
    assert check["logits_rel_rms"] > 100 * FLOAT32_LIMITS["logits_rel_rms"], (
        wrong, check)
    if wrong.get("wrong") in MILD:
        return
    model, batch, p, got = amp_run
    check = _check(got, p, batch, model, **wrong)
    assert not check["ok"], (wrong, check)
    assert check["logits_rel_rms"] > TOLERANCE["logits_rel_rms"], check


# ------------------------------------------------ one step's gradients

KINDS = {
    "embedding": ("qwen3next.embed",),
    "head": ("qwen3next.head.w_0",),
    "rms_norm": (".input_norm.w_0", ".post_attn_norm.w_0",
                 "final_norm.w_0"),
    "W_qkvz": (".gdn.in_proj_qkvz.w_0",),
    "W_ba": (".gdn.in_proj_ba.w_0",),
    "conv_filter": (".gdn.conv.w_0",),
    "A_log": (".gdn.A_log",),
    "dt_bias": (".gdn.dt_bias",),
    "gated_norm": (".gdn.norm.w_0",),
    "W_out": (".gdn.out_proj.w_0",),
    "W_q_and_gate": (".attn.q.w_0", ".attn.gate.w_0"),
    "attention": (".attn.k.w_0", ".attn.v.w_0", ".attn.o.w_0"),
    "qk_norm": (".q_norm.w_0", ".k_norm.w_0"),
    "router": (".moe.gate",),
    "shared_gate": (".shared_gate.w_0",),
    "shared_expert": (".shared.gate.w_0", ".shared.up.w_0",
                      ".shared.down.w_0"),
    "experts": (".moe.w_gate", ".moe.w_up", ".moe.w_down"),
}


def _gradients(model, traffic, place=None, seed=3):
    """{name: gradient} of the program's train step (one SGD step at rate
    1: the gradient is what the parameter lost) and of `jax.grad` of the
    reference's loss, from the same seeded state and batch."""
    import jax

    import paddle_tpu as fluid
    from benchmark.models import qwen3_next as adapter
    from benchmark.runners import train_loop

    model = dict(model, optimizer={"type": "SGD", "learning_rate": 1.0})
    main, startup, built, _ = train_loop.build_programs(
        fluid, adapter, model, traffic, seed)
    exe = fluid.Executor(place or fluid.CPUPlace())
    exe.run(startup)
    names = [p.name for p in main.global_block().all_parameters()]
    _move_norms(names, seed)
    before = state(names)
    batch = batch_for(model, traffic)
    exe.run(main, feed=batch, fetch_list=[built["loss"]])
    got = {n: before[n] - v for n, v in state(names).items()}
    scope = fluid.global_scope()
    for n in list(scope.local_names()):  # the device is the reference's now
        scope.delete(n)
    with jax.default_matmul_precision("highest"):
        want = f32(jax.jit(jax.grad(
            lambda p: ref.loss(p, batch, model)))(before))
    return got, want, before


def test_one_train_steps_gradients_equal_jax_grad_of_the_reference():
    """Every parameter's gradient, by kind: `W_qkvz`, `W_ba`, the filter,
    `A_log`, `dt_bias`, the gated norm, `W_q` with its gate half, the two
    QK-norms, the router, the shared gate, the experts held."""
    model, traffic = cell(precision="float32", **AS_AT_WIDTH)
    got, want, before = _gradients(model, dict(traffic, seq_len=80))
    worst = check_gradients(got, want, before, 2e-4, kinds=KINDS)
    assert set(worst) == set(KINDS)


# -------------------------------------------------- the expert layer


@pytest.mark.parametrize("total,held,k", [(32, 2, 3), (512, 32, 10)])
def test_the_16_shares_add_up_to_the_uncut_layer(total, held, k):
    """Sixteen shares' routed parts, and the gated shared expert counted
    once, equal the reference's layer with all the experts held, under
    the softmax router with renormalised weights: the published 512
    experts 32 a share and 10 a token, and a small layer."""
    import paddle_tpu as fluid

    r = np.random.RandomState(total)
    hidden, width, shares = 16, 8, 16
    assert total == shares * held
    p = {"m.moe.gate": r.randn(hidden, total).astype(np.float32) * 0.5}
    for w, shape in (("w_gate", (total, hidden, width)),
                     ("w_up", (total, hidden, width)),
                     ("w_down", (total, width, hidden))):
        p["m.moe." + w] = r.randn(*shape).astype(np.float32) * 0.2
    for w, shape in (("shared.gate", (hidden, width)),
                     ("shared.up", (hidden, width)),
                     ("shared.down", (width, hidden)),
                     ("shared_gate", (hidden, 1))):
        p[f"m.{w}.w_0"] = r.randn(*shape).astype(np.float32) * 0.3
    u = r.randn(2, 24, hidden).astype(np.float32)
    x = fluid.layers.data("u", list(u.shape), append_batch_size=False)
    outs = []
    for lo in range(0, total, held):
        outs += fluid.layers.moe_experts(
            x, experts_total=total, experts_held=held, d_ff=width, k=k,
            held_from=lo, score_func="softmax",
            param_attr=fluid.ParamAttr(name=f"share{lo}"))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    for lo in range(0, total, held):
        scope.set(f"share{lo}.gate", p["m.moe.gate"])
        for w in ("w_gate", "w_up", "w_down"):
            scope.set(f"share{lo}.{w}", p[f"m.moe.{w}"][lo:lo + held])
    got = exe.run(feed={"u": u}, fetch_list=outs)
    routed, loads = got[0::2], got[1::2]
    assert len(routed) == shares
    assert int(np.sum(loads)) == u.shape[0] * u.shape[1] * k
    layer = {"num_experts_per_tok": k, "num_experts": total, "held_from": 0,
             "norm_topk_prob": True}
    uncut = highest(ref.expert_ffn, p, u, "m", layer)
    routed_only = highest(ref.expert_ffn, p, u, "m",
                          dict(layer, shared_expert=False))
    shared = uncut - routed_only  # what every chip computes alike
    assert np.abs(shared).max() > 1e-3
    assert rel(sum(routed), routed_only) < 1e-5
    assert rel(sum(routed) + shared, uncut) < 1e-5
    # sixteen shares that each added their shared expert would count it
    # sixteen times
    assert rel(sum(routed) + shares * shared, uncut) > 0.5
    # and one share alone is the reference's share
    p_share = dict(p, **{f"m.moe.{w}": p[f"m.moe.{w}"][held:2 * held]
                         for w in ("w_gate", "w_up", "w_down")})
    one = highest(ref.expert_ffn, p_share, u, "m",
                  dict(layer, num_experts=held, held_from=held,
                       shared_expert=False))
    assert rel(routed[1], one) < 1e-5


def test_the_shared_experts_gate_in_the_program():
    """`expert_ffn` with `shared_expert_gate`: one projection of width 1,
    a sigmoid and a product more than without, and the output is the
    reference's with the gate."""
    import paddle_tpu as fluid
    from benchmark.models import qwen3_next as adapter
    from paddle_tpu import profiler
    from paddle_tpu.models import decoder_parts

    model, _ = cell(**AS_AT_WIDTH)
    cfg = adapter.config(model)
    types = {}
    for gated in (True, False):
        cfg.shared_expert_gate = gated
        before = profiler.counters().get("moe_shared_expert_gated", 0)
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()), \
                fluid.unique_name.guard():
            u = fluid.layers.data("u", [2, 8, cfg.hidden_size],
                                  append_batch_size=False)
            decoder_parts.expert_ffn(u, cfg, "m")
        types[gated] = [op.type for op in main.global_block().ops]
        names = [p.name for p in main.global_block().all_parameters()]
        assert ("m.shared_gate.w_0" in names) == gated
        assert profiler.counters().get(
            "moe_shared_expert_gated", 0) - before == int(gated)
    extra = list(types[True])
    for t in types[False]:
        extra.remove(t)
    assert sorted(extra) == ["elementwise_mul", "mul", "sigmoid"]


# ------------------------------------------- gauges, counters, the cell


def test_gauges_and_counters_at_the_rehearsal_size(monkeypatch):
    import paddle_tpu as fluid
    from paddle_tpu import profiler

    # no interpreter, whatever a test file imported before this one set:
    # the convolution's 128 channels would take the kernel under it
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    model, traffic = cell()
    before = profiler.counters()
    with fluid.program_guard(fluid.Program(), fluid.Program()), \
            fluid.unique_name.guard(), fluid.scope_guard(fluid.Scope()):
        main, eval_prog, built, exe, names = built_model(model, traffic)
        batch = batch_for(model, traffic)
        loads = exe.run(main, feed=batch, fetch_list=built["loads"])
    after = profiler.counters()
    assert {n: after[n] for n in (
        "gated_delta_layers", "attention_layers", "expert_layers",
        "moe_experts_held", "moe_experts_total", "attn_kv_group",
        "attn_rotary_lanes", "kda_key_group")} == {
        "gated_delta_layers": 3, "attention_layers": 1, "expert_layers": 4,
        "moe_experts_held": 2, "moe_experts_total": 8, "attn_kv_group": 2,
        "attn_rotary_lanes": 4, "kda_key_group": 2}

    def bumped(name):
        return after.get(name, 0) - before.get(name, 0)

    # three Gated DeltaNet layers, the forward op's lowering and the
    # gradient op's replay; heads of 16 on a CPU: the plain path
    assert bumped("kda_dispatch_chunked") == 6
    assert bumped("kda_decay_per_head") == 6
    assert bumped("kda_dispatch_pallas") == 0
    assert bumped("short_conv_dispatch_xla") == 3
    assert bumped("moe_dispatch_grouped") == 8
    assert bumped("moe_route_softmax") == 8
    assert bumped("moe_shared_expert_gated") == 4
    assert bumped("attn_dispatch_xla") == 2
    ops = main.global_block().ops
    types = [op.type for op in ops]
    assert types.count("short_conv1d") == 3
    assert types.count("kda_attention") == 3
    assert types.count("fused_multihead_attention") == 1
    assert types.count("moe_experts") == 4
    deltas = [op for op in ops if op.type == "kda_attention"]
    assert all(op.attr("num_heads") == 4 and op.attr("num_key_heads") == 2
               for op in deltas)
    experts = [op for op in ops if op.type == "moe_experts"]
    assert all(op.attr("score_func") == "softmax" and op.attr("renormalize")
               and not op.attr("norm_eps") for op in experts)
    (attn,) = [op for op in ops if op.type == "fused_multihead_attention"]
    assert attn.attr("rope_theta") == 1e7 and attn.attr("rotary_dim") == 4
    assert not attn.attr("window")
    assert len(loads) == 4 and all(x.shape == (2,) for x in loads)


def test_parameters_and_flops_of_the_cell():
    from benchmark.models import qwen3_next as adapter
    from benchmark.runners import train_loop

    model, traffic = cell(rehearse=False)
    assert (traffic["batch"], traffic["seq_len"]) == (1, 4096)
    assert model["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    # ISSUE 51's arithmetic, redone
    delta = 2048 * 12288 + 2048 * 64 + 4096 * 2048
    attn = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    expert, router, shared = 3 * 2048 * 512, 2048 * 512, 3 * 2048 * 512 + 2048
    assert [adapter.mixer_matrix_params(model, k) for k in (
        "linear_attention", "full_attention")] == [delta, attn]
    per_token = adapter.matrix_params_per_token(model)
    # a balanced router sends a token's 10 assignments to the 32 of 512
    # held five eighths of an expert's worth
    assert per_token == (3 * delta + attn
                         + 4 * (router + shared + expert * 10 * 32 / 512)
                         + 2048 * 18992)
    assert abs(per_token / 1e6 - 191.9) < 0.05
    held = (3 * (delta + 8192 * 4 + 32 + 32 + 128) + attn + 2 * 256
            + 4 * (32 * expert + router + 512 + shared)
            + 9 * 2048 + 2 * 2048 * 18992)
    assert abs(held / 1e6 - 625.7) < 0.05  # the parameters held
    pairs = 4096 * 4097 // 2
    flops = adapter.flops_per_example(model, traffic)
    assert flops == 3.0 * (2 * 4096 * per_token + pairs * 16 * 4 * 256
                           + 3 * 4096 * 32 * 6 * 128 * 128)
    assert 5.2e12 < flops < 5.3e12
    # attention's score maps are 0.41 of them, the recurrence 0.12
    assert abs(3 * pairs * 16 * 4 * 256 / 1e12 - 0.41) < 0.01
    assert abs(3 * 3 * 4096 * 32 * 6 * 128 * 128 / 1e12 - 0.116) < 0.001

    # the count the program reports
    import paddle_tpu as fluid

    for rehearse, want in ((True, None), (False, held)):
        m, t = cell(rehearse=rehearse)
        with fluid.program_guard(fluid.Program(), fluid.Program()), \
                fluid.unique_name.guard():
            main, _, built, _ = train_loop.build_programs(
                fluid, adapter, m, t, 3)
            params = main.global_block().all_parameters()
        names = [p.name for p in params]
        assert len(names) == len(set(names)) == (
            2 + 3 * 7 + 7 + 4 * (2 + 5 + 4) + 1)
        if want:
            assert sum(int(np.prod(p.shape)) for p in params) == want
        assert built["feeds"] == ["tokens", "labels"]
        assert len(built["loads"]) == 4


# ------------------------------------------------------- on the chip


def _on_chip(model, traffic, seed):
    """The cell's programs on the attached TPU with the seeded state."""
    import paddle_tpu as fluid
    from benchmark.models import qwen3_next as adapter
    from benchmark.runners import train_loop

    main, startup, built, eval_prog = train_loop.build_programs(
        fluid, adapter, model, traffic, seed)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)
    return main, eval_prog, built, exe


def chip_readings(seeds, only=(), few=2):
    """At the published widths on the attached TPU: the cell's own check
    (program in bf16 AMP against the float32 reference) at every seed,
    and the same program against the wrong models named in `only` at
    every seed, or with none named against each wrong model and the fp8
    reference at the first `few`."""
    import paddle_tpu as fluid
    from benchmark.models import qwen3_next as adapter
    from benchmark.runners import train_loop

    model, traffic = cell(rehearse=False)
    for at, seed in enumerate(seeds):
        with fluid.program_guard(fluid.Program(), fluid.Program()), \
                fluid.unique_name.guard(), fluid.scope_guard(fluid.Scope()):
            main, eval_prog, built, exe = _on_chip(model, traffic, seed)
            batch = adapter.make_batch(np.random.RandomState(seed), model,
                                       traffic)
            got_loss, got_logits = exe.run(eval_prog, feed=batch,
                                           fetch_list=built["check"])
            p = state([v.name for v in main.global_block().all_parameters()])
        variants = [("reference", p, {})] + [
            (w, p, {"wrong": (w,)}) for w in only]
        if not only and at < few:
            variants += [("fp8", _fp8(p), {}),
                         ("drop_layers", p, {"drop_layers": 1})] + [
                (w, p, {"wrong": (w,)}) for w in adapter.WRONG]
        for label, params, kw in variants:
            loss, logits = train_loop.reference_outputs(
                adapter, params, batch, model, 1, **kw)
            check = train_loop.check_reference(
                got_loss, got_logits, loss, logits, adapter.TOLERANCE)
            print(f"seed {seed} {label}: logits_rel_rms "
                  f"{check['logits_rel_rms']:.5f} loss_abs "
                  f"{check['loss_abs']:.5f} ok {check['ok']}", flush=True)


def held_loads(seeds, steps=44, rate=None):
    """At the published widths on the attached TPU, the cell's train step
    on the batches its runner would feed (one check batch drawn first,
    then the pool of 32), `steps` of them at `rate`: the share of the
    40,960 assignments that each expert layer's 32 held experts take, at
    the first step, the window's first (the fifth) and the last, and the
    largest over all steps, beside the first block's share; the loss, and
    its fall as the runner takes it."""
    import paddle_tpu as fluid
    from benchmark.models import qwen3_next as adapter
    from paddle_tpu import profiler

    model, traffic = cell(rehearse=False)
    if rate:  # the sweep that chose the optimizer's rate
        model["optimizer"] = dict(model["optimizer"], learning_rate=rate)
    total = traffic["batch"] * traffic["seq_len"] * model["num_experts_per_tok"]
    c0 = profiler.counters()
    for seed in seeds:
        with fluid.program_guard(fluid.Program(), fluid.Program()), \
                fluid.unique_name.guard(), fluid.scope_guard(fluid.Scope()):
            main, _, built, exe = _on_chip(model, traffic, seed)
            rng = np.random.RandomState(seed)
            adapter.make_batch(rng, model, traffic)  # the check's batch
            pool = [adapter.make_batch(rng, model, traffic)
                    for _ in range(traffic["pool_batches"])]
            shares, losses = [], []
            for i in range(steps):
                loss, *loads = exe.run(
                    main, feed=pool[i % len(pool)],
                    fetch_list=[built["loss"]] + built["loads"])
                losses.append(float(np.asarray(loss).reshape(-1)[0]))
                shares.append([float(np.sum(x)) / total for x in loads])
        shares = np.array(shares)
        rows = profiler.counters()["moe_block_rows"]

        def row(values):
            return " ".join(f"{v:.4f}" for v in values)

        print(f"seed {seed} rate {model['optimizer']['learning_rate']}: "
              f"block {rows} rows = {rows / total:.4f} of "
              f"{total}; held share by layer, step 0: {row(shares[0])}; "
              f"step 4: {row(shares[4])}; step {steps - 1}: "
              f"{row(shares[-1])}; largest: {row(shares.max(0))}; loss "
              f"step 0 {losses[0]:.4f}, step 4 {losses[4]:.4f}, step "
              f"{steps - 1} {losses[-1]:.4f}; fall (median of steps 4-13 "
              f"less median of the last ten) "
              f"{np.median(losses[4:14]) - np.median(losses[-10:]):.4f}; "
              "every tenth: " + " ".join(f"{v:.3f}" for v in losses[::10]),
              flush=True)
    c1 = profiler.counters()
    print("counters of", len(seeds), "train steps' traces:", {
        n: c1.get(n, 0) - c0.get(n, 0) for n in (
            "kda_dispatch_pallas", "kda_dispatch_chunked",
            "kda_decay_per_head", "short_conv_dispatch_pallas",
            "short_conv_dispatch_xla", "attn_dispatch_flash",
            "attn_qk_prep_fused", "flash_bwd_fused_calls",
            "moe_dispatch_grouped", "moe_dispatch_gmm", "moe_route_softmax",
            "moe_shared_expert_gated")},
        {n: c1.get(n) for n in (
            "gated_delta_layers", "attention_layers", "expert_layers",
            "kda_key_group", "attn_kv_group", "attn_rotary_lanes",
            "moe_block_rows", "moe_experts_held", "moe_experts_total",
            "flash_blocks_visited", "flash_blocks_total")}, flush=True)


def chip_gradients():
    """The gradients of every kind of parameter at the published widths,
    program against `jax.grad` of the reference, on one 512-token row."""
    import jax

    import paddle_tpu as fluid

    # How the reference is differentiated, not what it computes: the token
    # recurrence keeps a [32, 128, 128] state a token for its backward;
    # rebuilt a layer at a time it fits.
    ref.delta_recurrence = jax.checkpoint(ref.delta_recurrence)
    model, traffic = cell(rehearse=False, precision="float32")
    traffic = dict(traffic, seq_len=512)
    # float32 on a TPU is a bf16 pass a product unless told otherwise, so
    # the "float32" program is held to 5%, the AMP one to 20%
    for precision, limit, routed in (("float32", 0.05, 0.3),
                                     ("bf16_amp", 0.2, 0.6)):
        with fluid.program_guard(fluid.Program(), fluid.Program()), \
                fluid.unique_name.guard(), fluid.scope_guard(fluid.Scope()):
            got, want, before = _gradients(
                dict(model, precision=precision), traffic,
                place=fluid.TPUPlace())
        try:
            worst = check_gradients(got, want, before, limit, routed, KINDS)
        except AssertionError as e:
            print(f"FAIL {precision}: {e}", flush=True)
            raise
        print(f"gradients at the published widths, s=512, {precision}: "
              "worst relative error by kind "
              + ", ".join(f"{k} {v:.2e}" for k, v in worst.items()), flush=True)


if __name__ == "__main__":
    import jax

    assert jax.devices()[0].platform == "tpu", jax.devices()
    what, _, rate = sys.argv[1].partition("@")
    what, _, only = what.partition(":")
    seeds = [int(a) for a in sys.argv[2:]] or [51001]
    {"readings": lambda: chip_readings(
        seeds, tuple(w for w in only.split(",") if w)),
     "loads": lambda: held_loads(seeds, rate=float(rate) if rate else None),
     "gradients": chip_gradients}[what]()
