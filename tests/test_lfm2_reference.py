"""LFM2 against its plain reference (`tests/lfm2_reference.py`) at the
rehearsal size of the cell `lfm2_24b_ep8_longdoc`: every mixer alone, the
attention layer through the flash kernel over grouped heads, the whole
model in float32 and under bf16 AMP, one train step's gradients for every
kind of parameter, that each lowering to bf16 and each wrong model is
caught, the eight shares of an expert layer against the uncut layer, the
gauges and counters, the cell's arithmetic, and that the five other
decoders' Programs are op for op what they were.

Run as a script on the attached TPU, outside any timed window:

    python3 tests/test_lfm2_reference.py readings 1 2   # program, wrong models and fp8 reference against the reference
    python3 tests/test_lfm2_reference.py loads@3e-6 1 2   # held share by expert layer and the loss over the window's steps at a rate
    python3 tests/test_lfm2_reference.py gradients      # at the published widths on one 1,024-token row
"""

from __future__ import annotations

import inspect
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import lfm2_reference as ref  # noqa: E402 — beside this file
from test_kimi_linear_reference import (  # noqa: E402 — the shared helpers
    check_gradients, f32, highest, rel, state)
from test_mellum_reference import _fp8  # noqa: E402 — the matrices in e4m3

CELL = "lfm2_24b_ep8_longdoc"


def cell(rehearse=True, **config):
    from benchmark.harness import spec

    c = spec.cell(CELL, rehearse=rehearse)
    c["config"].update(config)
    return c["config"], c["traffic"]


def built_model(model, traffic, seed=3):
    """Programs, executor and the seeded state by name, in a scope of its
    own (the caller holds the guards). The QK-norm weights are moved off
    their seeded 1: at 1 a norm before the positions is a norm after
    them, since a rotation keeps a head's length."""
    import paddle_tpu as fluid
    from benchmark.models import lfm2 as adapter
    from benchmark.runners import train_loop

    main, startup, built, eval_prog = train_loop.build_programs(
        fluid, adapter, model, traffic, seed)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    names = [p.name for p in main.global_block().all_parameters()]
    scope, r = fluid.global_scope(), np.random.RandomState(seed)
    for n in names:
        if n.endswith(("q_norm.w_0", "k_norm.w_0")):
            scope.set(n, r.uniform(0.5, 1.5, np.shape(scope.get(n))).astype(
                np.float32))
    return main, eval_prog, built, exe, names


def batch_for(model, traffic, seed=0):
    from benchmark.models import lfm2 as adapter

    return adapter.make_batch(np.random.RandomState(seed), model, traffic)


# At 64 wide, seeded as the cell is (matrices Normal(0, 0.02)), a product
# gives 0.16 of its input and a mixer next to nothing of the residual
# stream, so neither a wrong model nor a lowering to bf16 shows in the
# logits. With the matrices at 0.1 the layers weigh in the stream as at the
# published width (0.02 x sqrt(2048) = 0.9 a product; 0.1 x sqrt(64) = 0.8).
# The router's correction at the other configurations' 0.1, so that a
# wrong use of it shows (the cell seeds it zeros, as the published code
# does, which keeps a chip's load steady: nothing a test here needs).
AS_AT_WIDTH = {"initializer_range": 0.1, "router_bias_scale": 0.1}


# ------------------------------------------------- the copy is a copy


def test_reference_copy_is_the_adapters_word_for_word():
    from benchmark.models import lfm2 as adapter

    for name in ("held_layers", "_rms", "_silu", "_ffn", "_rope", "_conv",
                 "conv_mixer", "attention_mixer", "expert_ffn", "reference"):
        assert inspect.getsource(getattr(ref, name)) == inspect.getsource(
            getattr(adapter, name)), name
    assert (ref.SCORED_EVERY, ref.QUERY_BLOCK) == (
        adapter.SCORED_EVERY, adapter.QUERY_BLOCK)


def test_layer_kinds_follow_the_published_list():
    from benchmark.models import lfm2 as adapter
    from paddle_tpu.models.lfm2 import PUBLISHED_LAYER_TYPES, Lfm2Config

    model, _ = cell(rehearse=False)
    assert adapter.held_layers(model) == [
        (1, "conv", True), (2, "full_attention", False), (3, "conv", False),
        (4, "conv", False), (5, "conv", False)]
    assert model["layer_types"] == PUBLISHED_LAYER_TYPES
    assert [i for i, k in enumerate(PUBLISHED_LAYER_TYPES)
            if k == "full_attention"] == list(range(2, 40, 4))
    whole = dict(model, first_layer_held=0, num_hidden_layers=40)
    kinds = [(k, dense) for _, k, dense in adapter.held_layers(whole)]
    assert kinds.count(("conv", True)) == 2  # the two leading dense layers
    assert kinds.count(("conv", False)) == 28
    assert kinds.count(("full_attention", False)) == 10
    cfg = adapter.config(model)
    assert (cfg.layer_types, cfg.first_layer, cfg.dense_layers) == (
        ["conv", "full_attention", "conv", "conv", "conv"], 1, 1)
    assert (cfg.head_dim, cfg.conv_L_cache, cfg.rope_theta,
            cfg.router_norm_eps) == (64, 3, 1e6, model["router_norm_eps"])
    assert Lfm2Config().head_dim == 64 and len(Lfm2Config().layer_types) == 40
    import paddle_tpu as fluid
    from paddle_tpu.models import build_lfm2

    with pytest.raises(ValueError, match="sliding"):
        build_lfm2(Lfm2Config(vocab_size=16, hidden_size=8,
                              layer_types=["sliding"], num_attention_heads=2,
                              num_key_value_heads=1), 1, 4)
    assert fluid.default_main_program().global_block().has_var("lfm2.embed")


# ------------------------------------------ the program, mixer by mixer


def _mixer_program(which, model, batch=2, seq=80):
    """A mixer or a feed-forward alone in a Program: `u` in, `y` out."""
    import paddle_tpu as fluid
    from benchmark.models import lfm2 as adapter
    from paddle_tpu.models import decoder_parts

    cfg = adapter.config(model)
    u = fluid.layers.data("u", [batch, seq, cfg.hidden_size],
                          append_batch_size=False)
    if which == "conv":
        y = decoder_parts.gated_short_conv(u, cfg, "m")
    elif which == "attention":
        y = decoder_parts.attention(u, cfg, "m", rope_theta=cfg.rope_theta)
    elif which == "dense":
        y = decoder_parts.ffn(u, cfg.intermediate_size, "m.mlp", cfg)
    else:
        y, _ = decoder_parts.expert_ffn(u, cfg, "m", cfg.router_norm_eps)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    names = [p.name for p in
             fluid.default_main_program().global_block().all_parameters()]
    scope, r = fluid.global_scope(), np.random.RandomState(5)
    for n in names:
        if n.endswith("_norm.w_0"):
            scope.set(n, r.uniform(0.5, 1.5, np.shape(scope.get(n))).astype(
                np.float32))
    return exe, y, names


def _want_mixer(which, p, u, model, wrong=()):
    return {"conv": lambda: ref.conv_mixer(p, u, "m", model, wrong),
            "attention": lambda: ref.attention_mixer(p, u, "m", model, wrong),
            "dense": lambda: ref._ffn(p, u, "m.mlp"),
            "experts": lambda: ref.expert_ffn(p, u, "m", model, wrong)}[which]


@pytest.mark.parametrize("which", ["conv", "attention", "dense", "experts"])
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
    for wrong in {"conv": ("conv_silu", "gates_swapped"),
                  "attention": ("norm_after_rope", "no_qk_norm", "group_mod"),
                  "experts": ("bias_in_weights",)}.get(which, ()):
        other = highest(_want_mixer(which, p, u, model, (wrong,)))
        assert rel(got, other) > 0.02, wrong


def test_the_convolution_starts_from_zero_and_sees_three_tokens():
    """A change to token t moves the mixer's output at t, t + 1 and t + 2
    and nowhere else; the first token's output is the last tap's alone."""
    model, _ = cell(**AS_AT_WIDTH)
    exe, y, names = _mixer_program("conv", model, batch=1, seq=16)
    h = model["hidden_size"]
    u = np.random.RandomState(2).randn(1, 16, h).astype(np.float32)
    (base,) = exe.run(feed={"u": u}, fetch_list=[y])
    moved = u.copy()
    moved[0, 5] += 1.0
    (got,) = exe.run(feed={"u": moved}, fetch_list=[y])
    hit = np.flatnonzero(np.abs(got - base)[0].max(axis=1) > 1e-7)
    assert hit.tolist() == [5, 6, 7]
    p = state(names)
    both = u[0, 0] @ p["m.in_proj.w_0"]
    first = (both[h:2 * h] * (p["m.conv.w_0"][:, 2] * both[:h] * both[2 * h:])
             ) @ p["m.out_proj.w_0"]
    np.testing.assert_allclose(base[0, 0], first, atol=1e-5)


def test_attention_through_the_flash_kernel(monkeypatch, attn_path):
    """The blocked kernel, interpreted, over two key/value heads of 16
    lanes with QK-norm and positions from the two ops' own functions
    (`qk_prep` takes whole 128-lane heads alone): forced by name, since
    the CPU's dispatch never chooses it."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    attn_path("flash")
    from paddle_tpu import profiler

    before = profiler.counters()
    model, _ = cell(**AS_AT_WIDTH)
    exe, y, names = _mixer_program("attention", model, batch=1, seq=160)
    u = np.random.RandomState(2).randn(1, 160, model["hidden_size"]).astype(
        np.float32)
    (got,) = exe.run(feed={"u": u}, fetch_list=[y])
    after = profiler.counters()
    assert after["attn_dispatch_flash"] == before.get(
        "attn_dispatch_flash", 0) + 1
    assert after.get("attn_qk_prep_fused", 0) == before.get(
        "attn_qk_prep_fused", 0)
    assert after["attn_kv_group"] == 2
    want = highest(_want_mixer("attention", state(names), u, model))
    assert rel(got, want) < 2e-5


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
    from benchmark.models import lfm2 as adapter
    from benchmark.runners import train_loop

    nll, count, want = highest(adapter.reference, p, batch, model, **kw)
    return train_loop.check_reference(
        got[0], got[1], nll / count, want[:adapter.SCORED_SEQUENCES],
        adapter.TOLERANCE)


# what the float32 program is held to, and so what a lowering to bf16 has
# to exceed: float32's own rounding through five layers reads 1e-6
FLOAT32_LIMITS = {"logits_rel_rms": 5e-5, "loss_abs": 1e-5}


def test_whole_model_logits_and_loss_equal_reference_float32(float32_run):
    from benchmark.models import lfm2 as adapter

    model, batch, p, got = float32_run
    assert sorted(batch) == ["labels", "tokens"]
    np.testing.assert_array_equal(batch["labels"][:, :-1], batch["tokens"][:, 1:])
    assert np.asarray(got[1]).shape == (
        adapter.SCORED_SEQUENCES, 80 // adapter.SCORED_EVERY,
        model["vocab_size"])
    check = _check(got, p, batch, model)
    assert check["ok"], check
    assert all(check[k] < v for k, v in FLOAT32_LIMITS.items()), check


@pytest.mark.parametrize("lowered", ["taps_bf16", "router_bf16",
                                     "softmax_bf16", "norm_bf16"])
def test_a_lowering_to_bf16_fails_the_float32_limits(lowered, float32_run):
    """The convolution's taps, the router, the softmax or the norms'
    statistics in bf16: each moves the logits by several times the float32
    program's limit, which is the tolerance that catches it here (on the
    chip, where the program's matrices are bf16, the adapter's `TOLERANCE`
    comment says which of them its limits still catch)."""
    model, batch, p, got = float32_run
    check = _check(got, p, batch, model, wrong=(lowered,))
    assert check["logits_rel_rms"] > 4 * FLOAT32_LIMITS["logits_rel_rms"], check


def test_whole_model_equals_reference_under_bf16_amp(amp_run):
    """The logits within the cell's limit. The loss here is a mean of 96
    bf16 per-token losses where the cell's is one of 8,192, so its
    rounding is sqrt(8192 / 96) = 9.2 times as coarse: held to that many
    times the cell's limit."""
    from benchmark.models.lfm2 import TOLERANCE

    model, batch, p, got = amp_run
    check = _check(got, p, batch, model)
    assert 1e-4 < check["logits_rel_rms"] <= TOLERANCE["logits_rel_rms"], check
    assert check["loss_abs"] <= 9.2 * TOLERANCE["loss_abs"], check


@pytest.mark.parametrize("wrong", [{"drop_layers": 1}] + [
    {"wrong": (w,)} for w in (
        "conv_silu", "gates_swapped", "no_qk_norm", "group_mod")])
def test_a_wrong_model_is_caught_by_the_cells_tolerance(wrong, amp_run):
    """The reference with its last layer left out or with one departure of
    `WRONG` (the SiLU left on the convolution, its gates swapped, no
    QK-norm, the group mapped `n % g`), against the program in the cell's
    precision: refused by the logits' limit, which carries the check (a
    mean of 96 bf16 losses is too coarse for the loss's limit to say
    anything here)."""
    from benchmark.models.lfm2 import TOLERANCE

    model, batch, p, got = amp_run
    check = _check(got, p, batch, model, **wrong)
    assert not check["ok"], (wrong, check)
    assert check["logits_rel_rms"] > 2 * TOLERANCE["logits_rel_rms"], check


@pytest.mark.parametrize("wrong", ["norm_after_rope", "bias_in_weights"])
def test_a_mild_wrong_model_is_caught_where_the_program_is_float32(
        wrong, float32_run, amp_run):
    """QK-norm after the positions (with the norms' weights off their
    seeded 1: at 1 the two orders are one model, a rotation keeps a
    head's length) and the router's correction inside the weights move
    the logits by 1 to 5% here and by 0.2 to 0.5 points on the chip
    (PERF.md section 6, PR 47), which is what bf16 rounding moves them
    by: the cell's tolerance has to sit above the program's own 2.3 to
    2.6% and cannot tell them from it. The float32 program can: each
    reads over a hundred times its limit."""
    model, batch, p, got = float32_run
    check = _check(got, p, batch, model, wrong=(wrong,))
    assert check["logits_rel_rms"] > 100 * FLOAT32_LIMITS["logits_rel_rms"]
    # and against the bf16 program it reads above the right reference
    model, batch, p, got = amp_run
    assert (_check(got, p, batch, model, wrong=(wrong,))["logits_rel_rms"]
            > 1.2 * _check(got, p, batch, model)["logits_rel_rms"])


# ------------------------------------------------ one step's gradients

KINDS = {
    "embedding_and_head": ("lfm2.embed",),
    "rms_norm": (".operator_norm.w_0", ".ffn_norm.w_0",
                 "embedding_norm.w_0"),
    "conv_projections": (".conv.in_proj.w_0", ".conv.out_proj.w_0"),
    "conv_filter": (".conv.conv.w_0",),
    "qk_norm": (".q_norm.w_0", ".k_norm.w_0"),
    "attention": (".attn.q.w_0", ".attn.k.w_0", ".attn.v.w_0",
                  ".attn.o.w_0"),
    "dense_ffn": (".mlp.gate.w_0", ".mlp.up.w_0", ".mlp.down.w_0"),
    "router": (".moe.gate",),
    "experts": (".moe.w_gate", ".moe.w_up", ".moe.w_down"),
}


def _gradients(model, traffic, place=None, seed=3):
    """{name: gradient} of the program's train step (one SGD step at rate
    1: the gradient is what the parameter lost) and of `jax.grad` of the
    reference's loss, from the same seeded state and batch."""
    import jax

    import paddle_tpu as fluid
    from benchmark.models import lfm2 as adapter
    from benchmark.runners import train_loop

    model = dict(model, optimizer={"type": "SGD", "learning_rate": 1.0})
    main, startup, built, _ = train_loop.build_programs(
        fluid, adapter, model, traffic, seed)
    exe = fluid.Executor(place or fluid.CPUPlace())
    exe.run(startup)
    names = [p.name for p in main.global_block().all_parameters()]
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
    """Every parameter's gradient; the tied table's is the sum of the
    gather's and the head's."""
    # at 64 wide a correction of 0.1 would pick the same two experts for
    # every token, none of them held in some layer (Trinity's test)
    model, traffic = cell(precision="float32",
                          **dict(AS_AT_WIDTH, router_bias_scale=0.02))
    got, want, before = _gradients(model, dict(traffic, seq_len=80))
    worst = check_gradients(got, want, before, 2e-4, kinds=KINDS)
    assert set(worst) == set(KINDS)


# -------------------------------------------------- the expert layer


@pytest.mark.parametrize("total,held,k", [(16, 2, 2), (64, 8, 4)])
def test_the_8_shares_add_up_to_the_uncut_layer(total, held, k):
    """Eight shares' parts equal the reference's layer with all the
    experts held, under the sigmoid router with its correction, the
    weights renormalised over `sum + 1e-6`: the published 64 experts 8 a
    share and 4 a token, and a small layer. There is no shared expert to
    count once."""
    import paddle_tpu as fluid

    r = np.random.RandomState(total)
    hidden, width, shares = 16, 8, 8
    assert total == shares * held
    p = {"m.moe.gate": r.randn(hidden, total).astype(np.float32) * 0.3,
         "m.moe.bias": r.randn(total).astype(np.float32) * 0.1}
    for w, shape in (("w_gate", (total, hidden, width)),
                     ("w_up", (total, hidden, width)),
                     ("w_down", (total, width, hidden))):
        p["m.moe." + w] = r.randn(*shape).astype(np.float32) * 0.2
    u = r.randn(2, 24, hidden).astype(np.float32)
    x = fluid.layers.data("u", list(u.shape), append_batch_size=False)
    outs = []
    for lo in range(0, total, held):
        outs += fluid.layers.moe_experts(
            x, experts_total=total, experts_held=held, d_ff=width, k=k,
            held_from=lo, norm_eps=1e-6,
            param_attr=fluid.ParamAttr(name=f"share{lo}"))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    for lo in range(0, total, held):
        scope.set(f"share{lo}.gate", p["m.moe.gate"])
        scope.set(f"share{lo}.bias", p["m.moe.bias"])
        for w in ("w_gate", "w_up", "w_down"):
            scope.set(f"share{lo}.{w}", p[f"m.moe.{w}"][lo:lo + held])
    got = exe.run(feed={"u": u}, fetch_list=outs)
    routed, loads = got[0::2], got[1::2]
    assert len(routed) == shares
    assert int(np.sum(loads)) == u.shape[0] * u.shape[1] * k
    layer = {"num_experts_per_tok": k, "num_experts": total, "held_from": 0,
             "norm_topk_prob": True, "routed_scaling_factor": 1.0,
             "router_norm_eps": 1e-6}
    uncut = highest(ref.expert_ffn, p, u, "m", layer)
    assert rel(sum(routed), uncut) < 1e-5
    # and one share alone is the reference's share
    p_share = dict(p, **{f"m.moe.{w}": p[f"m.moe.{w}"][held:2 * held]
                         for w in ("w_gate", "w_up", "w_down")})
    one = highest(ref.expert_ffn, p_share, u, "m",
                  dict(layer, num_experts=held, held_from=held))
    assert rel(routed[1], one) < 1e-5


# --------------------------------- what the other decoders' Programs hold

# the train Programs at the rehearsal size as the parent of PR 47 builds
# them: op types in order, with every attribute of the `short_conv1d` and
# `moe_experts` ops and their gradient ops, hashed
PROGRAMS = {
    "kimi_linear_ep32_s4096": (658, "9eb255366073b453", 12, 4),
    "trinity_mini_ep16_s8192": (571, "7ca4a60943b9db18", 0, 4),
    "mellum2_ep4_s8192": (291, "02b67599f71fcf96", 0, 4),
    "joyai_flash_ep32_s4096": (827, "a2bb1e36a2ea91e9", 0, 5),
    "phi4_mini_flash_vp8_longdoc": (733, "270f149b0a3b48ba", 2, 0),
}


@pytest.mark.parametrize("cell_name", sorted(PROGRAMS))
def test_the_other_decoders_programs_are_op_for_op_what_they_were(cell_name):
    """Kimi's and Phi-4's convolutions carry no `activation` attribute and
    the four expert decoders' expert ops no `norm_eps`: their Programs are
    the parent's, and their lowering bumps no counter of this PR."""
    import hashlib
    import json

    import paddle_tpu as fluid
    from benchmark.harness import spec
    from benchmark.runners import train_loop
    from paddle_tpu import profiler

    c = spec.cell(cell_name, rehearse=True)
    adapter = spec.plugin("models", c["config"]["adapter"])
    main, startup, built, _ = train_loop.build_programs(
        fluid, adapter, c["config"], c["traffic"], 3)
    ops = main.global_block().ops
    lines = [op.type + (" " + json.dumps(
        {k: v for k, v in sorted(op.attrs.items()) if not k.startswith("op_")},
        sort_keys=True, default=str)
        if op.type.startswith(("short_conv1d", "moe_experts")) else "")
        for op in ops]
    digest = hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16]
    convs = [op for op in ops if op.type == "short_conv1d"]
    experts = [op for op in ops if op.type == "moe_experts"]
    assert (len(ops), digest, len(convs), len(experts)) == PROGRAMS[cell_name]
    assert not any("activation" in op.attrs for op in convs)
    assert not any("norm_eps" in op.attrs for op in experts)
    before = profiler.counters()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    batch = adapter.make_batch(np.random.RandomState(0), c["config"],
                               c["traffic"])
    exe.run(main, feed=batch, fetch_list=[built["loss"]])
    after = profiler.counters()
    assert after.get("short_conv_linear_calls", 0) == before.get(
        "short_conv_linear_calls", 0)


# ------------------------------------------- gauges, counters, the cell


def test_gauges_and_counters_at_the_rehearsal_size():
    from paddle_tpu import profiler

    model, traffic = cell()
    before = profiler.counters()
    main, eval_prog, built, exe, names = built_model(model, traffic)
    batch = batch_for(model, traffic)
    loads = exe.run(main, feed=batch, fetch_list=built["loads"])
    after = profiler.counters()
    assert {n: after[n] for n in (
        "gated_conv_layers", "attention_layers", "expert_layers",
        "moe_experts_held", "moe_experts_total", "attn_kv_group")} == {
        "gated_conv_layers": 4, "attention_layers": 1, "expert_layers": 4,
        "moe_experts_held": 2, "moe_experts_total": 8, "attn_kv_group": 2}

    def bumped(name):
        return after.get(name, 0) - before.get(name, 0)

    # the convolutions' gradient ops run the written-out backward once
    # each (rows of 48 tokens on a CPU: the XLA form; on the chip the
    # kernel, 4 and 0, `loads` prints them)
    assert bumped("short_conv_linear_calls") == 4
    assert bumped("short_conv_dispatch_xla") == 4
    assert bumped("short_conv_dispatch_pallas") == 0
    # four expert layers, the forward op's lowering and the gradient op's
    # replay; one attention layer likewise
    assert bumped("moe_dispatch_grouped") == 8
    assert bumped("moe_dispatch_gmm") == 0
    assert bumped("attn_dispatch_xla") == 2
    assert bumped("attn_qk_prep_fused") == 0
    # 2 x 48 tokens x 2 a token = 192 assignments, 2 of 8 held: 7/16 of them
    assert after["moe_block_rows"] == 84
    ops = main.global_block().ops
    types = [op.type for op in ops]
    assert types.count("short_conv1d") == 4
    assert types.count("fused_multihead_attention") == 1
    assert types.count("moe_experts") == 4
    assert types.count("rms_norm") == 2 * 5 + 1  # no QK-norm among them
    assert "rotary_embedding" not in types
    convs = [op for op in ops if op.type == "short_conv1d"]
    assert len(convs) == 4 and all(
        op.attr("activation") == "none" and "Bias" not in op.inputs
        for op in convs)
    experts = [op for op in ops if op.type == "moe_experts"]
    assert all(op.attr("norm_eps") == 1e-6 and op.attr("score_func")
               == "sigmoid" and op.attr("renormalize") for op in experts)
    (attn,) = [op for op in ops if op.type == "fused_multihead_attention"]
    assert attn.attr("rope_theta") == 1e6 and not attn.attr("window")
    assert set(attn.inputs) >= {"Q", "K", "V", "QNorm", "KNorm"}
    # the gates are the Program's ordinary ops: two a convolution layer,
    # one a SiLU-gated feed-forward (the dense layer's; the experts' are
    # inside their op)
    forward = [op.type for op in ops if not (op.attr("op_role", 0) or 0)]
    assert forward.count("elementwise_mul") == 2 * 4 + 1
    assert len(loads) == 4 and all(x.shape == (2,) for x in loads)


def test_parameters_and_flops_of_the_cell():
    from benchmark.models import lfm2 as adapter
    from benchmark.runners import train_loop

    model, traffic = cell(rehearse=False)
    assert (traffic["batch"], traffic["seq_len"]) == (1, 8192)
    assert model["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    # ISSUE 47's arithmetic, redone
    conv, attn = 4 * 2048 * 2048, 2 * 2048 * 2048 + 2 * 2048 * 512
    dense, expert, router = 3 * 2048 * 11776, 3 * 2048 * 1536, 2048 * 64
    assert [adapter.mixer_matrix_params(model, k) for k in (
        "conv", "full_attention")] == [conv, attn]
    per_token = adapter.matrix_params_per_token(model)
    # a balanced router sends a token's 4 assignments to the 8 of 64 held
    # half an expert's worth
    assert per_token == (4 * conv + attn + dense + 4 * (router + expert / 2)
                         + 2048 * 8192)
    held = (4 * (conv + 2048 * 3) + attn + 2 * 64 + dense
            + 4 * (8 * expert + router + 64) + 11 * 2048 + 2048 * 8192)
    assert abs(held / 1e6 - 469.3) < 0.05  # the parameters held
    pairs = 8192 * 8193 // 2
    flops = adapter.flops_per_example(model, traffic)
    assert flops == 3.0 * (2 * 8192 * per_token + pairs * 32 * 4 * 64)
    assert 9.9e12 < flops < 10.1e12
    # the dense layer's feed-forward is 3.6 of them, attention's score
    # maps 0.8
    assert abs(3 * 2 * 8192 * dense / 1e12 - 3.56) < 0.01
    assert abs(3 * pairs * 32 * 4 * 64 / 1e12 - 0.82) < 0.01

    # the count the program reports, by the same formula at the rehearsal
    # size and by the shapes' product at the published one
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
            1 + 4 * 5 + 8 + 3 + 4 * 5 + 1)
        if want:
            assert sum(int(np.prod(p.shape)) for p in params) == want
        assert built["feeds"] == ["tokens", "labels"]
        assert len(built["loads"]) == 4


# ------------------------------------------------------- on the chip


def _on_chip(model, traffic, seed):
    """The cell's programs on the attached TPU with the seeded state."""
    import paddle_tpu as fluid
    from benchmark.models import lfm2 as adapter
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
    from benchmark.models import lfm2 as adapter
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
        variants = [("reference", p, ())] + [(w, p, (w,)) for w in only]
        if not only and at < few:
            variants += [("fp8", _fp8(p), ())] + [
                (w, p, (w,)) for w in adapter.WRONG]
        for label, params, wrong in variants:
            loss, logits = train_loop.reference_outputs(
                adapter, params, batch, model, 1, wrong=wrong)
            check = train_loop.check_reference(
                got_loss, got_logits, loss, logits, adapter.TOLERANCE)
            print(f"seed {seed} {label}: logits_rel_rms "
                  f"{check['logits_rel_rms']:.5f} loss_abs "
                  f"{check['loss_abs']:.5f} ok {check['ok']}", flush=True)


def held_loads(seeds, steps=44, rate=None):
    """At the published widths on the attached TPU, the cell's train step
    on the batches its runner would feed (one check batch drawn first,
    then the pool of 32), `steps` of them at `rate`: the share of the
    32,768 assignments that each expert layer's 8 held experts take, at
    the first step, the window's first (the fifth) and the last, and the
    largest over all steps, beside the first block's share; the loss, and
    its fall as the runner takes it."""
    import paddle_tpu as fluid
    from benchmark.models import lfm2 as adapter
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
            "short_conv_linear_calls", "short_conv_dispatch_pallas",
            "short_conv_dispatch_xla", "attn_dispatch_flash",
            "attn_qk_prep_fused", "flash_fwd_wide_key_calls",
            "moe_dispatch_grouped", "moe_dispatch_gmm")},
        {n: c1.get(n) for n in (
            "gated_conv_layers", "attention_layers", "expert_layers",
            "attn_kv_group", "moe_block_rows", "flash_blocks_visited",
            "flash_blocks_total")}, flush=True)


def chip_gradients():
    """The gradients of every kind of parameter at the published widths,
    program against `jax.grad` of the reference, on one 1,024-token row."""
    import paddle_tpu as fluid

    model, traffic = cell(rehearse=False, precision="float32")
    traffic = dict(traffic, seq_len=1024)
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
        print(f"gradients at the published widths, s=1024, {precision}: "
              "worst relative error by kind "
              + ", ".join(f"{k} {v:.2e}" for k, v in worst.items()), flush=True)


if __name__ == "__main__":
    import jax

    assert jax.devices()[0].platform == "tpu", jax.devices()
    what, _, rate = sys.argv[1].partition("@")
    what, _, only = what.partition(":")
    seeds = [int(a) for a in sys.argv[2:]] or [47001]
    {"readings": lambda: chip_readings(
        seeds, tuple(w for w in only.split(",") if w)),
     "loads": lambda: held_loads(seeds, rate=float(rate) if rate else None),
     "gradients": chip_gradients}[what]()
