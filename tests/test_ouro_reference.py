"""Ouro against its plain reference (`benchmark/models/ouro.py`) at the
rehearsal size of the cell `ouro_2p6b_vp8_s4096`: what every decoder suite
holds (`tests/decoder_suite.py`: the attention mixer and the sandwich
block alone, the whole model in float32 and under bf16 AMP with the logits
of all four steps, one train step's gradients for every kind of parameter
with a shared weight's the sum over its four uses, that each wrong model
is caught) on this model's data, and its own: the wrong models of the loss,
which the loss alone shows; the fp8 reference; that one use's gradient is
no rounding of four; the vocabulary's slices at every step; the builder
with one step and pre-norm blocks against a plain decoder, op for op; the
counters and gauges; the cell's arithmetic.

Run as a script on the attached TPU, outside any timed window
(`tests/decoder_suite.py` has the arguments):

    python3 tests/test_ouro_reference.py readings 1 2   # program, wrong models and fp8 reference against the reference, by step
    python3 tests/test_ouro_reference.py falls@3e-6 1 2   # the loss and its two terms over the window's steps at a rate
    python3 tests/test_ouro_reference.py gradients      # at the published widths on one 1,024-token row
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from decoder_suite import *  # noqa: F401,F403 — the shared cases, on SUITE
from decoder_suite import (FLOAT32_LIMITS, compiled, fp8, guards, highest,
                           main, rel, state)

from benchmark.models import ouro as adapter  # noqa: E402

CELL = "ouro_2p6b_vp8_s4096"
STEPS = 4  # total_ut_steps, at the rehearsal size too

# At 64 wide, seeded as the cell is (matrices Normal(0, 0.02)), q and k are
# 0.16 a lane and every score 0.03: the softmax is flat, and neither the
# positions nor a wrong mask show. With the matrices at 0.1 the scores
# spread as at the published width (0.02 x sqrt(2048) = 0.9 a product; 0.1
# x sqrt(64) = 0.8). What a sublayer adds to the stream needs no such help:
# its output norm puts it at the norm's weight whatever the matrices' scale.
AS_AT_WIDTH = {"initializer_range": 0.1}

# which wrong models change the stack (the logits show them) and which the
# loss alone (the logits are the right model's to the last bit)
OF_THE_STACK = ("pre_norm_only", "no_norm_between_steps",
                "fresh_weights_a_step", "no_rope")
OF_THE_LOSS = ("last_exit_only", "gate_mass_lost", "no_entropy")


def _mixer_program(which, model, batch, seq):
    """The attention mixer or a whole sandwich block alone in a Program:
    `u` in, `y` out."""
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_parts, ouro as zoo

    cfg = adapter.config(model)
    u = fluid.layers.data("u", [batch, seq, cfg.hidden_size],
                          append_batch_size=False)
    if which == "attention":
        return decoder_parts.attention(u, cfg, "m", rope_theta=cfg.rope_theta,
                                       qk_norm=False)
    return zoo.sandwich_block(u, cfg, "m")


def _want_mixer(which, p, feeds, model, wrong=()):
    if which == "attention":
        return highest(adapter.attention_mixer, p, feeds["u"], "m", model,
                       wrong)
    return highest(adapter.sandwich_block, p, feeds["u"], "m", model, wrong)


def _by_step(got, want):
    """A reading's logits split by the loop's step: a fault in one step
    shows in that step's and in the later ones'."""
    got = np.asarray(got, np.float32).reshape(np.shape(want))
    return " by step " + " ".join(
        f"{rel(got[:, t], np.asarray(want)[:, t]):.5f}"
        for t in range(np.shape(want)[1]))


KINDS = {
    "embedding": ("ouro.embed",), "head": ("ouro.head.w_0",),
    "rms_norm": (".input_norm.w_0", ".input_norm_2.w_0",
                 ".post_attn_norm.w_0", ".post_attn_norm_2.w_0",
                 "final_norm.w_0"),
    "attention": (".attn.q.w_0", ".attn.k.w_0", ".attn.v.w_0",
                  ".attn.o.w_0"),
    "ffn": (".mlp.gate.w_0", ".mlp.up.w_0", ".mlp.down.w_0"),
    "exit_gate": ("exit_gate.w_0", "exit_gate.b_0"),
}

SUITE = Suite(  # noqa: F405
    CELL, adapter, kinds=KINDS, as_at_width=AS_AT_WIDTH,
    # the norms' weights off 1, so that a norm left out or misplaced shows
    moved=lambda n: "norm" in n,
    mixers=("attention", "block"), mixer_program=_mixer_program,
    want_mixer=_want_mixer,
    wrong_by_mixer={"attention": ("no_rope",), "block": ("pre_norm_only",)},
    # the reference with its last layer left out at every step, or with a
    # departure of the stack: refused by the logits' limit with room (the
    # mildest, no positions, reads 0.6 of the reference's scale at this
    # size). A departure of the loss: refused by the loss's limit alone
    # (`test_a_wrong_loss_...` below has the sizes)
    wrong={"drop_layers": caught(amp=3, drop_layers=1),  # noqa: F405
           **{w: caught(amp=3, wrong=(w,)) for w in OF_THE_STACK},  # noqa: F405
           **{w: caught(amp=0, wrong=(w,)) for w in OF_THE_LOSS}},  # noqa: F405
    reading_more=_by_step, seed=57001, chip_routed=(None, None),
    step_counters=("attn_dispatch_flash", "attn_dispatch_xla",
                   "attn_qk_prep_fused", "attn_qk_prep_rope_only",
                   "param_grads_summed",
                   "param_grad_partials", "rms_bwd_calls"),
    gauges=("loop_steps", "loop_layers", "loss_terms", "attn_kv_group",
            "flash_blocks_visited", "flash_blocks_total"))


def test_the_adapters_wrong_models_are_all_listed():
    assert sorted(adapter.WRONG) == sorted(OF_THE_STACK + OF_THE_LOSS)


# ------------------------------------------------ the logits of every step


def test_every_steps_logits_are_scored_and_each_is_the_references(float32_run):
    """The check's one array holds the four steps' scored logits step
    after step; each step's, alone, is the reference's, and the steps
    differ from each other by far more than any rounding (so none stands
    in for another)."""
    model, batch, _, got = float32_run
    _, want = SUITE.reference(float32_run)
    assert want.shape == (1, STEPS, 80 // adapter.SCORED_EVERY,
                          model["vocab_size"])
    got = np.asarray(got[1]).reshape(want.shape)
    for t in range(STEPS):
        assert rel(got[:, t], want[:, t]) < FLOAT32_LIMITS["logits_rel_rms"], t
        for other in range(t):
            assert rel(got[:, t], want[:, other]) > 0.05, (t, other)


@pytest.mark.parametrize("wrong", OF_THE_STACK)
def test_a_wrong_stack_shows_from_the_step_it_starts_at(wrong, float32_run):
    """No norm between the steps and weights of its own a step leave the
    first step's logits the right model's and show from the second on: a
    check of the last step alone would see them, a check of the first
    alone would not, and a fault that healed by the last step would pass
    the published class's one set of logits."""
    _, want = SUITE.reference(float32_run)
    _, off = SUITE.reference(float32_run, wrong=(wrong,))
    by_step = [rel(off[:, t], want[:, t]) for t in range(STEPS)]
    first_wrong = 1 if wrong in ("no_norm_between_steps",
                                 "fresh_weights_a_step") else 0
    assert all(r < 1e-6 for r in by_step[:first_wrong]), by_step
    assert all(r > 0.15 for r in by_step[first_wrong:]), by_step


# ------------------------------------------------------ the loss's terms


@pytest.mark.parametrize("wrong,least", [
    ("last_exit_only", 0.05), ("gate_mass_lost", 0.2), ("no_entropy", 0.1)])
def test_a_wrong_loss_moves_the_loss_and_not_the_logits(wrong, least,
                                                        float32_run):
    """The three wrong losses leave every step's logits as they are and
    move the loss by at least `least` nats at the seeded gate (lam about
    1/2: p = 1/2, 1/4, 1/8, 1/8, H(p) = 1.21, so the entropy term is 0.12
    and the mass the wrong last exit loses an eighth of a loss of about
    5; the last exit's own loss lies below the expected one by part of
    what the entropy term takes off, so that one moves least): ten
    times the cell's limit on the loss and more."""
    loss, want = SUITE.reference(float32_run)
    off_loss, off = SUITE.reference(float32_run, wrong=(wrong,))
    np.testing.assert_array_equal(off, want)
    assert abs(off_loss - loss) > least, (wrong, off_loss, loss)
    assert least >= 10 * adapter.TOLERANCE["loss_abs"]
    check = SUITE.check(float32_run, wrong=(wrong,))
    assert not check["ok"] and check["logits_rel_rms"] < 5e-5, check


def test_the_loss_is_its_two_terms_and_the_exits_sum_to_one(float32_run):
    """`terms`: the expected cross-entropy under the exit distribution and
    the mean entropy; loss = the first - beta x the second. The
    reference's distribution sums to 1 a token, and with the last exit's
    mass multiplied by its gate it does not."""
    model, batch, p, got = float32_run
    loss, task, entropy = (float(np.asarray(x).reshape(-1)[0])
                           for x in (got[0], got[2], got[3]))
    assert abs(loss - (task - model["entropy_weight"] * entropy)) < 1e-6
    assert 0.9 < entropy < np.log(STEPS)  # near the seeded gate's 1.21
    lams = [np.full((2, 5), v, np.float32) for v in (0.3, 0.6, 0.2, 0.9)]
    ps = adapter.exit_distribution(lams)
    np.testing.assert_allclose(sum(ps), 1.0, atol=1e-6)
    np.testing.assert_allclose(
        [q[0, 0] for q in ps], [0.3, 0.7 * 0.6, 0.7 * 0.4 * 0.2,
                                0.7 * 0.4 * 0.8], atol=1e-6)
    lost = adapter.exit_distribution(lams, wrong=("gate_mass_lost",))
    assert abs(float(sum(lost)[0, 0]) - (1 - 0.7 * 0.4 * 0.8 * 0.1)) < 1e-6


def test_the_fp8_reference_is_refused(amp_run):
    """The reference with its matrices rounded to fp8 (e4m3), the nearest
    precision below the bf16 the configuration states, against the
    program under bf16 AMP: refused by the logits' limit, the right
    reference on the same state admitted."""
    from benchmark.runners import train_loop

    model, batch, p, got = amp_run
    assert SUITE.check(amp_run)["ok"]
    nll, count, want = highest(adapter.reference, fp8(p), batch, model)
    check = train_loop.check_reference(
        got[0], got[1], nll / count, want[:adapter.SCORED_SEQUENCES],
        adapter.TOLERANCE)
    assert not check["ok"], check
    assert check["logits_rel_rms"] > 1.2 * adapter.TOLERANCE["logits_rel_rms"]


# ------------------------------------- a shared weight's four partials


def test_one_uses_gradient_is_no_rounding_of_the_four(float32_run,
                                                      monkeypatch):
    """`jax.grad` of the reference with the layers' matrices of the later
    steps held constant (one use's gradient where the train step sums
    four) differs from the true one by far more than the limit the
    gradients' case holds the program to, so that limit does hold the
    sum."""
    import jax

    model, batch, p, _ = float32_run
    name = "ouro.layer0.mlp.up.w_0"
    whole = compiled(jax.grad(lambda p: SUITE.loss(p, batch, model)), p)[name]
    monkeypatch.setattr(
        adapter, "_fresh",
        lambda p, step, std: jax.tree.map(jax.lax.stop_gradient, p))
    one = compiled(jax.grad(lambda p: SUITE.loss(
        p, batch, model, wrong=("fresh_weights_a_step",))), p)[name]
    assert rel(one, whole) > 0.3


def test_every_norms_gradient_is_its_own_op_and_the_kernels_is_the_references(
        monkeypatch):
    """The backward holds one `rms_norm_grad` a norm and no generic grad
    op of `rms_norm`. With the kernel admitted at the rehearsal's 64 lanes
    (the shape rule steered here, in the test; the interpreter runs it)
    all 36 sites take `rms_bwd`, a shared weight's four partials are
    summed as before, and every kind of parameter is `jax.grad` of the
    reference's within the limit the gradients' case holds the vjp to."""
    from decoder_suite import check_gradients

    from paddle_tpu.ops.pallas import layer_norm

    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(layer_norm, "rms_bwd_viable", lambda n, k: True)
    model, traffic = SUITE.cell(precision="float32", **SUITE.gradients_at)
    norms = STEPS * (4 * model["num_hidden_layers"] + 1)
    step = SUITE.gradients(model, dict(traffic, seq_len=80))
    ops = step.main.global_block().ops
    assert [op.type for op in ops].count("rms_norm") == norms
    assert [op.type for op in ops].count("rms_norm_grad") == norms
    assert not [op for op in ops if op.type == "__auto_grad__"
                and op.attr("fwd_type") == "rms_norm"]
    assert step.bumped("rms_bwd_calls") == norms
    assert step.bumped("param_grad_partials") == (
        STEPS * (11 * model["num_hidden_layers"] + 2) + (STEPS - 1) * 2)
    worst = check_gradients(step.got, step.want, step.before, 2e-4,
                            kinds=SUITE.kinds)
    assert set(worst) == set(KINDS)


# ------------------------------------------------ the vocabulary's slices


def test_the_eight_slices_logits_are_the_uncut_models_at_every_step():
    """One chip's Program holds an eighth of the head's columns. Run
    with each of the eight slices of one uncut head in turn, on the same
    layers and the same row, its four steps' logits side by side are the
    reference's with the whole head: the stream never reads a logit, so a
    slice changes nothing but its own columns. (The ids are drawn from the
    first slice's rows, which every chip's embedding holds here.)"""
    import paddle_tpu as fluid

    slices = 8
    model, traffic = SUITE.cell(precision="float32", **AS_AT_WIDTH)
    per = model["vocab_size"] // slices
    cut = dict(model, vocab_size=per)
    traffic = dict(traffic, batch=1, seq_len=32)
    r = np.random.RandomState(5)
    head = (r.randn(model["hidden_size"], model["vocab_size"]) * 0.3
            ).astype(np.float32)
    with guards():
        _, eval_prog, built, exe, names = SUITE.built_model(cut, traffic)
        batch = SUITE.batch_for(cut, traffic)
        scope, got = fluid.global_scope(), []
        for i in range(slices):
            scope.set("ouro.head.w_0", head[:, i * per:(i + 1) * per])
            got.append(np.asarray(exe.run(
                eval_prog, feed=batch, fetch_list=[built["check"][1]])[0]))
        p = state(names)
    assert got[0].shape == (1, STEPS * 32 // adapter.SCORED_EVERY, per)
    p["ouro.head.w_0"] = head
    table = np.zeros((model["vocab_size"], model["hidden_size"]), np.float32)
    table[:per] = p["ouro.embed"]
    p["ouro.embed"] = table
    _, _, want = highest(adapter.reference, p, batch, model)
    got = np.concatenate(got, -1).reshape(want.shape)
    assert want.shape == (1, STEPS, 2, model["vocab_size"])
    for t in range(STEPS):
        assert rel(got[:, t], want[:, t]) < 1e-5, t


# -------------------------------- one step and pre-norm: a plain decoder


def _ops(program):
    return [(op.type, json.dumps(op.inputs, sort_keys=True, default=str),
             json.dumps(op.outputs, sort_keys=True, default=str),
             json.dumps({k: v for k, v in op.attrs.items()
                         if not k.startswith("op_")}, sort_keys=True,
                        default=str))
            for op in program.global_block().ops]


def test_one_step_of_pre_norm_blocks_is_a_plain_decoder_op_for_op():
    """`build_ouro` with `total_ut_steps` 1 and `sandwich_norm` False
    against a decoder written here from `decoder_parts.attention` and
    `ffn`: the same ops on the same names with the same attributes, so
    the loop and the output norms are all that the builder adds. With
    the loop on, every step repeats the stack's ops on the same
    parameters."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.initializer import Normal
    from paddle_tpu.models import decoder_parts as parts, ouro as zoo
    from paddle_tpu.param_attr import ParamAttr

    model, _ = SUITE.cell()
    b, s = 2, 16

    def cfg(**kw):
        c = adapter.config(model)
        for k, v in kw.items():
            setattr(c, k, v)
        return c

    def plain(c):
        tokens = layers.data("tokens", [b, s], dtype="int64",
                             append_batch_size=False)
        labels = layers.data("labels", [b, s], dtype="int64",
                             append_batch_size=False)
        x = layers.embedding(
            tokens, (c.vocab_size, c.hidden_size),
            param_attr=ParamAttr(name="ouro.embed", initializer=Normal(
                0.0, c.embedding_initializer_range)))
        label_col = layers.reshape(labels, [b, s, 1])
        for l in range(c.layers_held):
            n = f"ouro.layer{l}"
            x = layers.elementwise_add(x, parts.attention(
                parts.norm(x, n + ".input_norm", c), c, n + ".attn",
                rope_theta=c.rope_theta, qk_norm=False))
            x = layers.elementwise_add(x, parts.ffn(
                parts.norm(x, n + ".post_attn_norm", c),
                c.intermediate_size, n + ".mlp", c))
        logits = parts.proj(parts.norm(x, "ouro.final_norm", c),
                            c.vocab_size, "ouro.head", c)
        return layers.mean(layers.cast(layers.softmax_with_cross_entropy(
            logits, label_col), "float32"))

    def built(fn):
        with fluid.program_guard(fluid.Program(), fluid.Program()), \
                fluid.unique_name.guard():
            fn()
            return (_ops(fluid.default_main_program()),
                    _ops(fluid.default_startup_program()))

    one = cfg(total_ut_steps=1, sandwich_norm=False)
    ours = built(lambda: zoo.build_ouro(one, b, s))
    assert ours == built(lambda: plain(one))
    types = [op[0] for op in ours[0]]
    assert types.count("rms_norm") == 2 * one.layers_held + 1
    assert "sigmoid" not in types and "log" not in types  # no gate, no entropy
    # the loop: four times the stack's ops, one seeding a parameter
    looped, seeded = built(lambda: zoo.build_ouro(
        cfg(sandwich_norm=False), b, s))
    for kind in ("fused_multihead_attention", "rotary_embedding", "mul",
                 "rms_norm", "softmax_with_cross_entropy"):
        assert [op[0] for op in looped].count(kind) == STEPS * types.count(
            kind), kind
    assert len(seeded) == len(ours[1]) + 2  # the gate's weight and bias
    sandwich = built(lambda: zoo.build_ouro(cfg(), b, s))[0]
    assert [op[0] for op in sandwich].count("rms_norm") == STEPS * (
        4 * one.layers_held + 1)


# ------------------------------------------- gauges, counters, the cell


def test_gauges_and_counters_at_the_rehearsal_size(monkeypatch):
    from paddle_tpu import profiler

    # no interpreter, whatever a test file imported before this one set
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    model, traffic = SUITE.cell()
    layers_held = model["num_hidden_layers"]
    assert (model["total_ut_steps"], layers_held) == (STEPS, 2)
    before = profiler.counters()
    with guards():
        main, _, built, exe, names = SUITE.built_model(model, traffic)
        built_at = profiler.counters()
        exe.run(main, feed=SUITE.batch_for(model, traffic),
                fetch_list=[built["loss"]])
    after = profiler.counters()
    assert {n: after[n] for n in ("loop_steps", "loop_layers", "loss_terms",
                                  "attn_kv_group")} == {
        "loop_steps": STEPS, "loop_layers": layers_held,
        "loss_terms": STEPS + 1, "attn_kv_group": 1}

    def bumped(name, since=before, till=after):
        return till.get(name, 0) - since.get(name, 0)

    # at Program build time: every parameter but the embedding has more
    # than one use. Eleven a layer (four norms, seven matrices), the final
    # norm and the head at four partials each; the gate's weight and bias
    # at three, since the last step's gate is in no term of the loss
    shared = 11 * layers_held + 2
    assert len(names) == shared + 2 + 1
    assert bumped("param_grads_summed", till=built_at) == shared + 2
    assert bumped("param_grad_partials", till=built_at) == (
        STEPS * shared + (STEPS - 1) * 2)
    assert bumped("param_grads_summed", since=built_at) == 0  # not at run time
    sums = [op for op in main.global_block().ops if op.type == "sum"
            and op.output("Out")[0].endswith("@GRAD")
            and op.output("Out")[0][:-5] in names]
    assert sorted(len(op.input("X")) for op in sums) == (
        [STEPS - 1] * 2 + [STEPS] * shared)
    # one attention call a layer application, lowered again by its
    # gradient op: the plain path here, the flash kernels on the chip
    # (`falls` prints the counters there)
    assert bumped("attn_dispatch_xla") == 2 * STEPS * layers_held
    assert bumped("attn_dispatch_flash") == 0
    # `qk_prep` runs where the flash kernels do: without weights on the
    # chip (`attn_qk_prep_rope_only`), not at all here
    assert bumped("attn_qk_prep_fused") == 0
    assert bumped("attn_qk_prep_rope_only") == 0
    # 64 lanes and no Pallas here: the norms' gradients are the vjp's (100
    # sites take `rms_bwd` at the cell's width on the chip)
    assert bumped("rms_bwd_calls") == 0
    ops = [op.type for op in main.global_block().ops]
    assert ops.count("fused_multihead_attention") == STEPS * layers_held
    # the positions are the attention op's, with no norm in front of them
    assert ops.count("rotary_embedding") == 0
    calls = [op for op in main.global_block().ops
             if op.type == "fused_multihead_attention"]
    assert all(op.attr("rope_theta") == model["rope_theta"]
               and not op.input("QNorm") for op in calls)
    assert ops.count("softmax_with_cross_entropy") == STEPS
    assert ops.count("lookup_table") == 1
    # and no counter that is another decoder's
    for other in ("moe_dispatch_grouped", "attn_rope_scaled",
                  "short_conv_linear_calls", "ssm_dispatch_chunked"):
        assert bumped(other) == 0, other


def test_attention_through_the_flash_kernels_and_qk_prep_without_a_norm(
        monkeypatch, attn_path):
    """The path the chip takes, interpreted and forced by name since the
    CPU's dispatch never chooses it: at a head of 128 lanes the positions
    and the head-major write are `qk_prep`'s pass without weights, in
    front of the blocked kernel, and the mixer is the reference's."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    attn_path("flash")
    m = SUITE.mixer("attention", batch=1, seq=160, seed=2,
                    config={"head_dim": 128, **AS_AT_WIDTH})
    for name in ("attn_dispatch_flash", "attn_qk_prep_fused",
                 "attn_qk_prep_rope_only"):
        assert m.bumped(name) == 1, name
    assert m.bumped("attn_qk_prep_handed_back") == 0
    assert rel(m.got, m.want()) < 2e-5
    assert rel(m.got, m.want(("no_rope",))) > 0.05


def test_a_model_with_every_weight_used_once_counts_nothing():
    """JoyAI's two shared tables count two partials each; a decoder whose
    weights have one use each counts none."""
    import paddle_tpu as fluid
    from benchmark.harness import spec
    from benchmark.runners import train_loop
    from paddle_tpu import profiler

    def counted(cell_name):
        c = spec.cell(cell_name, rehearse=True)
        before = profiler.counters()
        with guards():
            train_loop.build_programs(
                fluid, spec.plugin("models", c["config"]["adapter"]),
                c["config"], c["traffic"], 3)
        after = profiler.counters()
        return tuple(after.get(n, 0) - before.get(n, 0) for n in (
            "param_grads_summed", "param_grad_partials"))

    assert counted("trinity_mini_ep16_s8192") == (0, 0)
    assert counted("joyai_flash_ep32_s4096") == (2, 4)


def test_parameters_and_flops_of_the_cell():
    model, traffic = SUITE.cell(rehearse=False)
    assert (traffic["batch"], traffic["seq_len"]) == (1, 4096)
    assert model["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert model["total_ut_steps"] == STEPS  # the loop is not cut
    held = model["num_hidden_layers"]
    assert adapter.held_layers(model) == list(range(held))
    # ISSUE 57's arithmetic, redone
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert adapter.layer_matrix_params(model) == layer == 51380224
    head = 2048 * model["vocab_size"]
    per_token = adapter.matrix_params_per_token(model)
    assert per_token == STEPS * (held * layer + head)
    # the heads' share of a step's products is the published model's
    published = 4 * 2048 * 49152 / (4 * 48 * layer + 4 * 2048 * 49152)
    assert abs(STEPS * head / per_token - published) < (
        1e-9 if held == 6 else 0.02)
    params = held * (layer + 4 * 2048) + 2 * head + 2048 + 2048 + 1
    assert abs(params / 1e6 - (333.5 if held == 6 else 230.7)) < 0.1
    pairs = 4096 * 4097 // 2
    assert adapter.admitted_pairs(4096) == pairs
    flops = adapter.flops_per_example(model, traffic)
    assert flops == 3.0 * (2 * 4096 * per_token
                           + STEPS * held * pairs * 16 * 4 * 128)
    if held == 6:
        assert 36.0e12 < flops < 37.0e12


if __name__ == "__main__":
    main(SUITE)
