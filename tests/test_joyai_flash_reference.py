"""JoyAI-LLM-Flash against its plain reference (`tests/joyai_flash_reference.py`)
at the rehearsal size of the cell `joyai_flash_ep32_s4096`: latent attention
with the compressed query and the rotation by pairs, the expert layer, and
the whole model on both heads' logits and the two-term loss; one train
step's gradients for every kind of parameter, the embedding's and the
head's (each the sum of two uses) among them; that each wrong model is
caught by the cell's tolerance; the rotation by pairs against the formula
written out by hand and its gradient; the one-head key part's gradient
summed over the heads; the four shares against the uncut layer with the
shared expert counted once; Kimi's, Trinity's and Mellum's Programs held to
their op lists; the cell's counters and FLOPs.

Run as a script, on the attached TPU at the published widths and outside
any timed window: the program against the reference, against each wrong
model and against the reference with fp8 matrices (the readings that place
`TOLERANCE`); the held load of each expert layer and both loss terms, step
by step, at a learning rate (the sweep that chose the optimizer's rate):

    python3 tests/test_joyai_flash_reference.py readings[:wrong,wrong] [seed ...]
    python3 tests/test_joyai_flash_reference.py loads[@rate] [seed ...]
    python3 tests/test_joyai_flash_reference.py gradients
"""

from __future__ import annotations

import inspect
import math
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import joyai_flash_reference as ref  # noqa: E402 — beside this file
from test_kimi_linear_reference import (  # noqa: E402 — the shared helpers
    check_gradients, f32, highest, rel, state)
from test_mellum_reference import _fp8  # noqa: E402 — the matrices in e4m3

CELL = "joyai_flash_ep32_s4096"


def cell(rehearse=True, **config):
    from benchmark.harness import spec

    c = spec.cell(CELL, rehearse=rehearse)
    c["config"].update(config)
    return c["config"], c["traffic"]


def built_model(model, traffic, seed=3):
    """Programs, executor and the seeded state by name, in a scope of its
    own (the caller holds the guards)."""
    import paddle_tpu as fluid
    from benchmark.models import joyai_flash as adapter
    from benchmark.runners import train_loop

    main, startup, built, eval_prog = train_loop.build_programs(
        fluid, adapter, model, traffic, seed)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    names = [p.name for p in main.global_block().all_parameters()]
    return main, eval_prog, built, exe, names


def batch_for(model, traffic, seed=0):
    from benchmark.models import joyai_flash as adapter

    return adapter.make_batch(np.random.RandomState(seed), model, traffic)


# At 64 wide, seeded as the cell is (matrices Normal(0, 0.02), the
# embedding Normal(0, 2)), a layer adds a thousandth of the residual stream
# and no wrong model shows in the logits. With the matrices at 0.1 and the
# embedding at 0.3 (Mellum's test has the same two) the layers weigh in the
# stream as at the published width (0.02 x sqrt(2048) = 0.9 a product).
AS_AT_WIDTH = {"initializer_range": 0.1, "embedding_initializer_range": 0.3}


# ------------------------------------------------- the copy is a copy


def test_reference_copy_is_the_adapters_word_for_word():
    from benchmark.models import joyai_flash as adapter

    for name in ("_rms", "_silu", "_ffn", "_rope", "latent_mixer",
                 "expert_ffn", "block", "reference"):
        assert inspect.getsource(getattr(ref, name)) == inspect.getsource(
            getattr(adapter, name)), name
    assert (ref.SCORED_EVERY, ref.QUERY_BLOCK) == (
        adapter.SCORED_EVERY, adapter.QUERY_BLOCK)


# ------------------------------------------------ the rotation by pairs


def _by_hand(x, theta):
    """d = 8: lanes (0,1), (2,3), (4,5), (6,7) turned by p times
    theta^0, theta^-1/4, theta^-1/2, theta^-3/4, one lane at a time."""
    want = np.zeros_like(x)
    for p in range(x.shape[1]):
        for i in range(4):
            a = p * theta ** (-2 * i / 8)
            even, odd = x[:, p, :, 2 * i], x[:, p, :, 2 * i + 1]
            want[:, p, :, 2 * i] = even * math.cos(a) - odd * math.sin(a)
            want[:, p, :, 2 * i + 1] = odd * math.cos(a) + even * math.sin(a)
    return want


def test_rotation_by_pairs_equals_the_formula_written_out_by_hand():
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import profiler

    shape, theta = (2, 12, 3, 8), 10000.0
    x = fluid.layers.data("x", list(shape), append_batch_size=False)
    pairs = fluid.layers.rotary_embedding(x, theta=theta, interleaved=True)
    halves = fluid.layers.rotary_embedding(x, theta=theta)
    ops = fluid.default_main_program().global_block().ops
    assert ops[0].attr("interleaved") is True and "interleaved" not in ops[1].attrs
    data = np.random.RandomState(0).randn(*shape).astype(np.float32)
    before = profiler.counters().get("rope_interleaved", 0)
    got_pairs, got_halves = fluid.Executor(fluid.CPUPlace()).run(
        feed={"x": data}, fetch_list=[pairs, halves])
    assert profiler.counters()["rope_interleaved"] == before + 1
    want = _by_hand(data.astype(np.float64), theta)
    np.testing.assert_allclose(got_pairs, want, atol=2e-5)
    np.testing.assert_allclose(highest(ref._rope, jnp.asarray(data), theta),
                               want, atol=2e-5)
    # the other pairing is another function, and the reference's wrong one
    assert rel(got_halves, want) > 0.1
    np.testing.assert_allclose(
        got_halves, highest(ref._rope, jnp.asarray(data), theta, True),
        atol=2e-5)
    # position 0 is left as it is, and a turn keeps each plane's length
    np.testing.assert_array_equal(got_pairs[:, 0], data[:, 0])
    np.testing.assert_allclose(
        got_pairs[..., 0::2] ** 2 + got_pairs[..., 1::2] ** 2,
        data[..., 0::2] ** 2 + data[..., 1::2] ** 2, rtol=1e-4)


def test_rotation_by_pairs_gradient_equals_jax_grad():
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid

    shape, theta = (2, 12, 3, 8), 10000.0
    x = fluid.layers.data("x", list(shape), append_batch_size=False)
    x.stop_gradient = False
    w = fluid.layers.data("w", list(shape), append_batch_size=False)
    y = fluid.layers.rotary_embedding(x, theta=theta, interleaved=True)
    total = fluid.layers.reduce_sum(fluid.layers.elementwise_mul(y, w))
    (dx,) = fluid.backward.gradients([total], [x])
    r = np.random.RandomState(1)
    data, weight = (r.randn(*shape).astype(np.float32) for _ in range(2))
    (got,) = fluid.Executor(fluid.CPUPlace()).run(
        feed={"x": data, "w": weight}, fetch_list=[dx])
    want = jax.grad(lambda t: jnp.sum(ref._rope(t, theta) * weight))(
        jnp.asarray(data))
    np.testing.assert_allclose(got, want, atol=2e-5)
    # a turn's transpose is the turn back: the cotangent, turned by -p
    def swapped(t):  # the two lanes of each plane change places
        return t.reshape(*t.shape[:-1], 4, 2)[..., ::-1].reshape(t.shape)

    back = swapped(_by_hand(swapped(weight.astype(np.float64)), theta))
    np.testing.assert_allclose(got, back, atol=2e-5)


@pytest.mark.parametrize("s,d,theta", [(48, 8, 10000.0),
                                       (4096, 64, 32000000.0)])
def test_tables_without_interleaved_are_todays_bit_for_bit(s, d, theta):
    """`rotary_tables` with no `interleaved`, written out as the parent
    has it; and the tables by pairs carry the same angles, lane by lane."""
    import jax.numpy as jnp

    from paddle_tpu.ops.nn_ops import rotary_tables

    freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)
    sin = jnp.concatenate([-jnp.sin(angle), jnp.sin(angle)], -1)
    for extra in ((), (None,), (None, False)):
        got_cos, got_sin = rotary_tables(s, d, theta, *extra)
        assert np.array_equal(got_cos, cos) and np.array_equal(got_sin, sin)
    pair_cos, pair_sin = rotary_tables(s, d, theta, interleaved=True)
    assert np.array_equal(pair_cos[:, 0::2], cos[:, :d // 2])
    assert np.array_equal(pair_cos[:, 1::2], cos[:, :d // 2])
    assert np.array_equal(pair_sin[:, 0::2], sin[:, :d // 2])
    assert np.array_equal(pair_sin[:, 1::2], sin[:, d // 2:])


def test_one_head_key_parts_gradient_is_the_sum_over_the_heads():
    """The shared key part is turned as one head and broadcast to four by
    `expand`: its gradient is the four heads' gradients summed, and the
    same as turning four copies and summing."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid

    b, s, h, d, theta = 2, 12, 4, 8, 10000.0
    k = fluid.layers.data("k", [b, s, 1, d], append_batch_size=False)
    k.stop_gradient = False
    w = fluid.layers.data("w", [b, s, h, d], append_batch_size=False)
    spread = fluid.layers.expand(
        fluid.layers.rotary_embedding(k, theta=theta, interleaved=True),
        [1, 1, h, 1])
    total = fluid.layers.reduce_sum(fluid.layers.elementwise_mul(spread, w))
    (dk,) = fluid.backward.gradients([total], [k])
    r = np.random.RandomState(2)
    data = r.randn(b, s, 1, d).astype(np.float32)
    weight = r.randn(b, s, h, d).astype(np.float32)
    (got,) = fluid.Executor(fluid.CPUPlace()).run(
        feed={"k": data, "w": weight}, fetch_list=[dk])
    assert got.shape == (b, s, 1, d)
    per_head = jax.grad(lambda t: jnp.sum(ref._rope(t, theta) * weight))(
        jnp.broadcast_to(jnp.asarray(data), (b, s, h, d)))
    np.testing.assert_allclose(got, np.sum(per_head, 2, keepdims=True),
                               atol=5e-5)


# ------------------------------------------ the program, mixer by mixer


def _mixer_program(which, model, batch=2, seq=80):
    """Latent attention or the expert layer alone in a Program: `u` in,
    `y` out."""
    import paddle_tpu as fluid
    from benchmark.models import joyai_flash as adapter
    from paddle_tpu.models import decoder_parts

    cfg = adapter.config(model)
    u = fluid.layers.data("u", [batch, seq, cfg.hidden_size],
                          append_batch_size=False)
    if which == "experts":
        y, _ = decoder_parts.expert_ffn(u, cfg, "m")
    else:
        y = decoder_parts.latent_attention(u, cfg, "m")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    names = [p.name for p in
             fluid.default_main_program().global_block().all_parameters()]
    return exe, y, names


@pytest.mark.parametrize("which", ["latent", "experts"])
def test_program_mixer_equals_reference(which):
    from paddle_tpu import profiler

    model, _ = cell(**AS_AT_WIDTH)
    exe, y, names = _mixer_program(which, model)
    u = np.random.RandomState(1).randn(2, 80, model["hidden_size"]).astype(
        np.float32)
    before = profiler.counters()
    (got,) = exe.run(feed={"u": u}, fetch_list=[y])
    after = profiler.counters()
    p = state(names)
    if which == "latent":
        assert sorted(n[2:] for n in names) == [
            "kv_a.w_0", "kv_a_norm.w_0", "kv_b.w_0", "o.w_0", "q_a.w_0",
            "q_a_norm.w_0", "q_b.w_0"]
        assert after["attn_latent_q_lora"] == before.get(
            "attn_latent_q_lora", 0) + 1
        assert after["rope_interleaved"] == before.get(
            "rope_interleaved", 0) + 2
        want = highest(ref.latent_mixer, p, u, "m", model)
        for wrong in ("no_rope", "half_pairs", "rope_on_nope", "no_q_norm",
                      "scale_128"):
            other = highest(ref.latent_mixer, p, u, "m", model, (wrong,))
            assert rel(other, want) > 1e-3, wrong
    else:
        want = highest(ref.expert_ffn, p, u, "m", model)
    assert np.abs(want).max() > 1e-4  # something was computed
    assert rel(got, want) < 2e-5


def test_latent_attention_through_the_flash_kernel(monkeypatch, attn_path):
    """The blocked kernel, interpreted, at the published head widths
    (192-wide keys, 128-wide values, padded inside), forced by name since
    the CPU's dispatch never chooses it."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    attn_path("flash")
    from paddle_tpu import profiler

    model, _ = cell(qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                    num_attention_heads=2, **AS_AT_WIDTH)
    before = profiler.counters().get("attn_dispatch_flash", 0)
    exe, y, names = _mixer_program("latent", model, batch=1, seq=160)
    u = np.random.RandomState(2).randn(1, 160, model["hidden_size"]).astype(
        np.float32)
    (got,) = exe.run(feed={"u": u}, fetch_list=[y])
    assert profiler.counters()["attn_dispatch_flash"] == before + 1
    want = highest(ref.latent_mixer, state(names), u, "m", model)
    assert rel(got, want) < 2e-5


def test_kimis_latent_layer_is_built_with_both_off():
    """`decoder_parts.latent_attention` under Kimi's configuration: one
    query projection, no rotation, and no new counter."""
    import paddle_tpu as fluid
    from benchmark.harness import spec
    from benchmark.models import kimi_linear as adapter
    from paddle_tpu import profiler
    from paddle_tpu.models import decoder_parts

    import kimi_linear_reference as kimi_ref

    model = spec.cell("kimi_linear_ep32_s4096", rehearse=True)["config"]
    model["initializer_range"] = 0.1
    cfg = adapter.config(model)
    assert (cfg.q_lora_rank, cfg.rope_theta, cfg.rope_interleave) == (
        None, 0.0, False)
    u = fluid.layers.data("u", [2, 80, cfg.hidden_size],
                          append_batch_size=False)
    y = decoder_parts.latent_attention(u, cfg, "m")
    block = fluid.default_main_program().global_block()
    assert [op.type for op in block.ops] == [
        "mul", "reshape2", "mul", "split", "rms_norm", "mul", "reshape2",
        "split", "reshape2", "expand", "concat", "fused_multihead_attention",
        "reshape2", "mul"]
    assert "q_lora_rank" not in block.ops[11].attrs
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    names = [p.name for p in block.all_parameters()]
    assert sorted(n[2:] for n in names) == [
        "kv_a.w_0", "kv_a_norm.w_0", "kv_b.w_0", "o.w_0", "q.w_0"]
    data = np.random.RandomState(1).randn(2, 80, cfg.hidden_size).astype(
        np.float32)
    before = profiler.counters()
    (got,) = exe.run(feed={"u": data}, fetch_list=[y])
    after = profiler.counters()
    for counter in ("attn_latent_q_lora", "rope_interleaved"):
        assert after.get(counter, 0) == before.get(counter, 0)
    want = highest(kimi_ref.latent_mixer, state(names), data, "m", model)
    assert rel(got, want) < 2e-5


# ------------------------------------------------------ the whole model


@pytest.fixture(scope="module")
def amp_run():
    """The cell's program at the rehearsal size in the cell's precision,
    built and run once for the tests below: (model, batch, parameters,
    [loss, scored logits, main term, module's term])."""
    import paddle_tpu as fluid

    model, traffic = cell(**AS_AT_WIDTH)
    with fluid.program_guard(fluid.Program(), fluid.Program()), \
            fluid.unique_name.guard(), fluid.scope_guard(fluid.Scope()):
        _, eval_prog, built, exe, names = built_model(model, traffic)
        batch = batch_for(model, traffic)
        got = exe.run(eval_prog, feed=batch,
                      fetch_list=built["check"] + built["terms"])
        return model, batch, state(names), got


def _check(got, p, batch, model, **kw):
    from benchmark.models import joyai_flash as adapter
    from benchmark.runners import train_loop

    nll, count, want = highest(adapter.reference, p, batch, model, **kw)
    return want, train_loop.check_reference(
        got[0], got[1], nll / count, want[:adapter.SCORED_SEQUENCES],
        adapter.TOLERANCE)


def test_whole_model_both_heads_and_the_loss_equal_reference_float32():
    from benchmark.models import joyai_flash as adapter

    model, traffic = cell(precision="float32", **AS_AT_WIDTH)
    traffic = dict(traffic, seq_len=80)
    _, eval_prog, built, exe, names = built_model(model, traffic)
    batch = batch_for(model, traffic)
    assert sorted(batch) == ["labels", "labels_mtp", "tokens"]
    np.testing.assert_array_equal(batch["labels"][:, :-1], batch["tokens"][:, 1:])
    np.testing.assert_array_equal(batch["labels_mtp"][:, :-1],
                                  batch["labels"][:, 1:])
    got = exe.run(eval_prog, feed=batch,
                  fetch_list=built["check"] + built["terms"])
    loss, logits, main_loss, mtp_loss = (np.asarray(x) for x in got)
    rows = adapter.SCORED_SEQUENCES
    assert logits.shape == (rows, 2 * 80 // adapter.SCORED_EVERY,
                            model["vocab_size"])
    want, check = _check(got, state(names), batch, model)
    assert check["ok"], check
    assert check["logits_rel_rms"] < 5e-5 and check["loss_abs"] < 1e-5
    # the loss is its two terms, the second at lambda
    assert abs(main_loss.item() + 0.3 * mtp_loss.item() - loss.item()) < 1e-5
    assert mtp_loss.item() > 1.0
    # each head on its own half of the array, and the halves differ
    half = want.shape[1] // 2
    assert rel(logits[:, :half], want[:rows, :half]) < 5e-5
    assert rel(logits[:, half:], want[:rows, half:]) < 5e-5
    assert rel(want[:, half:], want[:, :half]) > 0.5


def test_whole_model_equals_reference_under_bf16_amp(amp_run):
    """The logits within the cell's limit. The loss here is a mean of 96
    bf16 per-token losses where the cell's is one of 4,096, so its
    rounding is sqrt(4096 / 96) = 6.5 times as coarse: held to that many
    times the cell's limit."""
    from benchmark.models.joyai_flash import TOLERANCE

    model, batch, p, got = amp_run
    _, check = _check(got, p, batch, model)
    assert 1e-4 < check["logits_rel_rms"] <= TOLERANCE["logits_rel_rms"], check
    assert check["loss_abs"] <= 6.5 * TOLERANCE["loss_abs"], check


@pytest.mark.parametrize("wrong", [{"drop_layers": 1}] + [
    {"wrong": (w,)} for w in (
        "no_rope", "half_pairs", "rope_on_nope", "no_q_norm", "scale_128",
        "no_scaling", "no_mtp", "mtp_no_norms", "mtp_own_embedding")])
def test_a_wrong_model_is_caught_by_the_cells_tolerance(wrong, amp_run):
    """The reference with its last layer left out or with one departure of
    `WRONG`, against the program in the cell's precision."""
    from benchmark.models import joyai_flash as adapter

    assert "wrong" not in wrong or wrong["wrong"][0] in adapter.WRONG
    model, batch, p, got = amp_run
    _, check = _check(got, p, batch, model, **wrong)
    assert check["ok"] is False, (wrong, check)
    assert check["logits_rel_rms"] > adapter.TOLERANCE["logits_rel_rms"]


# ------------------------------------------------ one step's gradients

KINDS = {
    "embedding": ("joyai.embed",), "head": ("joyai.head.w_0",),
    "rms_norm": (".input_norm.w_0", ".post_attn_norm.w_0", "final_norm.w_0",
                 ".hnorm.w_0", ".enorm.w_0"),
    "latent_norm": (".q_a_norm.w_0", ".kv_a_norm.w_0"),
    "latent": (".attn.q_a.w_0", ".attn.q_b.w_0", ".attn.kv_a.w_0",
               ".attn.kv_b.w_0", ".attn.o.w_0"),
    "mtp_proj": ("joyai.mtp.proj.w_0",),
    "ffn": (".gate.w_0", ".up.w_0", ".down.w_0"),
    "router": (".moe.gate",),
    "experts": (".moe.w_gate", ".moe.w_up", ".moe.w_down"),
}


def _grad_of_reference(before, batch, model, **kw):
    import jax

    with jax.default_matmul_precision("highest"):
        return f32(jax.jit(jax.grad(
            lambda p: ref.loss(p, batch, model, **kw)))(before))


def _gradients(model, traffic, place=None, seed=3):
    """{name: gradient} of the program's train step (one SGD step at rate
    1: the gradient is what the parameter lost) and of `jax.grad` of the
    reference's loss, from the same seeded state and batch."""
    import paddle_tpu as fluid
    from benchmark.models import joyai_flash as adapter
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
    return got, _grad_of_reference(before, batch, model), before, (main, batch)


def test_one_train_steps_gradients_equal_jax_grad_of_the_reference():
    from paddle_tpu import profiler

    model, traffic = cell(precision="float32", **AS_AT_WIDTH)
    c0 = profiler.counters()
    got, want, before, (main, batch) = _gradients(
        model, dict(traffic, seq_len=80))
    c1 = profiler.counters()
    check_gradients(got, want, before, 2e-4, kinds=KINDS)
    # the embedding and the head are each read twice, and the backward
    # pass sums two partial gradients into each
    sums = {op.output("Out")[0]: op.input("X")
            for op in main.global_block().ops if op.type == "sum"}
    for name in ("joyai.embed", "joyai.head.w_0"):
        assert len(sums[name + "@GRAD"]) == 2, name
    # and neither term alone gives them: the main loss's part differs
    only_main = _grad_of_reference(before, batch, model, wrong=("no_mtp",))
    for name in ("joyai.embed", "joyai.head.w_0"):
        assert rel(only_main[name], want[name]) > 0.05, name

    def bumped(name):
        return c1.get(name, 0) - c0.get(name, 0)

    # what that one train step's trace counted: six blocks (five layers
    # and the module), the forward op's lowering and the gradient op's
    # replay; the numbers the model's docstring has
    assert bumped("attn_latent_q_lora") == 12
    assert bumped("rope_interleaved") == 24
    assert bumped("attn_dispatch_xla") == 12  # the chip: attn_dispatch_flash
    assert bumped("moe_dispatch_grouped") == 10  # five expert layers
    assert (c1["mtp_depth"], c1["loss_terms"]) == (1, 2)
    assert (c1["moe_experts_held"], c1["moe_experts_total"]) == (2, 8)


# -------------------------------------------------- the expert layer


def _sigmoid_layer(r, hidden, width, total):
    p = {"m.moe.gate": r.randn(hidden, total).astype(np.float32) * 0.5,
         "m.moe.bias": r.randn(total).astype(np.float32) * 0.1}
    for w, shape in (("w_gate", (total, hidden, width)),
                     ("w_up", (total, hidden, width)),
                     ("w_down", (total, width, hidden))):
        p["m.moe." + w] = r.randn(*shape).astype(np.float32) * 0.2
    for w, shape in (("gate", (hidden, width)), ("up", (hidden, width)),
                     ("down", (width, hidden))):
        p[f"m.shared.{w}.w_0"] = r.randn(*shape).astype(np.float32) * 0.2
    return p


@pytest.mark.parametrize("total,held,k", [(8, 2, 2), (32, 4, 8)])
def test_the_shares_add_up_to_the_uncut_layer_shared_expert_once(
        total, held, k):
    """Every share's routed part, and the shared expert counted once,
    equal the reference's layer with all the experts held: four shares of
    2 of 8 with 2 a token, and eight shares of 4 of 32 with the published
    8 a token (the published 32 shares of 8 of 256 are 32 expert ops to
    compile, half a minute here). Each share's own output holds the
    shared expert, so the sum of the outputs holds it `shares` times."""
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_parts

    r = np.random.RandomState(total)
    hidden, width, shares = 16, 8, total // held
    p = _sigmoid_layer(r, hidden, width, total)
    u = r.randn(2, 24, hidden).astype(np.float32)
    x = fluid.layers.data("u", list(u.shape), append_batch_size=False)

    class Cfg:
        hidden_size, initializer_range, rms_norm_eps = hidden, 0.02, 1e-6
        num_experts, experts_held = total, held
        moe_intermediate_size, num_experts_per_token = width, k
        routed_scaling_factor, moe_renormalize = 2.5, True
        router_bias_scale, score_func, num_shared_experts = 0.1, "sigmoid", 1

    outs = []
    for lo in range(0, total, held):
        Cfg.held_from = lo
        y, load = decoder_parts.expert_ffn(x, Cfg, f"share{lo}")
        outs += [y, load]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    for lo in range(0, total, held):
        scope.set(f"share{lo}.moe.gate", p["m.moe.gate"])
        scope.set(f"share{lo}.moe.bias", p["m.moe.bias"])
        for w in ("w_gate", "w_up", "w_down"):
            scope.set(f"share{lo}.moe.{w}", p[f"m.moe.{w}"][lo:lo + held])
        for w in ("gate", "up", "down"):
            scope.set(f"share{lo}.shared.{w}.w_0", p[f"m.shared.{w}.w_0"])
    got = exe.run(feed={"u": u}, fetch_list=outs)
    parts, loads = got[0::2], got[1::2]
    assert len(parts) == shares
    assert int(np.sum(loads)) == u.shape[0] * u.shape[1] * k
    layer = {"num_experts_per_tok": k, "n_routed_experts": total,
             "held_from": 0, "norm_topk_prob": True, "n_shared_experts": 1,
             "routed_scaling_factor": 2.5}
    uncut = highest(ref.expert_ffn, p, u, "m", layer)
    shared = highest(ref._ffn, p, u, "m.shared")
    assert rel(shared, uncut) > 0.1  # the routed experts weigh in
    assert rel(sum(parts) - (shares - 1) * shared, uncut) < 1e-5
    # and one share alone is the reference's share
    p_share = dict(p, **{f"m.moe.{w}": p[f"m.moe.{w}"][held:2 * held]
                         for w in ("w_gate", "w_up", "w_down")})
    one = highest(ref.expert_ffn, p_share, u, "m",
                  dict(layer, n_routed_experts=held, held_from=held))
    assert rel(parts[1], one) < 1e-5


# --------------------------------- what the other decoders' Programs hold

# the train Programs at the rehearsal size as the parent of PR 39 builds
# them: the op types in order, hashed, and the ops a set-up traces
# (startup, the `for_test` clone, the train step: `traced_ops` on the chip)
PROGRAMS = {
    "kimi_linear_ep32_s4096": (658, "5ea25165bcb3257a", 1010),
    "trinity_mini_ep16_s8192": (571, "f8f09e607b35f074", 865),
    "mellum2_ep4_s8192": (291, "d3ac24a9cc23cc8d", 463),
}


@pytest.mark.parametrize("cell_name", sorted(PROGRAMS))
def test_the_other_expert_decoders_programs_are_op_for_op_what_they_were(
        cell_name):
    """Kimi's (its latent layer now from `decoder_parts.latent_attention`),
    Trinity's and Mellum's train Programs: the op list the parent builds,
    no rotation by pairs and no compressed query anywhere. For Kimi's, the
    one whose builder moved, the set-up is traced too: 1,010 ops, and none
    of this PR's counters bumped by their lowering (Trinity's and Mellum's
    865 and 463 are the chip's `traced_ops`, and
    `tests/test_mellum_reference.py` traces Trinity's step)."""
    import hashlib

    import paddle_tpu as fluid
    from benchmark.harness import spec
    from benchmark.runners import train_loop
    from paddle_tpu import profiler

    c = spec.cell(cell_name, rehearse=True)
    adapter = spec.plugin("models", c["config"]["adapter"])
    before = profiler.counters()
    main, startup, built, eval_prog = train_loop.build_programs(
        fluid, adapter, c["config"], c["traffic"], 3)
    ops = main.global_block().ops
    types = [op.type for op in ops]
    digest = hashlib.sha256("\n".join(types).encode()).hexdigest()[:16]
    assert (len(types), digest) == PROGRAMS[cell_name][:2], (len(types), digest)
    assert not any(op.attrs.get("interleaved") or op.attrs.get("q_lora_rank")
                   for op in ops)
    if cell_name != "kimi_linear_ep32_s4096":
        return
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    batch = adapter.make_batch(np.random.RandomState(0), c["config"],
                               c["traffic"])
    exe.run(eval_prog, feed=batch, fetch_list=built["check"])
    exe.run(main, feed=batch, fetch_list=[built["loss"]])
    after = profiler.counters()
    assert (after["program_traced_ops"] - before.get("program_traced_ops", 0)
            == PROGRAMS[cell_name][2])
    for counter in ("attn_latent_q_lora", "rope_interleaved", "mtp_depth",
                    "loss_terms"):
        assert after.get(counter, 0) == before.get(counter, 0), counter


# ----------------------------------------------- the cell's arithmetic


def test_parameters_and_flops_of_the_cell():
    from benchmark.models import joyai_flash as adapter
    from benchmark.runners import train_loop

    model, traffic = cell(rehearse=False)
    assert (traffic["batch"], traffic["seq_len"]) == (1, 4096)
    # ISSUE 39's arithmetic, redone: latent attention 26.35M a layer, the
    # dense layer's feed-forward 44.04M, an expert layer's router 0.52M,
    # shared expert 4.72M and a quarter of an expert a token
    assert adapter.latent_params(model) == 26345472
    expert = 524288 + 4718592 + 4718592 // 4
    per_token = adapter.matrix_params_per_token(model)
    assert per_token == (6 * 26345472 + 3 * 2048 * 7168 + 5 * expert
                         + 2 * 2048 * 2048 + 2 * 2048 * 16160)
    norms = 6 * (2 * 2048 + 1536 + 512) + 4 * 2048
    held = (6 * 26345472 + 3 * 2048 * 7168
            + 5 * (524288 + 256 + 9 * 4718592) + 2 * 2048 * 2048
            + 2 * 2048 * 16160 + norms)
    assert abs(held / 1e6 - 491.7) < 0.1  # the parameters held
    flops = adapter.flops_per_example(model, traffic)
    assert flops == 3.0 * 4096 * (2 * per_token + 6 * 32 * 4097 / 2 * 2 * 320)
    assert 10.5e12 < flops < 11.0e12

    # one parameter each, whatever the uses
    import paddle_tpu as fluid

    small, small_traffic = cell()
    main, _, built, _ = train_loop.build_programs(
        fluid, adapter, small, small_traffic, 3)
    names = [p.name for p in main.global_block().all_parameters()]
    assert len(names) == len(set(names)) == 6 * 9 + 3 + 5 * 8 + 3 + 4
    assert built["feeds"] == ["tokens", "labels", "labels_mtp"]
    assert len(built["loads"]) == 5 and len(built["terms"]) == 2


# ------------------------------------------------------- on the chip


def chip_readings(seeds, only=(), few=2):
    """At the published widths on the attached TPU: the cell's own check
    (program in bf16 AMP against the float32 reference) at every seed,
    and the same program against the wrong models named in `only` at
    every seed, or with none named against each wrong model and the fp8
    reference at the first `few`."""
    import paddle_tpu as fluid
    from benchmark.models import joyai_flash as adapter
    from benchmark.runners import train_loop

    model, traffic = cell(rehearse=False)
    for at, seed in enumerate(seeds):
        with fluid.program_guard(fluid.Program(), fluid.Program()), \
                fluid.unique_name.guard(), fluid.scope_guard(fluid.Scope()):
            main, startup, built, eval_prog = train_loop.build_programs(
                fluid, adapter, model, traffic, seed)
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup)
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
            half = logits.shape[1] // 2
            got = np.asarray(got_logits, np.float32).reshape(logits.shape)
            print(f"seed {seed} {label}: logits_rel_rms "
                  f"{check['logits_rel_rms']:.5f} (main "
                  f"{rel(got[:, :half], logits[:, :half]):.5f} module "
                  f"{rel(got[:, half:], logits[:, half:]):.5f}) loss_abs "
                  f"{check['loss_abs']:.5f} ok {check['ok']}", flush=True)


def held_loads(seeds, steps=54, rate=None):
    """At the published widths on the attached TPU, the cell's train step
    on the batches its runner would feed (one check batch drawn first,
    then the pool of 32), `steps` of them: the share of the 32,768
    assignments that each expert layer's 8 held experts take (the
    module's last), at the first step, the window's first (the fifth) and
    the last, and the largest over all steps; and both loss terms' fall."""
    import paddle_tpu as fluid
    from benchmark.models import joyai_flash as adapter
    from benchmark.runners import train_loop
    from paddle_tpu import profiler

    model, traffic = cell(rehearse=False)
    if rate:  # the sweep that chose the optimizer's rate
        model["optimizer"] = dict(model["optimizer"], learning_rate=rate)
    total = traffic["batch"] * traffic["seq_len"] * model["num_experts_per_tok"]
    c0 = profiler.counters()
    for seed in seeds:
        with fluid.program_guard(fluid.Program(), fluid.Program()), \
                fluid.unique_name.guard(), fluid.scope_guard(fluid.Scope()):
            main, startup, built, _ = train_loop.build_programs(
                fluid, adapter, model, traffic, seed)
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup)
            rng = np.random.RandomState(seed)
            adapter.make_batch(rng, model, traffic)  # the check's batch
            pool = [adapter.make_batch(rng, model, traffic)
                    for _ in range(traffic["pool_batches"])]
            shares, losses = [], []
            for i in range(steps):
                loss, main_loss, mtp_loss, *loads = exe.run(
                    main, feed=pool[i % len(pool)],
                    fetch_list=[built["loss"]] + built["terms"]
                    + built["loads"])
                losses.append([float(np.asarray(x).reshape(-1)[0])
                               for x in (loss, main_loss, mtp_loss)])
                shares.append([float(np.sum(x)) / total for x in loads])
        shares, losses = np.array(shares), np.array(losses)

        def row(values):
            return " ".join(f"{v:.4f}" for v in values)

        fall = np.median(losses[4:14], 0) - np.median(losses[-10:], 0)
        print(f"seed {seed} rate {model['optimizer']['learning_rate']}: a "
              f"balanced share is {8 / 256:.4f} of {total}; held share by "
              f"layer, step 0: {row(shares[0])}; step 4: {row(shares[4])}; "
              f"step {steps - 1}: {row(shares[-1])}; largest: "
              f"{row(shares.max(0))}; loss, main, module: step 0 "
              f"{row(losses[0])}; step {steps - 1} {row(losses[-1])}; fall "
              f"(median of steps 4-13 less median of the last ten) "
              f"{row(fall)}", flush=True)
    c1 = profiler.counters()
    print("counters of", len(seeds), "train steps' traces:", {
        n: c1.get(n, 0) - c0.get(n, 0) for n in (
            "attn_latent_q_lora", "rope_interleaved", "attn_dispatch_flash",
            "attn_dispatch_xla", "moe_dispatch_grouped", "moe_dispatch_gmm")},
        {n: c1.get(n) for n in ("mtp_depth", "loss_terms", "moe_experts_held",
                                "moe_experts_total", "moe_block_rows")},
        flush=True)


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
            got, want, before, _ = _gradients(
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
    seeds = [int(a) for a in sys.argv[2:]] or [39001]
    {"readings": lambda: chip_readings(
        seeds, tuple(w for w in only.split(",") if w)),
     "loads": lambda: held_loads(seeds, rate=float(rate) if rate else None),
     "gradients": chip_gradients}[what]()
