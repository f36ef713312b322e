"""JoyAI-LLM-Flash against its plain reference
(`benchmark/models/joyai_flash.py`) at the rehearsal size of the cell
`joyai_flash_ep32_s4096`: what every decoder suite holds
(`tests/decoder_suite.py`: latent attention and the expert layer alone,
the whole model on both heads' logits and the two-term loss, one train
step's gradients for every kind of parameter, that each wrong model is
caught by the cell's tolerance) on this model's data, and its own: the
compressed query and the rotation by pairs in the latent layer, the
rotation against the formula written out by hand and its gradient; the
one-head key part's gradient summed over the heads; the embedding's and
the head's gradients, each the sum of two uses; the four shares against
the uncut layer with the shared expert counted once; the cell's counters
and FLOPs.

Run as a script on the attached TPU (`tests/decoder_suite.py` has the
arguments): the readings that place `TOLERANCE`; the held load of each
expert layer and both loss terms, step by step, at a learning rate (the
sweep that chose the optimizer's rate):

    python3 tests/test_joyai_flash_reference.py readings[:wrong,wrong] [seed ...]
    python3 tests/test_joyai_flash_reference.py loads[@rate] [seed ...]
    python3 tests/test_joyai_flash_reference.py gradients
"""

from __future__ import annotations

import math

import numpy as np
import pytest

from decoder_suite import *  # noqa: F401,F403 — the shared cases, on SUITE
from decoder_suite import compiled, highest, main, rel, state

from benchmark.models import joyai_flash as adapter  # noqa: E402

CELL = "joyai_flash_ep32_s4096"

# At 64 wide, seeded as the cell is (matrices Normal(0, 0.02), the
# embedding Normal(0, 2)), a layer adds a thousandth of the residual stream
# and no wrong model shows in the logits. With the matrices at 0.1 and the
# embedding at 0.3 (Mellum's test has the same two) the layers weigh in the
# stream as at the published width (0.02 x sqrt(2048) = 0.9 a product).
AS_AT_WIDTH = {"initializer_range": 0.1, "embedding_initializer_range": 0.3}



def _mixer_program(which, model, batch, seq):
    """Latent attention or the expert layer alone in a Program: `u` in,
    `y` out."""
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_parts

    cfg = adapter.config(model)
    u = fluid.layers.data("u", [batch, seq, cfg.hidden_size],
                          append_batch_size=False)
    if which == "experts":
        return decoder_parts.expert_ffn(u, cfg, "m")[0]
    return decoder_parts.latent_attention(u, cfg, "m")


def _want_mixer(which, p, feeds, model, wrong=()):
    fn = {"latent": adapter.latent_mixer, "experts": adapter.expert_ffn}[which]
    return highest(fn, p, feeds["u"], "m", model, wrong)


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


def _two_uses(step):
    """The embedding and the head are each read twice, and the backward
    pass sums two partial gradients into each; neither term alone gives
    them; and what that one train step's trace counted."""
    import jax

    sums = {op.output("Out")[0]: op.input("X")
            for op in step.main.global_block().ops if op.type == "sum"}
    for name in ("joyai.embed", "joyai.head.w_0"):
        assert len(sums[name + "@GRAD"]) == 2, name
    only_main = compiled(jax.grad(lambda p: SUITE.loss(
        p, step.batch, step.model, wrong=("no_mtp",))), step.before)
    for name in ("joyai.embed", "joyai.head.w_0"):
        assert rel(only_main[name], step.want[name]) > 0.05, name
    # six blocks (five layers and the module), the forward op's lowering
    # and the gradient op's replay; the numbers the model's docstring has
    assert step.bumped("attn_latent_q_lora") == 12
    assert step.bumped("rope_interleaved") == 24
    assert step.bumped("attn_dispatch_xla") == 12  # the chip: attn_dispatch_flash
    assert step.bumped("moe_dispatch_grouped") == 10  # five expert layers
    assert step.bumped("short_conv_linear_calls") == 0  # no convolution
    assert (step.counters["mtp_depth"], step.counters["loss_terms"]) == (1, 2)
    assert (step.counters["moe_experts_held"],
            step.counters["moe_experts_total"]) == (2, 8)


def _by_head(got_logits, logits):
    half = logits.shape[1] // 2
    got = np.asarray(got_logits, np.float32).reshape(logits.shape)
    return (f" (main {rel(got[:, :half], logits[:, :half]):.5f} module "
            f"{rel(got[:, half:], logits[:, half:]):.5f})")


# what a latent layer alone already shows, each by a thousandth or more
LATENT_WRONG = ("no_rope", "half_pairs", "rope_on_nope", "no_q_norm",
                "scale_128")

SUITE = Suite(  # noqa: F405
    CELL, adapter, kinds=KINDS, as_at_width=AS_AT_WIDTH,
    mixers=("latent", "experts"), mixer_program=_mixer_program,
    want_mixer=_want_mixer, wrong_by_mixer={"latent": LATENT_WRONG},
    mixer_wrong_limit=1e-3,
    # the reference with its last layer left out or with one departure of
    # `WRONG`: refused by the cell's logits' limit
    wrong={"drop_layers": caught(amp=1, drop_layers=1),  # noqa: F405
           **{w: caught(amp=1, wrong=(w,))  # noqa: F405
              for w in adapter.WRONG}},
    on_gradients=_two_uses, reading_more=_by_head, seed=39001, steps=54,
    step_counters=("attn_latent_q_lora", "rope_interleaved",
                   "attn_dispatch_flash", "attn_dispatch_xla",
                   "moe_dispatch_grouped", "moe_dispatch_gmm"),
    gauges=("mtp_depth", "loss_terms", "moe_experts_held",
            "moe_experts_total", "moe_block_rows"))


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
    np.testing.assert_allclose(highest(adapter._rope, jnp.asarray(data), theta),
                               want, atol=2e-5)
    # the other pairing is another function, and the reference's wrong one
    assert rel(got_halves, want) > 0.1
    np.testing.assert_allclose(
        got_halves, highest(adapter._rope, jnp.asarray(data), theta, True),
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
    want = jax.grad(lambda t: jnp.sum(adapter._rope(t, theta) * weight))(
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
    per_head = jax.grad(lambda t: jnp.sum(adapter._rope(t, theta) * weight))(
        jnp.broadcast_to(jnp.asarray(data), (b, s, h, d)))
    np.testing.assert_allclose(got, np.sum(per_head, 2, keepdims=True),
                               atol=5e-5)


# ------------------------------------------ the latent layer's own


def test_the_latent_layer_compresses_the_query_and_turns_by_pairs():
    m = SUITE.mixer("latent")
    assert sorted(n[2:] for n in m.names) == [
        "kv_a.w_0", "kv_a_norm.w_0", "kv_b.w_0", "o.w_0", "q_a.w_0",
        "q_a_norm.w_0", "q_b.w_0"]
    assert m.bumped("attn_latent_q_lora") == 1
    assert m.bumped("rope_interleaved") == 2


def test_latent_attention_through_the_flash_kernel(monkeypatch, attn_path):
    """The blocked kernel, interpreted, at the published head widths
    (192-wide keys, 128-wide values, padded inside), forced by name since
    the CPU's dispatch never chooses it."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    attn_path("flash")
    m = SUITE.mixer("latent", batch=1, seq=160, seed=2, config=dict(
        AS_AT_WIDTH, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, num_attention_heads=2))
    assert m.bumped("attn_dispatch_flash") == 1
    assert rel(m.got, m.want()) < 2e-5


def test_kimis_latent_layer_is_built_with_both_off():
    """`decoder_parts.latent_attention` under Kimi's configuration: one
    query projection, no rotation, and no new counter."""
    import paddle_tpu as fluid
    from benchmark.harness import spec
    from benchmark.models import kimi_linear
    from paddle_tpu import profiler
    from paddle_tpu.models import decoder_parts

    model = spec.cell("kimi_linear_ep32_s4096", rehearse=True)["config"]
    model["initializer_range"] = 0.1
    cfg = kimi_linear.config(model)
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
    want = highest(kimi_linear.latent_mixer, state(names), data, "m", model)
    assert rel(got, want) < 2e-5


# ------------------------------------------------ both heads, two terms


def test_both_heads_and_the_two_loss_terms_equal_reference(float32_run):
    model, batch, p, got = float32_run
    assert sorted(batch) == ["labels", "labels_mtp", "tokens"]
    np.testing.assert_array_equal(batch["labels_mtp"][:, :-1],
                                  batch["labels"][:, 1:])
    loss, logits, main_loss, mtp_loss = (np.asarray(x) for x in got)
    rows = adapter.SCORED_SEQUENCES
    assert logits.shape == (rows, 2 * 80 // adapter.SCORED_EVERY,
                            model["vocab_size"])
    # the loss is its two terms, the second at lambda
    assert abs(main_loss.item() + 0.3 * mtp_loss.item() - loss.item()) < 1e-5
    assert mtp_loss.item() > 1.0
    # each head on its own half of the array, and the halves differ
    _, want = SUITE.reference(float32_run)
    half = want.shape[1] // 2
    assert rel(logits[:, :half], want[:, :half]) < 5e-5
    assert rel(logits[:, half:], want[:, half:]) < 5e-5
    assert rel(want[:, half:], want[:, :half]) > 0.5


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
    uncut = highest(adapter.expert_ffn, p, u, "m", layer)
    shared = highest(adapter._ffn, p, u, "m.shared")
    assert rel(shared, uncut) > 0.1  # the routed experts weigh in
    assert rel(sum(parts) - (shares - 1) * shared, uncut) < 1e-5
    # and one share alone is the reference's share
    p_share = dict(p, **{f"m.moe.{w}": p[f"m.moe.{w}"][held:2 * held]
                         for w in ("w_gate", "w_up", "w_down")})
    one = highest(adapter.expert_ffn, p_share, u, "m",
                  dict(layer, n_routed_experts=held, held_from=held))
    assert rel(parts[1], one) < 1e-5


# ----------------------------------------------- the cell's arithmetic


def test_parameters_and_flops_of_the_cell():
    from benchmark.runners import train_loop

    model, traffic = SUITE.cell(rehearse=False)
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

    small, small_traffic = SUITE.cell()
    main, _, built, _ = train_loop.build_programs(
        fluid, adapter, small, small_traffic, 3)
    names = [p.name for p in main.global_block().all_parameters()]
    assert len(names) == len(set(names)) == 6 * 9 + 3 + 5 * 8 + 3 + 4
    assert built["feeds"] == ["tokens", "labels", "labels_mtp"]
    assert len(built["loads"]) == 5 and len(built["terms"]) == 2


if __name__ == "__main__":
    main(SUITE)
