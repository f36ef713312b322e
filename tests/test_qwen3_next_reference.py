"""Qwen3-Next against its plain reference (`benchmark/models/qwen3_next.py`)
at the rehearsal size of the cell `qwen3_next_ep16_s4096`: what every
decoder suite holds (`tests/decoder_suite.py`: every mixer alone, the
whole model in float32 and under bf16 AMP, one train step's gradients for
every kind of parameter, that each wrong model is caught) on this model's
data, and its own: the Gated DeltaNet mixer through the kernel pair and
the attention layer through `qk_prep` and the flash kernel (interpreted,
heads of 128), the sixteen shares of an expert layer against the uncut
layer with the gated shared expert counted once, the gauges and counters,
and the cell's arithmetic.

Run as a script on the attached TPU, outside any timed window
(`tests/decoder_suite.py` has the arguments):

    python3 tests/test_qwen3_next_reference.py readings 1 2   # program, wrong models and fp8 reference against the reference
    python3 tests/test_qwen3_next_reference.py loads@3e-6 1 2   # held share by expert layer and the loss over the window's steps at a rate
    python3 tests/test_qwen3_next_reference.py gradients      # at the published widths on one 512-token row
"""

from __future__ import annotations

import numpy as np
import pytest

from decoder_suite import *  # noqa: F401,F403 — the shared cases, on SUITE
from decoder_suite import guards, highest, main, rel, routed_shares

from benchmark.models import qwen3_next as adapter  # noqa: E402

CELL = "qwen3_next_ep16_s4096"

# At 64 wide, seeded as the cell is (matrices Normal(0, 0.02)), a product
# gives 0.16 of its input and a mixer next to nothing of the residual
# stream, so a wrong model does not show in the logits. With the matrices
# at 0.1 the layers weigh in the stream as at the published width
# (0.02 x sqrt(2048) = 0.9 a product; 0.1 x sqrt(64) = 0.8).
AS_AT_WIDTH = {"initializer_range": 0.1}



def _mixer_program(which, model, batch, seq):
    """A mixer or an expert layer alone in a Program: `u` in, `y` out."""
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_parts

    cfg = adapter.config(model)
    u = fluid.layers.data("u", [batch, seq, cfg.hidden_size],
                          append_batch_size=False)
    if which == "delta":
        return decoder_parts.gated_delta_net(u, cfg, "m")
    if which == "attention":
        return decoder_parts.attention(u, cfg, "m", gated=True,
                                       rope_theta=cfg.rope_theta,
                                       rotary_dim=cfg.rotary_dim)
    return decoder_parts.expert_ffn(u, cfg, "m")[0]


def _want_mixer(which, p, feeds, model, wrong=()):
    fn = {"delta": adapter.delta_mixer, "attention": adapter.attention_mixer,
          "experts": adapter.expert_ffn}[which]
    return highest(fn, p, feeds["u"], "m", model, wrong)


WRONG_BY_MIXER = {
    "delta": ("key_head_mod", "one_decay", "no_conv_silu"),
    "attention": ("rope_whole_head", "no_attn_gate", "norm_after_rope"),
    "experts": ("no_shared_gate", "sigmoid_router", "no_renormalize"),
}


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

# QK-norm after the positions moves the logits by what bf16 rounding moves
# them by (with the norms' weights at their seeded 1 it is the same model:
# a rotation keeps a head's length): the float32 program catches it, the
# cell's limit cannot and is not asked to
MILD = ("norm_after_rope",)

SUITE = Suite(  # noqa: F405
    CELL, adapter, kinds=KINDS, as_at_width=AS_AT_WIDTH,
    # the norms' weights off their seeded 1, so that a zero-centred norm
    # read as a plain one, a norm left out, or QK-norm after the positions
    # shows
    moved=lambda n: n.endswith("norm.w_0"),
    mixers=("delta", "attention", "experts"), mixer_program=_mixer_program,
    want_mixer=_want_mixer, wrong_by_mixer=WRONG_BY_MIXER,
    # the reference with its last layer left out or with one departure of
    # `WRONG`: against the float32 program each reads hundreds of times
    # its limit, and against the program in the cell's precision each but
    # `MILD`'s is refused by the cell's logits' limit
    wrong={"drop_layers": caught(100, 1, drop_layers=1),  # noqa: F405
           **{w: caught(100, None if w in MILD else 1,  # noqa: F405
                        wrong=(w,)) for w in adapter.WRONG}},
    seed=51001, gradient_row=512, checkpointed="delta_recurrence",
    step_counters=("kda_dispatch_pallas", "kda_dispatch_chunked",
                   "kda_decay_per_head", "short_conv_dispatch_pallas",
                   "short_conv_dispatch_xla", "attn_dispatch_flash",
                   "attn_qk_prep_fused", "flash_bwd_fused_calls",
                   "moe_dispatch_grouped", "moe_dispatch_gmm",
                   "moe_route_softmax", "moe_shared_expert_gated"),
    gauges=("gated_delta_layers", "attention_layers", "expert_layers",
            "kda_key_group", "attn_kv_group", "attn_rotary_lanes",
            "moe_block_rows", "moe_experts_held", "moe_experts_total",
            "flash_blocks_visited", "flash_blocks_total"))


def test_every_wrong_model_belongs_to_a_mixer():
    assert sorted(sum(WRONG_BY_MIXER.values(), ())) == sorted(adapter.WRONG)


def test_layer_kinds_follow_the_published_interval():
    from paddle_tpu.models.qwen3_next import Qwen3NextConfig

    model, _ = SUITE.cell(rehearse=False)
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


# ------------------------------------------ the kernels, by name

# heads of 128 lanes, which the kernels take, at the rehearsal's other sizes
LANES_128 = dict(AS_AT_WIDTH, linear_key_head_dim=128,
                 linear_value_head_dim=128, head_dim=128)


def test_delta_mixer_through_the_kernel_pair(monkeypatch):
    """Heads of 128, two key heads under four value heads, rows of 200
    tokens (a ragged last chunk): the mixer's Program takes `gdn_fwd`
    under the interpreter and agrees with the token-a-step reference."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    m = SUITE.mixer("delta", batch=1, seq=200, seed=2, config=LANES_128)
    assert m.bumped("kda_dispatch_pallas") == 1
    assert m.bumped("kda_decay_per_head") == 1
    assert m.counters["kda_key_group"] == 2
    assert rel(m.got, m.want()) < 2e-5


def test_attention_through_qk_prep_and_the_flash_kernel(monkeypatch,
                                                        attn_path):
    """Heads of 128 lanes of which 32 turn: the blocked kernel and the
    `qk_prep` pair, interpreted, forced by name since the CPU's dispatch
    never chooses them."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    attn_path("flash")
    m = SUITE.mixer("attention", batch=1, seq=160, seed=2, config=LANES_128)
    assert m.bumped("attn_dispatch_flash") == 1
    assert m.bumped("attn_qk_prep_fused") == 1
    assert (m.counters["attn_kv_group"],
            m.counters["attn_rotary_lanes"]) == (2, 32)
    assert rel(m.got, m.want()) < 2e-5
    assert rel(m.got, m.want(("rope_whole_head",))) > 0.02


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
    def share(i):
        return fluid.layers.moe_experts(
            x, experts_total=total, experts_held=held, d_ff=width, k=k,
            held_from=i * held, score_func="softmax",
            param_attr=fluid.ParamAttr(name=f"share{i}"))

    scope = fluid.global_scope()

    def hold(i, order, lo):
        # layer `i` holds `held` experts from `lo` of the experts in `order`
        scope.set(f"share{i}.gate", p["m.moe.gate"][:, order])
        for w in ("w_gate", "w_up", "w_down"):
            scope.set(f"share{i}.{w}", p[f"m.moe.{w}"][order[lo:lo + held]])

    routed, loads = routed_shares(share, hold, {"u": u}, shares, total, held)
    assert len(routed) == shares
    assert int(np.sum(loads)) == u.shape[0] * u.shape[1] * k
    layer = {"num_experts_per_tok": k, "num_experts": total, "held_from": 0,
             "norm_topk_prob": True}
    uncut = highest(adapter.expert_ffn, p, u, "m", layer)
    routed_only = highest(adapter.expert_ffn, p, u, "m",
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
    one = highest(adapter.expert_ffn, p_share, u, "m",
                  dict(layer, num_experts=held, held_from=held,
                       shared_expert=False))
    assert rel(routed[1], one) < 1e-5


def test_the_shared_experts_gate_in_the_program():
    """`expert_ffn` with `shared_expert_gate`: one projection of width 1,
    a sigmoid and a product more than without, and the output is the
    reference's with the gate."""
    import paddle_tpu as fluid
    from paddle_tpu import profiler
    from paddle_tpu.models import decoder_parts

    model, _ = SUITE.cell(**AS_AT_WIDTH)
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
    from paddle_tpu import profiler

    # no interpreter, whatever a test file imported before this one set:
    # the convolution's 128 channels would take the kernel under it
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    model, traffic = SUITE.cell()
    before = profiler.counters()
    with guards():
        main, eval_prog, built, exe, names = SUITE.built_model(model, traffic)
        batch = SUITE.batch_for(model, traffic)
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
    from benchmark.runners import train_loop

    model, traffic = SUITE.cell(rehearse=False)
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
        m, t = SUITE.cell(rehearse=rehearse)
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


if __name__ == "__main__":
    main(SUITE)
