"""LFM2 against its plain reference (`benchmark/models/lfm2.py`) at the
rehearsal size of the cell `lfm2_24b_ep8_longdoc`: what every decoder suite
holds (`tests/decoder_suite.py`: every mixer alone, the whole model in
float32 and under bf16 AMP, one train step's gradients for every kind of
parameter, that each lowering to bf16 and each wrong model is caught) on
this model's data, and its own: what the convolution sees, the attention
layer through the flash kernel over grouped heads, the mild wrong models
under AMP, the eight shares of an expert layer against the uncut layer,
the gauges and counters, the cell's arithmetic.

Run as a script on the attached TPU, outside any timed window
(`tests/decoder_suite.py` has the arguments):

    python3 tests/test_lfm2_reference.py readings 1 2   # program, wrong models and fp8 reference against the reference
    python3 tests/test_lfm2_reference.py loads@3e-6 1 2   # held share by expert layer and the loss over the window's steps at a rate
    python3 tests/test_lfm2_reference.py gradients      # at the published widths on one 1,024-token row
"""

from __future__ import annotations

import numpy as np
import pytest

from decoder_suite import *  # noqa: F401,F403 — the shared cases, on SUITE
from decoder_suite import highest, main, rel

from benchmark.models import lfm2 as adapter  # noqa: E402

CELL = "lfm2_24b_ep8_longdoc"

# At 64 wide, seeded as the cell is (matrices Normal(0, 0.02)), a product
# gives 0.16 of its input and a mixer next to nothing of the residual
# stream, so neither a wrong model nor a lowering to bf16 shows in the
# logits. With the matrices at 0.1 the layers weigh in the stream as at the
# published width (0.02 x sqrt(2048) = 0.9 a product; 0.1 x sqrt(64) = 0.8).
# The router's correction at the other configurations' 0.1, so that a
# wrong use of it shows (the cell seeds it zeros, as the published code
# does, which keeps a chip's load steady: nothing a test here needs).
AS_AT_WIDTH = {"initializer_range": 0.1, "router_bias_scale": 0.1}



def _mixer_program(which, model, batch, seq):
    """A mixer or a feed-forward alone in a Program: `u` in, `y` out."""
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_parts

    cfg = adapter.config(model)
    u = fluid.layers.data("u", [batch, seq, cfg.hidden_size],
                          append_batch_size=False)
    if which == "conv":
        return decoder_parts.gated_short_conv(u, cfg, "m")
    if which == "attention":
        return decoder_parts.attention(u, cfg, "m", rope_theta=cfg.rope_theta)
    if which == "dense":
        return decoder_parts.ffn(u, cfg.intermediate_size, "m.mlp", cfg)
    return decoder_parts.expert_ffn(u, cfg, "m", cfg.router_norm_eps)[0]


def _want_mixer(which, p, feeds, model, wrong=()):
    if which == "dense":
        return highest(adapter._ffn, p, feeds["u"], "m.mlp")
    fn = {"conv": adapter.conv_mixer, "attention": adapter.attention_mixer,
          "experts": adapter.expert_ffn}[which]
    return highest(fn, p, feeds["u"], "m", model, wrong)


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

# QK-norm after the positions (with the norms' weights off their seeded 1:
# at 1 the two orders are one model, a rotation keeps a head's length) and
# the router's correction inside the weights move the logits by 1 to 5%
# here and by 0.2 to 0.5 points on the chip (PERF.md section 6, PR 47),
# which is what bf16 rounding moves them by: the cell's tolerance has to
# sit above the program's own 2.3 to 2.6% and cannot tell them from it.
# The float32 program can: each reads over a hundred times its limit.
MILD = ("norm_after_rope", "bias_in_weights")


def _caught(w):
    """The convolution's taps, the router, the softmax or the norms'
    statistics in bf16: each moves the logits by several times the float32
    program's limit, which is the tolerance that catches it here (on the
    chip the adapter's `TOLERANCE` comment says which of them its limits
    still catch). The SiLU left on the convolution, its gates swapped, no
    QK-norm, the group mapped `n % g`: refused by the cell's logits' limit
    with as much room again."""
    if w.endswith("_bf16"):
        return caught(float32=4, wrong=(w,))  # noqa: F405
    if w in MILD:
        return caught(float32=100, wrong=(w,))  # noqa: F405
    return caught(amp=2, wrong=(w,))  # noqa: F405


SUITE = Suite(  # noqa: F405
    CELL, adapter, kinds=KINDS, as_at_width=AS_AT_WIDTH,
    # the QK-norm weights, moved off their seeded 1
    moved=lambda n: n.endswith(("q_norm.w_0", "k_norm.w_0")),
    mixers=("conv", "attention", "dense", "experts"),
    mixer_program=_mixer_program, want_mixer=_want_mixer,
    wrong_by_mixer={"conv": ("conv_silu", "gates_swapped"),
                    "attention": ("norm_after_rope", "no_qk_norm",
                                  "group_mod"),
                    "experts": ("bias_in_weights",)},
    wrong={"drop_layers": caught(amp=2, drop_layers=1),  # noqa: F405
           **{w: _caught(w) for w in adapter.WRONG}},
    # the cell's loss is a mean of 8,192 losses: sqrt(8192 / 96) = 9.2
    amp_loss_room=9.2,
    # at 64 wide a correction of 0.1 would pick the same two experts for
    # every token, none of them held in some layer (Trinity's suite)
    gradients_at=dict(AS_AT_WIDTH, router_bias_scale=0.02),
    seed=47001,
    step_counters=("short_conv_linear_calls", "short_conv_dispatch_pallas",
                   "short_conv_dispatch_xla", "attn_dispatch_flash",
                   "attn_qk_prep_fused", "flash_fwd_wide_key_calls",
                   "moe_dispatch_grouped", "moe_dispatch_gmm"),
    gauges=("gated_conv_layers", "attention_layers", "expert_layers",
            "attn_kv_group", "moe_block_rows", "flash_blocks_visited",
            "flash_blocks_total"))


def test_layer_kinds_follow_the_published_list():
    from paddle_tpu.models.lfm2 import PUBLISHED_LAYER_TYPES, Lfm2Config

    model, _ = SUITE.cell(rehearse=False)
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


# ------------------------------------------ the mixers' own


def test_the_convolution_starts_from_zero_and_sees_three_tokens():
    """A change to token t moves the mixer's output at t, t + 1 and t + 2
    and nowhere else; the first token's output is the last tap's alone."""
    m = SUITE.mixer("conv", batch=1, seq=16, seed=2)
    h, u, base, p = m.model["hidden_size"], m.feeds["u"], m.got, m.p
    moved = u.copy()
    moved[0, 5] += 1.0
    (got,) = m.exe.run(feed={"u": moved}, fetch_list=[m.y])
    hit = np.flatnonzero(np.abs(got - base)[0].max(axis=1) > 1e-7)
    assert hit.tolist() == [5, 6, 7]
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
    m = SUITE.mixer("attention", batch=1, seq=160, seed=2)
    assert m.bumped("attn_dispatch_flash") == 1
    assert m.bumped("attn_qk_prep_fused") == 0
    assert m.counters["attn_kv_group"] == 2
    assert rel(m.got, m.want()) < 2e-5


@pytest.mark.parametrize("wrong", MILD)
def test_a_mild_wrong_model_reads_above_the_right_one_under_amp(wrong,
                                                                amp_run):
    """What the float32 program catches by a hundred times its limit
    (`MILD`) reads, against the bf16 program, above the right reference,
    and inside the cell's tolerance."""
    assert (SUITE.check(amp_run, wrong=(wrong,))["logits_rel_rms"]
            > 1.2 * SUITE.check(amp_run)["logits_rel_rms"])


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
    uncut = highest(adapter.expert_ffn, p, u, "m", layer)
    assert rel(sum(routed), uncut) < 1e-5
    # and one share alone is the reference's share
    p_share = dict(p, **{f"m.moe.{w}": p[f"m.moe.{w}"][held:2 * held]
                         for w in ("w_gate", "w_up", "w_down")})
    one = highest(adapter.expert_ffn, p_share, u, "m",
                  dict(layer, num_experts=held, held_from=held))
    assert rel(routed[1], one) < 1e-5


# ------------------------------------------- gauges, counters, the cell


def test_gauges_and_counters_at_the_rehearsal_size():
    from paddle_tpu import profiler

    model, traffic = SUITE.cell()
    before = profiler.counters()
    main, eval_prog, built, exe, names = SUITE.built_model(model, traffic)
    batch = SUITE.batch_for(model, traffic)
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
    from benchmark.runners import train_loop

    model, traffic = SUITE.cell(rehearse=False)
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
        m, t = SUITE.cell(rehearse=rehearse)
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


if __name__ == "__main__":
    main(SUITE)
