"""Olmo-Hybrid against its plain reference (`benchmark/models/olmo_hybrid.py`)
at the rehearsal size of the cell `olmo_hybrid_7b_vp8_longdoc`, which keeps
key heads and value heads of two widths, neither a multiple of the other's
tile (24 and 48): what every decoder suite holds (`tests/decoder_suite.py`:
every mixer alone, the whole model in float32 and under bf16 AMP, one train
step's gradients for every kind of parameter, that each wrong model is
caught) on this model's data, and its own: the Gated DeltaNet mixer
through the kernel pair at 24 and 48 lanes and at the published 96 and
192 (interpreted), the layers' kinds from the published list, the block's
output norms in the Program, the gauges and counters, and the cell's
arithmetic with the parameters of the published widths counted by shape
inference alone.

Run as a script on the attached TPU, outside any timed window
(`tests/decoder_suite.py` has the arguments):

    python3 tests/test_olmo_hybrid_reference.py readings 1 2   # program, wrong models and fp8 reference against the reference
    python3 tests/test_olmo_hybrid_reference.py falls@3e-6 1 2   # the loss over the window's steps at a rate
    python3 tests/test_olmo_hybrid_reference.py gradients      # at the published widths on one 512-token row
"""

from __future__ import annotations

import numpy as np
import pytest

from decoder_suite import *  # noqa: F401,F403 — the shared cases, on SUITE
from decoder_suite import guards, highest, main, rel

from benchmark.models import olmo_hybrid as adapter  # noqa: E402

CELL = "olmo_hybrid_7b_vp8_longdoc"

# At 64 wide, seeded as the cell is (matrices Normal(0, 0.02)), the output
# norms put every sublayer at unit scale whatever the matrices', but
# inside a mixer a product gives 0.16 of its input: beta's and the
# decay's logits sit at 0 and a wrong beta hardly shows. With the matrices
# at 0.1 the logits inside a mixer are as wide as at the published width
# (0.02 x sqrt(3840) = 1.2 a product; 0.1 x sqrt(64) = 0.8).
AS_AT_WIDTH = {"initializer_range": 0.1}


def _mixer_program(which, model, batch, seq):
    """A mixer alone in a Program: `u` in, `y` out."""
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_parts

    cfg = adapter.config(model)
    u = fluid.layers.data("u", [batch, seq, cfg.hidden_size],
                          append_batch_size=False)
    if which == "delta":
        return decoder_parts.gated_delta_net(u, cfg, "m")
    return decoder_parts.attention(u, cfg, "m", qk_norm="projection")


def _want_mixer(which, p, feeds, model, wrong=()):
    fn = {"delta": adapter.delta_mixer,
          "attention": adapter.attention_mixer}[which]
    return highest(fn, p, feeds["u"], "m", model, wrong)


# `input_norm` is the block's and shows in the whole model alone
WRONG_BY_MIXER = {
    "delta": ("bf16_solve", "beta_unscaled"),
    "attention": ("per_head_qk_norm", "rope"),
}

KINDS = {
    "embedding": ("olmohybrid.embed",),
    "head": ("olmohybrid.head.w_0",),
    "output_norm": (".post_attn_norm.w_0", ".post_ffn_norm.w_0",
                    "final_norm.w_0"),
    "W_qkvz": (".gdn.in_proj_qkvz.w_0",),
    "W_ba": (".gdn.in_proj_ba.w_0",),
    "conv_filter": (".gdn.conv.w_0",),
    "A_log": (".gdn.A_log",),
    "dt_bias": (".gdn.dt_bias",),
    "gated_norm": (".gdn.norm.w_0",),
    "W_out": (".gdn.out_proj.w_0",),
    "attention": (".attn.q.w_0", ".attn.k.w_0", ".attn.v.w_0", ".attn.o.w_0"),
    "qk_norm": (".q_norm.w_0", ".k_norm.w_0"),
    "ffn": (".mlp.gate.w_0", ".mlp.up.w_0", ".mlp.down.w_0"),
}

# the solve's products read in bf16 is what the program itself does under
# bf16 AMP on a TPU, and on the CPU (where a chunk's products are float32)
# moves the logits by less than the program's own bf16 activations do; a
# statistic a head where OLMo takes one over the projection moves them by
# 1.5% at the published widths with seeded weights (every head's mean
# square is within a few percent of the projection's: 2.25-2.31% where
# the program's own reading is 1.70-1.74, my chip runs, PR 63) and by 4%
# here with the weights moved: the float32 program refuses both, the
# cell's limit is asked for neither
MILD = ("bf16_solve", "per_head_qk_norm")

SUITE = Suite(  # noqa: F405
    CELL, adapter, kinds=KINDS, as_at_width=AS_AT_WIDTH,
    # the norms' weights off their seeded 1, so that a norm on the wrong
    # side of its sublayer, or a statistic a head where the weights
    # differ by head, shows
    moved=lambda n: n.endswith("norm.w_0"),
    mixers=("delta", "attention"), mixer_program=_mixer_program,
    want_mixer=_want_mixer, wrong_by_mixer=WRONG_BY_MIXER,
    # a bf16 read of the state is a rounding of the right mixer, 0.2% of
    # its output: the mixer's case holds the others to 2%, this to 0.05%
    mixer_wrong_limit=5e-4,
    # the reference with its last layer left out or with one departure of
    # `WRONG`: against the float32 program each reads tens to hundreds of
    # times its limit, and against the program in the cell's precision
    # each but `MILD`'s is refused by the cell's logits' limit
    wrong={"drop_layers": caught(100, 1, drop_layers=1),  # noqa: F405
           **{w: caught(20 if w == "bf16_solve" else 100,  # noqa: F405
                        None if w in MILD else 1, wrong=(w,))
              for w in adapter.WRONG}},
    # the gradients' case at matrices twice as wide again: at 0.1 a head's
    # A_log gradient is a sum over 160 tokens that cancels down to 1e-3
    # and float32 leaves 2.0e-4 of it, the case's limit to the digit; at
    # 0.2 the sum is what it is at the published width and reads 4e-5
    gradients_at={"initializer_range": 0.2},
    seed=63001, gradient_row=512, checkpointed="delta_recurrence",
    step_counters=("kda_dispatch_pallas", "kda_dispatch_chunked",
                   "kda_decay_per_head", "short_conv_dispatch_pallas",
                   "short_conv_dispatch_xla", "attn_dispatch_flash",
                   "attn_qk_prep_fused", "flash_bwd_fused_calls",
                   "rms_bwd_dispatch_pallas", "delta_rule_lanes_published",
                   "delta_rule_lanes_computed"),
    gauges=("gated_delta_layers", "attention_layers",
            "delta_rule_key_lanes", "delta_rule_value_lanes",
            "delta_rule_beta_scale", "kda_key_group", "kda_lockstep_chunks",
            "attn_kv_group", "flash_blocks_visited", "flash_blocks_total"))


def test_every_wrong_model_belongs_to_a_mixer_or_the_block():
    assert sorted(sum(WRONG_BY_MIXER.values(), ()) + ("input_norm",)) == (
        sorted(adapter.WRONG))
    assert adapter.WRONG == ("bf16_solve", "beta_unscaled",
                             "per_head_qk_norm", "input_norm", "rope")


def test_layer_kinds_are_the_published_lists():
    from paddle_tpu.models.olmo_hybrid import OlmoHybridConfig

    model, _ = SUITE.cell(rehearse=False)
    period = ["linear_attention"] * 3 + ["full_attention"]
    assert model["layer_types"] == period * 8
    assert adapter.held_layers(model) == list(enumerate(period))
    cfg = adapter.config(model)
    assert cfg.layer_kinds() == adapter.held_layers(model)
    assert (cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_num_key_heads, cfg.linear_num_value_heads) == (
        96, 192, 30, 30)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
            cfg.hidden_size, cfg.intermediate_size) == (30, 30, 128, 3840,
                                                        11008)
    assert cfg.linear_beta_scale == 2.0
    assert OlmoHybridConfig(linear_allow_neg_eigval=False
                            ).linear_beta_scale == 1.0
    # a later stage's layers are its own entries of the list
    later = adapter.config(dict(model, first_layer_held=6,
                                num_hidden_layers=3))
    assert later.layer_kinds() == [(6, "linear_attention"),
                                   (7, "full_attention"),
                                   (8, "linear_attention")]
    assert OlmoHybridConfig().layer_kinds()[-1] == (31, "full_attention")
    with pytest.raises(ValueError, match="layer_types"):
        OlmoHybridConfig(layer_types=period, num_hidden_layers=6)
    with pytest.raises(ValueError, match="key head"):
        OlmoHybridConfig(linear_num_key_heads=15)


def test_the_block_norms_outputs_and_no_input():
    """The Program's layer: the mixer reads the stream itself, every
    `rms_norm` reads a sublayer's output or the last stream (nine norms a
    period of four layers and the GDN heads' three), the attention's q
    and k are normed over all their heads' lanes before the heads are
    cut, and the attention op turns nothing and norms nothing."""
    model, traffic = SUITE.cell()
    with guards():
        main, _, _, _, names = SUITE.built_model(model, traffic)
    forward = [op for op in main.global_block().ops
               if not op.type.endswith("_grad")]
    norms = [op for op in forward if op.type == "rms_norm"]
    produced = {n: op for op in forward for n in op.output_arg_names()}

    def source(op):
        return produced[op.input("X")[0]].type

    # 3 GDN head norms (after a reshape), 2 QK-norms and 8 output norms
    # (after a product), the final norm (after the last residual add)
    assert sorted(source(op) for op in norms) == sorted(
        ["reshape2"] * 3 + ["mul"] * 10 + ["elementwise_add"])
    widths = sorted(int(np.prod(main.global_block().var(
        op.input("Scale")[0]).shape)) for op in norms)
    hidden, heads = model["hidden_size"], model["num_attention_heads"]
    assert widths == sorted(
        [model["linear_value_head_dim"]] * 3 + [hidden] * 9
        + [heads * model["head_dim"]] * 2)
    (attn,) = [op for op in forward
               if op.type == "fused_multihead_attention"]
    assert not attn.attr("rope_theta") and not attn.input("QNorm")
    deltas = [op for op in forward if op.type == "kda_attention"]
    assert len(deltas) == 3
    assert all(op.attr("beta_scale") == 2.0 and op.attr("num_heads") == 4
               and op.attr("num_key_heads", None) is None for op in deltas)
    assert sum(n.endswith("_norm.w_0") for n in names) == 8 + 2 + 1


# ------------------------------------------ the kernels, by name


@pytest.mark.parametrize("config,grid_heads", [
    (AS_AT_WIDTH, 4),
    (dict(AS_AT_WIDTH, linear_key_head_dim=96, linear_value_head_dim=192,
          linear_num_key_heads=6, linear_num_value_heads=6), 4),
], ids=["24x48", "96x192-six"])
def test_delta_mixer_through_the_kernel_pair(monkeypatch, config, grid_heads):
    """Heads of 24 and 48 lanes, and six of the published 96 and 192 (the
    second grid step's block hangs over the arrays' edge), rows of 200
    tokens (a ragged last chunk), beta in (0, 2): the mixer's Program
    takes `gdn_fwd` under the interpreter and agrees with the
    token-a-step reference."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    m = SUITE.mixer("delta", batch=1, seq=200, seed=2, config=config)
    assert m.bumped("kda_dispatch_pallas") == 1
    assert m.bumped("kda_decay_per_head") == 1
    assert m.counters["kda_key_group"] == 1
    assert m.counters["kda_lockstep_chunks"] == grid_heads
    assert rel(m.got, m.want()) < 2e-5
    assert rel(m.got, m.want(("beta_unscaled",))) > 0.02
    # the layout's waste, counted as the layer was built
    heads = m.model["linear_num_value_heads"]
    dk, dv = m.model["linear_key_head_dim"], m.model["linear_value_head_dim"]
    assert m.counters["delta_rule_lanes_published"] >= heads * dk * dv


def test_the_lanes_counters_follow_the_lowering(monkeypatch):
    """`delta_rule_lanes_computed` is the published product on the plain
    path and the layout's whole tiles and whole grid steps where the
    kernels run."""
    from paddle_tpu import profiler
    from paddle_tpu.ops.linear_attn_ops import delta_rule_lanes

    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    assert delta_rule_lanes(4096, 30, 30, 96, 192, True) == (552960, 552960)
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert delta_rule_lanes(4096, 30, 30, 96, 192, True) == (
        552960, 32 * 128 * 256)
    assert delta_rule_lanes(4096, 32, 16, 128, 128, True) == (
        32 * 128 * 128,) * 2
    before = profiler.counters()
    SUITE.mixer("delta", batch=1, seq=64, config=AS_AT_WIDTH)
    after = profiler.counters()
    assert (after["delta_rule_lanes_published"]
            - before.get("delta_rule_lanes_published", 0)) == 4 * 24 * 48
    assert (after["delta_rule_lanes_computed"]
            - before.get("delta_rule_lanes_computed", 0)) == 4 * 128 * 128


# ------------------------------------------- gauges, counters, the cell


def test_gauges_and_counters_at_the_rehearsal_size(monkeypatch):
    from paddle_tpu import profiler

    # no interpreter, whatever a test file imported before this one set
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    model, traffic = SUITE.cell()
    before = profiler.counters()
    with guards():
        main, eval_prog, built, exe, names = SUITE.built_model(model, traffic)
        batch = SUITE.batch_for(model, traffic)
        exe.run(main, feed=batch, fetch_list=[built["loss"]])
    after = profiler.counters()
    assert {n: after[n] for n in (
        "gated_delta_layers", "attention_layers", "delta_rule_key_lanes",
        "delta_rule_value_lanes", "delta_rule_beta_scale", "attn_kv_group",
        "kda_key_group")} == {
        "gated_delta_layers": 3, "attention_layers": 1,
        "delta_rule_key_lanes": 24, "delta_rule_value_lanes": 48,
        "delta_rule_beta_scale": 2, "attn_kv_group": 1, "kda_key_group": 1}

    def bumped(name):
        return after.get(name, 0) - before.get(name, 0)

    # three Gated DeltaNet layers built once; on a CPU the plain path,
    # where the layout multiplies what is published
    assert bumped("delta_rule_lanes_published") == 3 * 4 * 24 * 48
    assert bumped("delta_rule_lanes_computed") == 3 * 4 * 24 * 48
    # the forward op's lowering and the gradient op's replay
    assert bumped("kda_dispatch_chunked") == 6
    assert bumped("kda_decay_per_head") == 6
    assert bumped("kda_dispatch_pallas") == 0
    assert bumped("short_conv_dispatch_xla") == 3
    assert bumped("attn_dispatch_xla") == 2
    assert bumped("attn_qk_prep_fused") == 0
    types = [op.type for op in main.global_block().ops]
    assert types.count("short_conv1d") == 3
    assert types.count("kda_attention") == 3
    assert types.count("fused_multihead_attention") == 1
    assert types.count("rms_norm") == 14 and types.count("moe_experts") == 0
    assert "rotary_embedding" not in types
    assert built["loads"] == []


def test_parameters_and_flops_of_the_cell():
    from benchmark.runners import train_loop

    model, traffic = SUITE.cell(rehearse=False)
    assert (traffic["batch"], traffic["seq_len"]) == (1, 4096)
    assert model["reduced"] == ["num_hidden_layers", "vocab_size"]
    # ISSUE 63's arithmetic, redone
    hidden, ffn_width, vocab = 3840, 11008, 12544
    delta = hidden * (2 * 2880 + 2 * 5760) + hidden * 60 + 5760 * hidden
    attn = 4 * hidden * hidden
    ffn = 3 * hidden * ffn_width
    assert [adapter.mixer_matrix_params(model, k) for k in (
        "linear_attention", "full_attention")] == [delta, attn]
    per_token = adapter.matrix_params_per_token(model)
    assert per_token == 3 * delta + attn + 4 * ffn + hidden * vocab
    mixer = delta + 11520 * 4 + 30 + 30 + 192
    assert abs(mixer / 1e6 - 88.75) < 0.005
    assert abs((attn + 2 * hidden) / 1e6 - 58.99) < 0.005
    assert abs(ffn / 1e6 - 126.81) < 0.005
    held = (3 * mixer + attn + 2 * hidden + 4 * (ffn + 2 * hidden)
            + hidden + 2 * hidden * vocab)
    assert held == 928862196 and abs(held / 1e6 - 928.9) < 0.05
    pairs = 4096 * 4097 // 2
    flops = adapter.flops_per_example(model, traffic)
    assert flops == 3.0 * (2 * 4096 * per_token + pairs * 30 * 4 * 128
                           + 3 * 4096 * 30 * 6 * 96 * 192)
    assert 21.5e12 < flops < 22.5e12
    # attention's score maps are 0.39 of them, the recurrence 0.12
    assert abs(3 * pairs * 30 * 4 * 128 / 1e12 - 0.387) < 0.005
    assert abs(3 * 3 * 4096 * 30 * 6 * 96 * 192 / 1e12 - 0.122) < 0.001

    # the count the program reports, at the published widths by shape
    # inference alone: nothing is allocated, traced or run
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
            2 + 3 * 7 + 6 + 4 * (3 + 2) + 1)
        if want:
            assert sum(int(np.prod(p.shape)) for p in params) == want
        assert built["feeds"] == ["tokens", "labels"]


if __name__ == "__main__":
    main(SUITE)
