"""Granite 4.0-H against its plain reference
(`benchmark/models/granite_hybrid.py`) at the rehearsal size of the cell
`granite4_h_micro_vp8_longdoc` (published layers 4 to 6: a Mamba-2 layer,
the attention layer, a Mamba-2 layer; rows of one chunk and a half): what
every decoder suite holds (`tests/decoder_suite.py`: every mixer alone,
the whole model in float32 and under bf16 AMP, one train step's gradients
for every kind of parameter, the tied table's among them, that each wrong
model is caught) on this model's data, and its own: the layers' kinds from
the published list, the four multipliers in the Program, `attention`'s
`scale` argument and its default, the vocabulary's eight slices on the
tied head, `ssd_scan` at the published 64 heads on one group in chunks of
256 against the token-a-step recurrence, the gauges and counters, and the
cell's arithmetic with the parameters of the published widths counted by
shape inference alone.

Run as a script on the attached TPU, outside any timed window
(`tests/decoder_suite.py` has the arguments):

    python3 tests/test_granite_hybrid_reference.py readings 1 2   # program, wrong models and fp8 reference against the reference
    python3 tests/test_granite_hybrid_reference.py readings:fp8 3 4 5   # the fp8 reference alone, at more seeds
    python3 tests/test_granite_hybrid_reference.py falls@1.5e-4 1 2   # the loss over the window's steps at a rate
    python3 tests/test_granite_hybrid_reference.py gradients:scale_rsqrt,rope   # at the published widths on one 512-token row, then against the two wrong models the logits cannot see
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np
import pytest

from decoder_suite import *  # noqa: F401,F403 — the shared cases, on SUITE
from decoder_suite import check_gradients, guards, highest, main, rel

from benchmark.models import granite_hybrid as adapter  # noqa: E402

CELL = "granite4_h_micro_vp8_longdoc"

# At 64 wide, seeded as the cell is (matrices Normal(0, 0.02)), a product
# gives 0.16 of its input and a sublayer, times 0.22, adds next to nothing
# to a stream that starts at 12 x 0.02: a wrong model does not show in the
# logits. With the matrices at 0.1 a product is as wide as at the
# published width (0.02 x sqrt(2048) = 0.9; 0.1 x sqrt(64) = 0.8).
AS_AT_WIDTH = {"initializer_range": 0.1}


def _mixer_program(which, model, batch, seq):
    """A mixer alone in a Program: `u` in, `y` out."""
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_parts

    cfg = adapter.config(model)
    u = fluid.layers.data("u", [batch, seq, cfg.hidden_size],
                          append_batch_size=False)
    if which == "mamba":
        return decoder_parts.mamba2_mixer(u, cfg, "m")
    return decoder_parts.attention(u, cfg, "m", qk_norm=False,
                                   scale=cfg.attention_multiplier)


def _want_mixer(which, p, feeds, model, wrong=()):
    fn = {"mamba": adapter.mamba_mixer,
          "attention": adapter.attention_mixer}[which]
    return highest(fn, p, feeds["u"], "m", model, wrong)


# the three other multipliers are the block's and the model's, and show in
# the whole model alone
WRONG_BY_MIXER = {
    "mamba": ("norm_before_gate", "no_d_skip", "no_conv_bias"),
    "attention": ("scale_rsqrt", "rope"),
}
OF_THE_MODEL = ("residual_one", "embedding_unscaled", "logits_unscaled")

KINDS = {
    "table": ("granite.embed",),  # the lookup's gradient plus the head's
    "rms_norm": (".input_norm.w_0", ".post_norm.w_0", "final_norm.w_0"),
    "W_in": (".mamba.in_proj.w_0",),
    "conv_filter": (".mamba.conv.w_0",),
    "conv_bias": (".mamba.conv.b_0",),
    "A_log": (".mamba.A_log",),
    "dt_bias": (".mamba.dt_bias",),
    "D": (".mamba.D",),
    "gated_norm": (".norm.group0.w_0",),
    "W_out": (".mamba.out_proj.w_0",),
    "attention": (".attn.q.w_0", ".attn.k.w_0", ".attn.v.w_0", ".attn.o.w_0"),
    "ffn": (".mlp.fc1.w_0", ".mlp.fc2.w_0"),
}


# `attention_multiplier` is 1/64 where the usual scale is 1/8: with seeded
# weights the scores are a tenth wide and the softmax is all but uniform
# over the keys, here and at the published widths alike, so what turns q
# and k (positions) or widens the scores eightfold moves one layer's
# output a little and the logits by under the program's own bf16 reading.
# The float32 program refuses both; the cell's limit is asked for neither
# (the chip's readings: PERF.md section 4, PR 72)
MILD = ("scale_rsqrt", "rope")


def _tied(step):
    """One table and no head: the gradient compared under `table` is the
    sum of the lookup's and the head's."""
    assert not any("head" in n for n in step.want)
    assert sum(n == "granite.embed" for n in step.want) == 1


SUITE = Suite(  # noqa: F405
    CELL, adapter, kinds=KINDS, as_at_width=AS_AT_WIDTH,
    # the norms' weights off their seeded 1 and the skip's `D` off its, so
    # that a norm on the wrong side of the gate or a skip left off shows
    moved=lambda n: "norm" in n or n.endswith(".D"),
    # 80 tokens: two chunks and a half of the rehearsal's 32
    mixers=("mamba", "attention"), mixer_program=_mixer_program,
    want_mixer=_want_mixer, wrong_by_mixer=WRONG_BY_MIXER,
    # the reference with its last layer left out or with one departure of
    # `WRONG`: against the float32 program each reads a hundred times its
    # limit or more (positions twenty), and against the program in the
    # cell's precision each but `MILD`'s is refused by the cell's tolerance
    wrong={"drop_layers": caught(100, 1, drop_layers=1),  # noqa: F405
           **{w: caught(20 if w == "rope" else 100,  # noqa: F405
                        None if w in MILD else 1, wrong=(w,))
              for w in adapter.WRONG}},
    # the per-token losses are float32 (the logits are cast before the
    # op), so the cell's own limit on the loss holds at this size too
    amp_loss_room=1.0,
    on_gradients=_tied, seed=72001, gradient_row=512,
    checkpointed="ssm_recurrence",
    step_counters=("ssd_dispatch_chunked", "ssd_scan_calls",
                   "ssd_chunk_pairs", "short_conv_dispatch_pallas",
                   "short_conv_dispatch_xla", "attn_dispatch_flash",
                   "attn_qk_prep_fused", "flash_bwd_fused_calls",
                   "rms_bwd_dispatch_pallas"),
    gauges=("mamba2_layers", "attention_layers", "dense_ffn_layers",
            "ssd_chunk_len", "ssd_heads", "ssd_groups", "ssd_state_size",
            "attn_kv_group", "flash_blocks_visited", "flash_blocks_total"))


def test_every_wrong_model_belongs_to_a_mixer_or_the_model():
    assert sorted(sum(WRONG_BY_MIXER.values(), ()) + OF_THE_MODEL) == (
        sorted(adapter.WRONG))
    # each of the four multipliers has its wrong model
    assert set(adapter.WRONG) >= {"residual_one", "scale_rsqrt",
                                  "embedding_unscaled", "logits_unscaled"}


@pytest.mark.parametrize("wrong", MILD)
def test_the_attentions_gradients_tell_what_the_logits_cannot(wrong):
    """One train step's gradients against `jax.grad` of a wrong model the
    logits' limit passes: the attention layer's four matrices are most of
    their size off (on the chip at the published widths 91% and 89%:
    `gradients:scale_rsqrt,rope`), where against the right model every
    kind is within 2e-4."""
    model, traffic = SUITE.cell(precision="float32", **SUITE.gradients_at)
    with guards():
        step = SUITE.gradients(model, dict(traffic, seq_len=80),
                               **SUITE.wrong[wrong][0])
    worst = check_gradients(step.got, step.want, step.before, np.inf,
                            kinds=KINDS)
    assert worst["attention"] > 0.5, worst


def test_layer_kinds_are_the_published_list():
    from paddle_tpu.models.granite_hybrid import GraniteHybridConfig

    model, _ = SUITE.cell(rehearse=False)
    period = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert model["layer_types"] == period * 4
    assert adapter.held_layers(model) == list(enumerate(period))
    cfg = adapter.config(model)
    assert cfg.layer_kinds() == adapter.held_layers(model)
    assert (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.mamba_n_groups,
            cfg.ssm_state_size, cfg.mamba_conv_kernel,
            cfg.mamba_chunk_size) == (64, 64, 1, 128, 4, 256)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
            cfg.hidden_size, cfg.intermediate_size) == (32, 8, 64, 2048, 8192)
    assert (cfg.attention_multiplier, cfg.embedding_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) == (
        0.015625, 12, 0.22, 8)
    assert cfg.mamba_num_heads * cfg.mamba_head_dim == (
        model["mamba_expand"] * cfg.hidden_size)
    # the rehearsal holds a later run of the list: its own entries
    tiny, _ = SUITE.cell()
    assert adapter.held_layers(tiny) == [(4, "mamba"), (5, "attention"),
                                         (6, "mamba")]
    # the list is the configuration's to give: the class has none of its
    # own, and holds the run of it that it is asked for
    assert GraniteHybridConfig(period * 4).layer_types == period * 4
    assert GraniteHybridConfig(period * 4, first_layer=30,
                               num_hidden_layers=10
                               ).layer_kinds()[5] == (35, "attention")
    with pytest.raises(TypeError, match="layer_types"):
        GraniteHybridConfig()
    with pytest.raises(ValueError, match="layer_types"):
        GraniteHybridConfig(period, num_hidden_layers=12)
    with pytest.raises(ValueError, match="groups do not divide"):
        GraniteHybridConfig(period * 4, mamba_n_heads=12, mamba_n_groups=8)


def test_the_four_multipliers_are_in_the_program():
    """The Program's layer: every sublayer goes through a `scale` op of
    0.22 before its add, the embedding through one of 12, the head is one
    `matmul` on the embedding's own table with `alpha` 1/8, and the
    attention op's `sm_scale` is `attention_multiplier`; no positions, no
    QK-norm, no head of its own."""
    model, traffic = SUITE.cell()
    with guards():
        main, _, _, _, names = SUITE.built_model(model, traffic)
    block = main.global_block()
    forward = [op for op in block.ops if not op.type.endswith("_grad")
               and op.attr("op_role", 0) == 0]
    produced = {n: op for op in forward for n in op.output_arg_names()}
    scales = [op for op in forward if op.type == "scale"]
    assert sorted(op.attr("scale") for op in scales) == sorted(
        [model["residual_multiplier"]] * 6 + [model["embedding_multiplier"]])
    (emb,) = [op for op in scales if op.attr("scale") == 12]
    assert produced[emb.input("X")[0]].type == "lookup_table"
    adds = [op for op in forward if op.type == "elementwise_add"
            and produced.get(op.input("Y")[0]) in scales]
    assert len(adds) == 6  # two a layer, each of a scaled sublayer
    (head,) = [op for op in forward if op.type == "matmul"]
    assert head.input("Y") == ["granite.embed"]
    assert head.attr("transpose_Y") and head.attr("alpha") == 1 / 8
    assert produced[head.input("X")[0]].type == "rms_norm"
    (attn,) = [op for op in forward
               if op.type == "fused_multihead_attention"]
    assert attn.attr("sm_scale") == model["attention_multiplier"] == 0.03125
    assert not attn.attr("rope_theta") and not attn.input("QNorm")
    assert "rotary_embedding" not in [op.type for op in forward]
    assert not [n for n in names if "head" in n]
    # seven norms of the stream and a gated norm a Mamba-2 layer
    assert sum(op.type == "rms_norm" for op in forward) == 2 * 3 + 1 + 2


@pytest.mark.parametrize("scale,want", [(None, 16 ** -0.5), (0.03125, 0.03125)])
def test_attentions_scale_defaults_to_the_heads_root(scale, want):
    """`decoder_parts.attention(scale=None)` is what it was: the scores
    times `head_dim ** -0.5`; a number is taken as it is."""
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_parts

    cfg = SimpleNamespace(hidden_size=32, initializer_range=0.1,
                          rms_norm_eps=1e-5, num_attention_heads=4,
                          num_key_value_heads=2, head_dim=16)
    more = {} if scale is None else {"scale": scale}
    with guards():
        u = fluid.layers.data("u", [1, 8, 32], append_batch_size=False)
        decoder_parts.attention(u, cfg, "m", qk_norm=False, **more)
        (attn,) = [op for op in fluid.default_main_program().global_block().ops
                   if op.type == "fused_multihead_attention"]
    assert attn.attr("sm_scale") == want


def test_the_vocabularys_slices_give_the_whole_logits_columns():
    """The final norm and the tied head on one residual stream: eight
    slices' logits (each `matmul` on its slice of the table, transposed,
    over 8) side by side are the whole table's."""
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_parts

    hidden, vocab, slices = 32, 64, 8
    r = np.random.RandomState(9)
    w = r.uniform(0.5, 1.5, hidden).astype(np.float32)
    table = (r.randn(vocab, hidden) * 0.3).astype(np.float32)
    xs = r.randn(2, 12, hidden).astype(np.float32)
    cfg = SimpleNamespace(hidden_size=hidden, initializer_range=0.1,
                          rms_norm_eps=1e-5)
    per = vocab // slices
    with guards():
        x = fluid.layers.data("x", list(xs.shape), append_batch_size=False)
        outs = [fluid.layers.matmul(
            decoder_parts.norm(x, f"slice{i}.final_norm", cfg),
            fluid.layers.create_parameter(
                [per, hidden], "float32",
                attr=decoder_parts.attr(f"slice{i}.embed", cfg)),
            transpose_y=True, alpha=1 / 8) for i in range(slices)]
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        scope = fluid.global_scope()
        for i in range(slices):
            scope.set(f"slice{i}.final_norm.w_0", w)
            scope.set(f"slice{i}.embed", table[i * per:(i + 1) * per])
        got = np.concatenate(exe.run(feed={"x": xs}, fetch_list=outs), -1)
    whole = highest(lambda xs, w, t: adapter._rms(xs, w, 1e-5) @ t.T / 8,
                    xs, w, table)
    assert got.shape == (2, 12, vocab) and np.abs(whole).max() > 1e-2
    assert rel(got, whole) < 1e-5
    # a slice with its neighbour's rows is another model
    assert rel(np.roll(got, per, -1), whole) > 0.5


# --------------------------- the op at the mixer's published head count


def test_ssd_scan_at_64_heads_on_one_group_in_chunks_of_256():
    """The published 64 heads reading one group's B and C, chunks of 256,
    on a row of 300 tokens (a whole chunk and 44 tokens of a second,
    filled up with steps of 0): the op and its six gradients against the
    recurrence a token a step. Heads of 8 on a state of 16: the chunk's
    `[64, 256, 256]` maps are the published ones, the lanes are not."""
    import jax.numpy as jnp
    from kernel_cases import value_and_grads
    from test_ssd_scan import NAMES, operands, recurrence

    from paddle_tpu.ops import ssm_ops

    b, s, heads, p, n, groups, chunk = 1, 300, 64, 8, 16, 1, 256
    # steps as the cell seeds them (0.001 to 0.1): at the steps of up to 1
    # that `tests/test_ssd_scan.py` draws, a chunk of 256 tokens sums
    # exponents of thousands and float32 leaves 1e-5 of the output
    args = operands(b, s, heads, p, n, groups, seed=72, step=(-6.9, -2.25))
    w = jnp.asarray(np.random.RandomState(1).randn(*args[0].shape),
                    jnp.float32)
    (got, grads), (want, grads_want) = (
        value_and_grads(fn, args, w) for fn in (
            lambda *t: ssm_ops.ssd_scan(*t, groups, chunk),
            lambda *t: recurrence(*t, groups=groups)))
    assert ssm_ops.ssd_n_chunks(s, chunk) == 2
    scale = max(float(jnp.abs(want).max()), 1.0)
    assert float(jnp.abs(got - want).max()) < 5e-6 * scale
    assert len(grads) == len(NAMES) == 6
    for name, g, g_want in zip(NAMES, grads, grads_want):
        assert g.shape == g_want.shape, name
        scale = max(float(jnp.abs(g_want).max()), 1.0)
        assert float(jnp.abs(g - g_want).max()) < 2e-5 * scale, name
    # B's and C's gradients are sums over the 64 heads that read them
    assert grads[3].shape == (b, s, n) and float(jnp.abs(grads[3]).max()) > 0


def test_the_ops_counters_read_what_the_shapes_say():
    """`ssd_scan_calls` and `ssd_chunk_pairs`, once a lowering: batch x
    heads x padded tokens x chunk length."""
    import jax

    from paddle_tpu import profiler
    from paddle_tpu.ops import ssm_ops

    for (b, s, heads, chunk), pairs in (
            ((1, 4096, 64, 256), 64 * 4096 * 256),  # the cell's layer
            ((1, 4096, 16, 128), 16 * 4096 * 128),  # Nemotron's
            ((2, 300, 64, 256), 2 * 64 * 512 * 256),  # filled up
            ((2, 48, 8, 32), 2 * 8 * 64 * 32),  # the rehearsal's
            ((3, 5, 2, 128), 3 * 2 * 5 * 5)):  # shorter than a chunk
        before = profiler.counters()
        ssm_ops._ssd_count(jax.ShapeDtypeStruct((b, s, heads * 4), "float32"),
                           heads, chunk)
        after = profiler.counters()
        assert (after["ssd_scan_calls"]
                - before.get("ssd_scan_calls", 0)) == 1
        assert (after["ssd_chunk_pairs"]
                - before.get("ssd_chunk_pairs", 0)) == pairs


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
        "mamba2_layers", "attention_layers", "dense_ffn_layers",
        "attn_kv_group", "ssd_chunk_len", "ssd_heads", "ssd_groups",
        "ssd_state_size")} == {
        "mamba2_layers": 2, "attention_layers": 1, "dense_ffn_layers": 3,
        "attn_kv_group": 2, "ssd_chunk_len": 32, "ssd_heads": 8,
        "ssd_groups": 1, "ssd_state_size": 16}

    def bumped(name):
        return after.get(name, 0) - before.get(name, 0)

    # two Mamba-2 layers: the forward op's lowering and the gradient op's,
    # each on rows of 48 tokens filled up to two chunks of 32
    assert bumped("ssd_dispatch_chunked") == 2
    assert bumped("ssd_scan_calls") == 4
    assert bumped("ssd_chunk_pairs") == 4 * (
        traffic["batch"] * model["mamba_n_heads"] * 64 * 32)
    assert bumped("short_conv_dispatch_xla") == 2
    assert bumped("attn_dispatch_xla") == 2
    assert bumped("attn_qk_prep_fused") == 0  # no norm, no positions
    types = [op.type for op in main.global_block().ops]
    assert types.count("short_conv1d") == 2
    assert types.count("ssd_scan") == types.count("ssd_scan_grad") == 2
    assert types.count("fused_multihead_attention") == 1
    assert types.count("moe_experts") == 0
    assert "rotary_embedding" not in types
    scans = [op for op in main.global_block().ops if op.type == "ssd_scan"]
    assert all(op.attr("n_groups") == 1 and op.attr("chunk_size") == 32
               for op in scans)
    assert built["loads"] == []


def test_parameters_and_flops_of_the_cell():
    from benchmark.runners import train_loop

    model, traffic = SUITE.cell(rehearse=False)
    assert (traffic["batch"], traffic["seq_len"]) == (1, 4096)
    assert model["reduced"] == ["num_hidden_layers", "vocab_size"]
    # ISSUE 72's arithmetic, redone
    hidden, width, vocab = 2048, 8192, 12544
    w_in, w_out = hidden * (4096 + 4352 + 64), 4096 * hidden
    assert (w_in, w_out) == (17432576, 8388608)
    mamba = w_in + w_out + 4352 * 4 + 4352 + 3 * 64 + 4096
    attn = 2 * hidden * hidden + 2 * hidden * 512
    ffn = hidden * 2 * width + width * hidden
    assert (mamba, attn, ffn) == (25847232, 10485760, 50331648)
    assert [adapter.mixer_matrix_params(model, k) for k in (
        "mamba", "attention")] == [w_in + w_out, attn]
    per_token = adapter.matrix_params_per_token(model)
    assert per_token == 9 * (w_in + w_out) + attn + 10 * ffn + hidden * vocab
    held = (9 * mamba + attn + 10 * (ffn + 2 * hidden) + hidden
            + vocab * hidden)
    assert held == 772160448
    pairs = 4096 * 4097 // 2
    scan = adapter.ssd_flops_per_layer(model, 4096)
    # 16 chunks of 256: C B^T once, then a head's three products
    assert scan == 16 * (2 * 256 * 256 * 128 + 64 * (
        2 * 256 * 256 * 64 + 2 * 2 * 256 * 128 * 64))
    flops = adapter.flops_per_example(model, traffic)
    assert flops == 3.0 * (2 * 4096 * per_token + pairs * 32 * 4 * 64
                           + 9 * scan)
    assert 19.6e12 < flops < 19.7e12
    # the scan's products are 0.47 of them, the attention's maps 0.21
    assert abs(3 * 9 * scan / 1e12 - 0.471) < 0.001
    assert abs(3 * pairs * 32 * 4 * 64 / 1e12 - 0.206) < 0.001
    # a row that is no whole number of chunks is counted filled up
    assert adapter.ssd_flops_per_layer(model, 300) == scan // 8

    # the count the program reports, at the published widths by shape
    # inference alone: nothing is allocated, traced or run
    import paddle_tpu as fluid

    for rehearse, want in ((True, None), (False, held)):
        m, t = SUITE.cell(rehearse=rehearse)
        kinds = [k for _, k in adapter.held_layers(m)]
        with fluid.program_guard(fluid.Program(), fluid.Program()), \
                fluid.unique_name.guard():
            main, _, built, _ = train_loop.build_programs(
                fluid, adapter, m, t, 3)
            params = main.global_block().all_parameters()
        names = [p.name for p in params]
        assert len(names) == len(set(names)) == (
            1 + 1 + len(kinds) * (2 + 2) + kinds.count("mamba") * 8
            + kinds.count("attention") * 4)
        if want:
            assert sum(int(np.prod(p.shape)) for p in params) == want
        assert built["feeds"] == ["tokens", "labels"]


if __name__ == "__main__":
    main(SUITE)
