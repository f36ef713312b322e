"""Phi-4-mini-flash against its plain reference
(`benchmark/models/phi4_flash.py`) at the rehearsal size of the cell
`phi4_mini_flash_vp8_longdoc`: what every decoder suite holds
(`tests/decoder_suite.py`: every mixer alone, the whole model in float32
and under bf16 AMP, one train step's gradients for every kind of
parameter, that each wrong lowering and each wrong model is caught) on
this model's data, and its own: differential attention through the flash
kernel with values twice as wide as its keys, the scan output and the keys
and values that two layers read and the gradients they get from both, the
gauges and counters, and the cell's arithmetic.

Run as a script on the attached TPU, outside any timed window
(`tests/decoder_suite.py` has the arguments):

    python3 tests/test_phi4_flash_reference.py readings 1 2   # program, wrong models and fp8 reference against the reference
    python3 tests/test_phi4_flash_reference.py falls@3e-6 1 2   # the loss over the window's steps at a rate
    python3 tests/test_phi4_flash_reference.py gradients      # at the published widths on one 1,024-token row
"""

from __future__ import annotations

import numpy as np
import pytest

from decoder_suite import *  # noqa: F401,F403 — the shared cases, on SUITE
from decoder_suite import compiled, highest, main, rel

from benchmark.models import phi4_flash as adapter  # noqa: E402

CELL = "phi4_mini_flash_vp8_longdoc"

# At 64 wide, seeded as the cell is (matrices Normal(0, 0.02)), a mixer adds
# a hundredth of the residual stream, the scan's state next to nothing of
# the mixer, and neither a wrong model nor a lowering to bf16 shows in the
# logits. With the matrices at 0.1 the layers weigh in the stream as at the
# published width (0.02 x sqrt(2560) = 1.0 a product; 0.1 x sqrt(64) = 0.8).
AS_AT_WIDTH = {"initializer_range": 0.1}



def _mixer_program(which, model, batch, seq):
    """One mixer or the feed-forward alone in a Program: `u` in, `y` out
    (for "gmu" also `m`, the memory; for "cross" `k` and `v`)."""
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_parts, phi4_flash as zoo

    cfg = adapter.config(model)
    L = fluid.layers
    u = L.data("u", [batch, seq, cfg.hidden_size], append_batch_size=False)
    if which == "mamba":
        y, _ = zoo.mamba(u, cfg, "m")
    elif which == "scan_output":
        _, y = zoo.mamba(u, cfg, "m")
    elif which == "gmu":
        m = L.data("m", [batch, seq, cfg.d_inner], append_batch_size=False)
        y = zoo.gmu(u, m, cfg, "m")
    elif which == "ffn":
        y = decoder_parts.fused_ffn(u, cfg.intermediate_size, "m.mlp", cfg)
    else:
        kv = None
        if which == "cross":
            g, d = cfg.num_key_value_heads, cfg.head_dim
            k = L.data("k", [batch, seq, g, d], append_batch_size=False)
            v = L.data("v", [batch, seq, g, d], append_batch_size=False)
            first, second = decoder_parts._by_pairs(
                L.reshape(k, [batch, seq, g * d]), batch, seq, g // 2, d)
            kv = (first, second, L.reshape(v, [batch, seq, g // 2, 2 * d]))
        y, _ = decoder_parts.differential_attention(
            u, cfg, "m", window=cfg.sliding_window if which == "window" else 0,
            kv=kv, lam0=cfg.lambda_init(15))
    return y


def _mixer_feeds(which, model, batch, seq, seed):
    r = np.random.RandomState(seed)
    di, _, _, d = adapter.sizes(model)
    g = model["num_key_value_heads"]
    feeds = {"u": r.randn(batch, seq, model["hidden_size"])}
    if which == "gmu":
        feeds["m"] = r.randn(batch, seq, di)
    if which == "cross":
        feeds["k"] = r.randn(batch, seq, g, d)
        feeds["v"] = r.randn(batch, seq, g, d)
    return {n: v.astype(np.float32) for n, v in feeds.items()}


def _want_mixer(which, p, feeds, model, wrong=()):
    u = feeds["u"]
    if which in ("mamba", "scan_output"):
        return highest(adapter.mamba_mixer, p, u, "m", model)[
            which == "scan_output"]
    if which == "gmu":
        return highest(adapter.gmu_mixer, p, u, feeds["m"], "m")
    if which == "ffn":
        return highest(adapter._ffn, p, u, "m.mlp")
    lam0 = 0.8 - 0.6 * np.exp(-0.3 * 15)
    window = model["sliding_window"] if which == "window" else 0
    kv = (feeds["k"], feeds["v"]) if which == "cross" else None
    return highest(adapter.differential_mixer, p, u, "m", model, window,
                   lam0, kv)[0]


KINDS = {
    "embedding_and_head": ("phi4.embed",),
    "layer_norm": (".norm1.w_0", ".norm1.b_0", ".norm2.w_0", ".norm2.b_0",
                   "final_norm.w_0", "final_norm.b_0"),
    "ffn": (".mlp.fc1.w_0", ".mlp.fc2.w_0"),
    "mamba_projections": (".mamba.in_proj.w_0", ".mamba.x_proj.w_0",
                          ".mamba.dt_proj.w_0", ".mamba.dt_proj.b_0",
                          ".mamba.out_proj.w_0"),
    "mamba_conv": (".mamba.conv.w_0", ".mamba.conv.b_0"),
    "mamba_scan": (".mamba.A_log", ".mamba.D"),
    "gmu": (".gmu.in_proj.w_0", ".gmu.out_proj.w_0"),
    "attention": (".attn.qkv.w_0", ".attn.qkv.b_0", ".attn.q.w_0",
                  ".attn.q.b_0", ".attn.o.w_0", ".attn.o.b_0"),
    "lambdas": (".lambda_q1", ".lambda_k1", ".lambda_q2", ".lambda_k2"),
    "sub_norm": (".subln.w_0",),
}


def _two_readers(step):
    """Layer 16's scan output is read by its own gate and by layer 18's
    memory unit, layer 17's keys and values by its own maps and by layer
    19's: a gradient lost on either path shows in the Mamba projections of
    layer 16 and in layer 17's `qkv`, tens of percent off."""
    for n in ("phi4.layer16.mamba.in_proj.w_0", "phi4.layer16.mamba.x_proj.w_0",
              "phi4.layer17.attn.qkv.w_0", "phi4.layer17.attn.qkv.b_0"):
        assert rel(step.got[n], step.want[n]) < 2e-4, n


SUITE = Suite(  # noqa: F405
    CELL, adapter, kinds=KINDS, as_at_width=AS_AT_WIDTH,
    mixers=("mamba", "scan_output", "gmu", "ffn", "window", "full", "cross"),
    mixer_program=_mixer_program, want_mixer=_want_mixer,
    mixer_feeds=_mixer_feeds,
    wrong={
        # the reference with its last layer left out or with one departure
        # of `WRONG`: refused by the cell's tolerance. The scan's state,
        # the softmaxes or the sub-norm in bf16: each moves the logits by
        # several times the float32 program's limit (the scan 7 times, the
        # others far more), which is the tolerance that catches it here
        # (on the chip, where the program's matrices are bf16, the
        # adapter's `TOLERANCE` comment says which of them its limits
        # still catch)
        "drop_layers": caught(amp=0, drop_layers=1),  # noqa: F405
        **{w: caught(float32=4, wrong=(w,)) if w.endswith("_bf16")  # noqa: F405
           else caught(amp=0, wrong=(w,)) for w in adapter.WRONG}},  # noqa: F405
    on_gradients=_two_readers, seed=44001, chip_routed=(None, None),
    step_counters=("ssm_dispatch_pallas", "ssm_dispatch_chunked",
                   "attn_dispatch_flash", "attn_dispatch_flash_window",
                   "flash_wide_value_calls", "flash_narrow_value_calls",
                   "flash_fwd_wide_key_calls", "attn_dispatch_xla"),
    gauges=("ssm_state_size", "ssm_chunk_len", "diff_attn_layers",
            "shared_kv_layers", "gmu_layers", "attn_kv_group",
            "flash_blocks_visited", "flash_blocks_total"))


def test_layer_kinds_follow_the_published_index():
    model, _ = SUITE.cell(rehearse=False)
    assert adapter.held_layers(model) == [
        (14, "mamba"), (15, "window"), (16, "mamba"), (17, "full"),
        (18, "gmu"), (19, "cross")]
    whole = dict(model, first_layer_held=0, num_hidden_layers=32)
    kinds = [k for _, k in adapter.held_layers(whole)]
    assert {k: kinds.count(k) for k in set(kinds)} == {
        "mamba": 9, "gmu": 7, "window": 8, "full": 1, "cross": 7}
    cfg = adapter.config(model)
    assert [cfg.layer_kind(l) for l in range(32)] == kinds
    assert (cfg.d_inner, cfg.d_state, cfg.dt_rank, cfg.head_dim) == (
        5120, 16, 160, 64) == adapter.sizes(model)
    assert abs(cfg.lambda_init(17) - (0.8 - 0.6 * np.exp(-5.1))) < 1e-12


def test_a_share_without_its_source_layer_is_refused():
    import paddle_tpu as fluid

    model, traffic = SUITE.cell()
    for first, what in ((18, "memory unit"), (19, "keys and values")):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            with pytest.raises(ValueError, match=what):
                adapter.build(dict(model, first_layer_held=first,
                                   num_hidden_layers=2), traffic)


# ------------------------------------------ the flash kernel, by name


@pytest.mark.parametrize("which", ["window", "full", "cross"])
def test_differential_attention_through_the_flash_kernel(which, monkeypatch,
                                                         attn_path):
    """The blocked kernels, interpreted: two calls a layer, each over the
    first or the second heads of the pairs (16 lanes here) under values
    twice as wide, two query heads a key/value head, with a window that is
    no multiple of anything: forced by name, since the CPU's dispatch
    never chooses the kernel."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    attn_path("flash")
    m = SUITE.mixer(which, batch=1, seq=160, seed=2,
                    config=dict(AS_AT_WIDTH, sliding_window=50))
    assert m.bumped("attn_dispatch_flash") == 2
    assert m.bumped("flash_wide_value_calls") == 2
    assert m.bumped("flash_narrow_value_calls") == 0
    assert m.bumped("attn_dispatch_flash_window") == 2 * (which == "window")
    assert m.counters["attn_kv_group"] == 2
    assert rel(m.got, m.want()) < 2e-5


# ------------------------------------------ the second reader's gradient


def test_the_second_readers_gradient_is_no_rounding(float32_run):
    """`jax.grad` of the reference with layer 18 reading a memory that
    carries no gradient back differs from the true one by far more than
    the limit above, so that limit does hold the sum of the two."""
    import jax

    model, batch, p, _ = float32_run

    def loss(p, stop):
        real = adapter.gmu_mixer
        try:
            adapter.gmu_mixer = (lambda p, u, m, n: real(
                p, u, jax.lax.stop_gradient(m) if stop else m, n))
            return SUITE.loss(p, batch, model)
        finally:
            adapter.gmu_mixer = real

    name = "phi4.layer16.mamba.in_proj.w_0"
    both, one = (compiled(jax.grad(lambda p: loss(p, stop)), p)[name]
                 for stop in (False, True))
    assert rel(one, both) > 0.05


# ------------------------------------------- gauges, counters, the cell


def test_gauges_and_counters_at_the_rehearsal_size():
    from paddle_tpu import profiler

    model, traffic = SUITE.cell()
    before = profiler.counters()
    main, eval_prog, built, exe, names = SUITE.built_model(model, traffic)
    batch = SUITE.batch_for(model, traffic)
    exe.run(main, feed=batch, fetch_list=[built["loss"]])
    after = profiler.counters()
    assert {n: after[n] for n in (
        "diff_attn_layers", "shared_kv_layers", "gmu_layers",
        "ssm_state_size", "ssm_chunk_len", "attn_kv_group")} == {
        "diff_attn_layers": 3, "shared_kv_layers": 1, "gmu_layers": 1,
        "ssm_state_size": model["mamba_d_state"], "ssm_chunk_len": 8,
        "attn_kv_group": 2}

    def bumped(name):
        return after.get(name, 0) - before.get(name, 0)

    # the scans' gradient ops read the states kept and replay nothing; the
    # attention's lower their forward again. A state of 4 keeps the chunked
    # form whatever the backend (on the chip at the published 16: the
    # kernels, 2 and 0, `falls` prints them)
    assert bumped("ssm_dispatch_chunked") == 2
    assert bumped("ssm_dispatch_pallas") == 0
    assert bumped("attn_dispatch_xla") == 2 * 6
    assert bumped("attn_dispatch_flash") == 0
    assert bumped("short_conv_linear_calls") == 0  # the SiLU stays on
    ops = [op.type for op in main.global_block().ops]
    assert ops.count("selective_scan") == 2
    assert ops.count("short_conv1d") == 2
    assert ops.count("fused_multihead_attention") == 6
    # the memory and the shared keys and values: one variable, two readers
    block = main.global_block()
    forward = [op for op in block.ops if not (op.attr("op_role", 0) or 0)]
    scans = [op.output("Y")[0] for op in forward
             if op.type == "selective_scan"]
    readers = [op.type for op in forward
               if scans[1] in op.input_arg_names()]
    assert sorted(readers) == ["elementwise_mul", "elementwise_mul"]
    attn = [op for op in forward if op.type == "fused_multihead_attention"]
    assert attn[2].input("V") == attn[3].input("V") == attn[4].input("V") \
        == attn[5].input("V") != attn[0].input("V")
    assert attn[2].input("K") == attn[4].input("K") != attn[3].input("K")


def test_parameters_and_flops_of_the_cell():
    from benchmark.runners import train_loop

    model, traffic = SUITE.cell(rehearse=False)
    assert (traffic["batch"], traffic["seq_len"]) == (1, 4096)
    assert model["reduced"] == ["num_hidden_layers", "vocab_size"]
    # ISSUE 44's arithmetic, redone
    ffn = 3 * 2560 * 10240
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    attn, gmu, cross = 2560 * 5120 + 2560 * 2560, 2 * 2560 * 5120, 2 * 2560 * 2560
    assert [adapter.mixer_matrix_params(model, k) for k in (
        "mamba", "window", "full", "gmu", "cross")] == [
        mamba, attn, attn, gmu, cross]
    per_token = adapter.matrix_params_per_token(model)
    assert per_token == (6 * ffn + 2 * mamba + 2 * attn + gmu + cross
                         + 2560 * 25008)
    small = (2 * (5120 * 4 + 5120 + 5120 + 5120 * 16 + 5120)  # conv, dt, A, D
             + 2 * (5120 + 2560) + 2560 + 2560  # attention biases
             + 3 * (4 * 64 + 128) + 13 * 2 * 2560)  # lambdas, sub-norm, LN
    held = per_token + small
    assert abs(held / 1e6 - 697.0) < 0.1  # the parameters held
    pairs = 2 * (4096 * 4097 // 2) + (512 * 513 // 2 + 3584 * 512)
    flops = adapter.flops_per_example(model, traffic)
    assert flops == 3.0 * (2 * 4096 * per_token + pairs * 40 * 2 * 192)
    assert 17.9e12 < flops < 18.2e12

    # one parameter each, whatever the uses, and the count the program
    # reports at the rehearsal size by the same formula
    import paddle_tpu as fluid

    small_model, small_traffic = SUITE.cell()
    main, _, built, _ = train_loop.build_programs(
        fluid, adapter, small_model, small_traffic, 3)
    params = main.global_block().all_parameters()
    names = [p.name for p in params]
    assert len(names) == len(set(names)) == (
        1 + 6 * 6 + 2 * 9 + 2 + 3 * 9 + 2)
    assert built["feeds"] == ["tokens", "labels"] and built["loads"] == []


if __name__ == "__main__":
    main(SUITE)
