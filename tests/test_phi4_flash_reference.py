"""Phi-4-mini-flash against its plain reference
(`tests/phi4_flash_reference.py`) at the rehearsal size of the cell
`phi4_mini_flash_vp8_longdoc`: every mixer alone, differential attention
through the flash kernel with values twice as wide as its keys, the whole
model in float32 and under bf16 AMP, one train step's gradients for every
kind of parameter (the scan output and the keys and values that two
layers read among them), that each wrong lowering and each wrong model is
caught, the gauges and counters, and the cell's arithmetic.

Run as a script on the attached TPU, outside any timed window:

    python3 tests/test_phi4_flash_reference.py readings 1 2   # program, wrong models and fp8 reference against the reference
    python3 tests/test_phi4_flash_reference.py falls@3e-6 1 2   # the loss over the window's steps at a rate
    python3 tests/test_phi4_flash_reference.py gradients      # at the published widths on one 1,024-token row
"""

from __future__ import annotations

import inspect
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import phi4_flash_reference as ref  # noqa: E402 — beside this file
from test_kimi_linear_reference import (  # noqa: E402 — the shared helpers
    check_gradients, f32, highest, rel, state)
from test_mellum_reference import _fp8  # noqa: E402 — the matrices in e4m3

CELL = "phi4_mini_flash_vp8_longdoc"


def cell(rehearse=True, **config):
    from benchmark.harness import spec

    c = spec.cell(CELL, rehearse=rehearse)
    c["config"].update(config)
    return c["config"], c["traffic"]


def built_model(model, traffic, seed=3):
    """Programs, executor and the seeded state by name, in a scope of its
    own (the caller holds the guards)."""
    import paddle_tpu as fluid
    from benchmark.models import phi4_flash as adapter
    from benchmark.runners import train_loop

    main, startup, built, eval_prog = train_loop.build_programs(
        fluid, adapter, model, traffic, seed)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    names = [p.name for p in main.global_block().all_parameters()]
    return main, eval_prog, built, exe, names


def batch_for(model, traffic, seed=0):
    from benchmark.models import phi4_flash as adapter

    return adapter.make_batch(np.random.RandomState(seed), model, traffic)


# At 64 wide, seeded as the cell is (matrices Normal(0, 0.02)), a mixer adds
# a hundredth of the residual stream, the scan's state next to nothing of
# the mixer, and neither a wrong model nor a lowering to bf16 shows in the
# logits. With the matrices at 0.1 the layers weigh in the stream as at the
# published width (0.02 x sqrt(2560) = 1.0 a product; 0.1 x sqrt(64) = 0.8).
AS_AT_WIDTH = {"initializer_range": 0.1}


# ------------------------------------------------- the copy is a copy


def test_reference_copy_is_the_adapters_word_for_word():
    from benchmark.models import phi4_flash as adapter

    for name in ("held_layers", "sizes", "_ln", "_silu", "_ffn", "_conv",
                 "scan_recurrence", "mamba_mixer", "gmu_mixer",
                 "differential_mixer", "reference"):
        assert inspect.getsource(getattr(ref, name)) == inspect.getsource(
            getattr(adapter, name)), name
    assert (ref.SCORED_EVERY, ref.QUERY_BLOCK) == (
        adapter.SCORED_EVERY, adapter.QUERY_BLOCK)


def test_layer_kinds_follow_the_published_index():
    from benchmark.models import phi4_flash as adapter

    model, _ = cell(rehearse=False)
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
    from benchmark.models import phi4_flash as adapter

    model, traffic = cell()
    for first, what in ((18, "memory unit"), (19, "keys and values")):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            with pytest.raises(ValueError, match=what):
                adapter.build(dict(model, first_layer_held=first,
                                   num_hidden_layers=2), traffic)


# ------------------------------------------ the program, mixer by mixer


def _mixer_program(which, model, batch=2, seq=80):
    """One mixer or the feed-forward alone in a Program: `u` in, `y` out
    (for "gmu" also `m`, the memory; for "cross" `k` and `v`)."""
    import paddle_tpu as fluid
    from benchmark.models import phi4_flash as adapter
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
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    names = [p.name for p in
             fluid.default_main_program().global_block().all_parameters()]
    return exe, y, names


def _mixer_feeds(which, model, batch=2, seq=80, seed=1):
    from benchmark.models import phi4_flash as adapter

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


def _want_mixer(which, p, feeds, model):
    u = feeds["u"]
    lam0 = 0.8 - 0.6 * np.exp(-0.3 * 15)
    attn = lambda window, kv=None: ref.differential_mixer(  # noqa: E731
        p, u, "m", model, window, lam0, kv)[0]
    return {"mamba": lambda: ref.mamba_mixer(p, u, "m", model)[0],
            "scan_output": lambda: ref.mamba_mixer(p, u, "m", model)[1],
            "gmu": lambda: ref.gmu_mixer(p, u, feeds.get("m"), "m"),
            "ffn": lambda: ref._ffn(p, u, "m.mlp"),
            "window": lambda: attn(model["sliding_window"]),
            "full": lambda: attn(0),
            "cross": lambda: attn(0, (feeds["k"], feeds["v"]))}[which]


@pytest.mark.parametrize("which", ["mamba", "scan_output", "gmu", "ffn",
                                   "window", "full", "cross"])
def test_program_mixer_equals_reference(which):
    model, _ = cell(**AS_AT_WIDTH)
    exe, y, names = _mixer_program(which, model)
    feeds = _mixer_feeds(which, model)
    (got,) = exe.run(feed=feeds, fetch_list=[y])
    want = highest(_want_mixer(which, state(names), feeds, model))
    assert np.abs(want).max() > 1e-4  # something was computed
    assert rel(got, want) < 2e-5


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
    from paddle_tpu import profiler

    before = profiler.counters()
    model, _ = cell(sliding_window=50, **AS_AT_WIDTH)
    exe, y, names = _mixer_program(which, model, batch=1, seq=160)
    feeds = _mixer_feeds(which, model, batch=1, seq=160, seed=2)
    (got,) = exe.run(feed=feeds, fetch_list=[y])
    after = profiler.counters()

    def bumped(name):
        return after.get(name, 0) - before.get(name, 0)

    assert bumped("attn_dispatch_flash") == 2
    assert bumped("flash_wide_value_calls") == 2
    assert bumped("flash_narrow_value_calls") == 0
    assert bumped("attn_dispatch_flash_window") == 2 * (which == "window")
    assert after["attn_kv_group"] == 2
    want = highest(_want_mixer(which, state(names), feeds, model))
    assert rel(got, want) < 2e-5


# ------------------------------------------------------ the whole model


@pytest.fixture(scope="module")
def amp_run():
    """The cell's program at the rehearsal size in the cell's precision,
    built and run once for the tests below: (model, batch, parameters,
    [loss, scored logits])."""
    import paddle_tpu as fluid

    model, traffic = cell(**AS_AT_WIDTH)
    with fluid.program_guard(fluid.Program(), fluid.Program()), \
            fluid.unique_name.guard(), fluid.scope_guard(fluid.Scope()):
        _, eval_prog, built, exe, names = built_model(model, traffic)
        batch = batch_for(model, traffic)
        got = exe.run(eval_prog, feed=batch, fetch_list=built["check"])
        return model, batch, state(names), got


@pytest.fixture(scope="module")
def float32_run():
    """The same in float32, on rows of 80 tokens (five windows)."""
    import paddle_tpu as fluid

    model, traffic = cell(precision="float32", **AS_AT_WIDTH)
    traffic = dict(traffic, seq_len=80)
    with fluid.program_guard(fluid.Program(), fluid.Program()), \
            fluid.unique_name.guard(), fluid.scope_guard(fluid.Scope()):
        _, eval_prog, built, exe, names = built_model(model, traffic)
        batch = batch_for(model, traffic)
        got = exe.run(eval_prog, feed=batch, fetch_list=built["check"])
        return model, batch, state(names), got


def _check(got, p, batch, model, **kw):
    from benchmark.models import phi4_flash as adapter
    from benchmark.runners import train_loop

    nll, count, want = highest(adapter.reference, p, batch, model, **kw)
    return want, train_loop.check_reference(
        got[0], got[1], nll / count, want[:adapter.SCORED_SEQUENCES],
        adapter.TOLERANCE)


# what the float32 program is held to, and so what a lowering to bf16 has
# to exceed: float32's own rounding through six layers reads 1e-6
FLOAT32_LIMITS = {"logits_rel_rms": 5e-5, "loss_abs": 1e-5}


def test_whole_model_logits_and_loss_equal_reference_float32(float32_run):
    from benchmark.models import phi4_flash as adapter

    model, batch, p, got = float32_run
    assert sorted(batch) == ["labels", "tokens"]
    np.testing.assert_array_equal(batch["labels"][:, :-1], batch["tokens"][:, 1:])
    assert np.asarray(got[1]).shape == (
        adapter.SCORED_SEQUENCES, 80 // adapter.SCORED_EVERY,
        model["vocab_size"])
    _, check = _check(got, p, batch, model)
    assert check["ok"], check
    assert all(check[k] < v for k, v in FLOAT32_LIMITS.items()), check


@pytest.mark.parametrize("lowered", ["scan_bf16", "softmax_bf16",
                                     "subln_bf16"])
def test_a_lowering_to_bf16_fails_the_float32_limits(lowered, float32_run):
    """The scan's state, the softmaxes or the sub-norm in bf16: each moves
    the logits by several times the float32 program's limit (the scan 7
    times, the softmaxes and the sub-norm far more), which is the
    tolerance that catches it here (on the chip, where the
    program's matrices are bf16, the adapter's `TOLERANCE` comment says
    which of them its limits still catch)."""
    model, batch, p, got = float32_run
    _, check = _check(got, p, batch, model, wrong=(lowered,))
    assert check["logits_rel_rms"] > 4 * FLOAT32_LIMITS["logits_rel_rms"], check


def test_whole_model_equals_reference_under_bf16_amp(amp_run):
    """The logits within the cell's limit. The loss here is a mean of 96
    bf16 per-token losses where the cell's is one of 4,096, so its
    rounding is sqrt(4096 / 96) = 6.5 times as coarse: held to that many
    times the cell's limit."""
    from benchmark.models.phi4_flash import TOLERANCE

    model, batch, p, got = amp_run
    _, check = _check(got, p, batch, model)
    assert 1e-4 < check["logits_rel_rms"] <= TOLERANCE["logits_rel_rms"], check
    assert check["loss_abs"] <= 6.5 * TOLERANCE["loss_abs"], check


@pytest.mark.parametrize("wrong", [{"drop_layers": 1}] + [
    {"wrong": (w,)} for w in (
        "all_full", "no_conv_bias", "lam0_const", "memory_after_gate",
        "pair_halves", "own_memory")])
def test_a_wrong_model_is_caught_by_the_cells_tolerance(wrong, amp_run):
    """The reference with its last layer left out or with one departure of
    `WRONG`, against the program in the cell's precision."""
    model, batch, p, got = amp_run
    _, check = _check(got, p, batch, model, **wrong)
    assert not check["ok"], (wrong, check)


# ------------------------------------------------ one step's gradients

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


def _gradients(model, traffic, place=None, seed=3):
    """{name: gradient} of the program's train step (one SGD step at rate
    1: the gradient is what the parameter lost) and of `jax.grad` of the
    reference's loss, from the same seeded state and batch."""
    import jax

    import paddle_tpu as fluid
    from benchmark.models import phi4_flash as adapter
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
    """Every parameter's gradient. Layer 16's scan output is read by its
    own gate and by layer 18's memory unit, layer 17's keys and values by
    its own maps and by layer 19's: a gradient lost on either path shows
    in the Mamba projections of layer 16 and in layer 17's `qkv`."""
    model, traffic = cell(precision="float32", **AS_AT_WIDTH)
    got, want, before = _gradients(model, dict(traffic, seq_len=80))
    worst = check_gradients(got, want, before, 2e-4, kinds=KINDS)
    assert set(worst) == set(KINDS)
    # the two activations that receive two gradients: without the second
    # reader's share these are tens of percent off
    for n in ("phi4.layer16.mamba.in_proj.w_0", "phi4.layer16.mamba.x_proj.w_0",
              "phi4.layer17.attn.qkv.w_0", "phi4.layer17.attn.qkv.b_0"):
        assert rel(got[n], want[n]) < 2e-4, n


def test_the_second_readers_gradient_is_no_rounding(float32_run):
    """`jax.grad` of the reference with layer 18 reading a memory that
    carries no gradient back differs from the true one by far more than
    the limit above, so that limit does hold the sum of the two."""
    import jax
    import jax.numpy as jnp

    model, batch, p, _ = float32_run

    def loss(p, stop):
        real = ref.gmu_mixer
        try:
            ref.gmu_mixer = (lambda p, u, m, n: real(
                p, u, jax.lax.stop_gradient(m) if stop else m, n))
            return ref.loss(p, batch, model)
        finally:
            ref.gmu_mixer = real

    name = "phi4.layer16.mamba.in_proj.w_0"
    p = {n: jnp.asarray(v) for n, v in p.items()}
    both, one = (f32(jax.grad(lambda p: loss(p, stop))(p))[name]
                 for stop in (False, True))
    assert rel(one, both) > 0.05


# ------------------------------------------- gauges, counters, the cell


def test_gauges_and_counters_at_the_rehearsal_size():
    from paddle_tpu import profiler

    model, traffic = cell()
    before = profiler.counters()
    main, eval_prog, built, exe, names = built_model(model, traffic)
    batch = batch_for(model, traffic)
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
    from benchmark.models import phi4_flash as adapter
    from benchmark.runners import train_loop

    model, traffic = cell(rehearse=False)
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

    small_model, small_traffic = cell()
    main, _, built, _ = train_loop.build_programs(
        fluid, adapter, small_model, small_traffic, 3)
    params = main.global_block().all_parameters()
    names = [p.name for p in params]
    assert len(names) == len(set(names)) == (
        1 + 6 * 6 + 2 * 9 + 2 + 3 * 9 + 2)
    assert built["feeds"] == ["tokens", "labels"] and built["loads"] == []


# ------------------------------------------------------- on the chip


def _on_chip(model, traffic, seed):
    """The cell's programs on the attached TPU with the seeded state."""
    import paddle_tpu as fluid
    from benchmark.models import phi4_flash as adapter
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
    from benchmark.models import phi4_flash as adapter
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


def loss_falls(seeds, steps=44, rate=None):
    """At the published widths on the attached TPU, the cell's train step
    on the batches its runner would feed (one check batch drawn first,
    then the pool of 32), `steps` of them at `rate`: the loss at the
    first and the last step, and the fall as the runner takes it (the
    median of the window's first ten less that of its last ten; the
    window starts at the fifth step)."""
    import paddle_tpu as fluid
    from benchmark.models import phi4_flash as adapter

    from paddle_tpu import profiler

    model, traffic = cell(rehearse=False)
    if rate:
        model["optimizer"] = dict(model["optimizer"], learning_rate=rate)
    c0 = profiler.counters()
    for seed in seeds:
        with fluid.program_guard(fluid.Program(), fluid.Program()), \
                fluid.unique_name.guard(), fluid.scope_guard(fluid.Scope()):
            main, _, built, exe = _on_chip(model, traffic, seed)
            rng = np.random.RandomState(seed)
            adapter.make_batch(rng, model, traffic)  # the check's batch
            pool = [adapter.make_batch(rng, model, traffic)
                    for _ in range(traffic["pool_batches"])]
            losses = [float(np.asarray(exe.run(
                main, feed=pool[i % len(pool)],
                fetch_list=[built["loss"]])[0]).reshape(-1)[0])
                for i in range(steps)]
        fall = np.median(losses[4:14]) - np.median(losses[-10:])
        print(f"seed {seed} rate {model['optimizer']['learning_rate']}: loss "
              f"step 0 {losses[0]:.4f}, step 4 {losses[4]:.4f}, step "
              f"{steps - 1} {losses[-1]:.4f}; fall (median of steps 4-13 "
              f"less median of the last ten) {fall:.4f}; every tenth: "
              + " ".join(f"{v:.3f}" for v in losses[::10]), flush=True)
    c1 = profiler.counters()
    print("counters of", len(seeds), "train steps' traces:", {
        n: c1.get(n, 0) - c0.get(n, 0) for n in (
            "ssm_dispatch_pallas", "ssm_dispatch_chunked",
            "attn_dispatch_flash", "attn_dispatch_flash_window",
            "flash_wide_value_calls",
            "flash_narrow_value_calls", "flash_fwd_wide_key_calls",
            "attn_dispatch_xla")},
        {n: c1.get(n) for n in (
            "ssm_state_size", "ssm_chunk_len", "diff_attn_layers",
            "shared_kv_layers", "gmu_layers", "attn_kv_group",
            "flash_blocks_visited", "flash_blocks_total")}, flush=True)


def chip_gradients():
    """The gradients of every kind of parameter at the published widths,
    program against `jax.grad` of the reference, on one 1,024-token row."""
    import paddle_tpu as fluid

    model, traffic = cell(rehearse=False, precision="float32")
    traffic = dict(traffic, seq_len=1024)
    # float32 on a TPU is a bf16 pass a product unless told otherwise, so
    # the "float32" program is held to 5%, the AMP one to 20%
    for precision, limit in (("float32", 0.05), ("bf16_amp", 0.2)):
        with fluid.program_guard(fluid.Program(), fluid.Program()), \
                fluid.unique_name.guard(), fluid.scope_guard(fluid.Scope()):
            got, want, before = _gradients(
                dict(model, precision=precision), traffic,
                place=fluid.TPUPlace())
        try:
            worst = check_gradients(got, want, before, limit, kinds=KINDS)
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
    seeds = [int(a) for a in sys.argv[2:]] or [44001]
    {"readings": lambda: chip_readings(
        seeds, tuple(w for w in only.split(",") if w)),
     "falls": lambda: loss_falls(seeds, rate=float(rate) if rate else None),
     "gradients": chip_gradients}[what]()
