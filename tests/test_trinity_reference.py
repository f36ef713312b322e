"""Trinity against its plain reference (`benchmark/models/trinity.py`) at
the rehearsal size of the cell `trinity_mini_ep16_s8192`: what every
decoder suite holds (`tests/decoder_suite.py`: the attention mixer, window
and full, the feed-forwards and the whole model; one train step's
gradients for every kind of parameter; that each wrong model is caught by
the cell's tolerance) on this model's data, and its own: the op
`rotary_embedding`; QK-norm and the positions inside the attention op
against the model built from the separate ops; the expert layer's shares
against the uncut layer at this router's scale; the cell's counters and
FLOPs.

Run as a script on the attached TPU (`tests/decoder_suite.py` has the
arguments; with none, the gradients at the published widths on one
1,024-token row):

    python3 tests/test_trinity_reference.py
"""

from __future__ import annotations

import math
import os

import numpy as np
import pytest

from decoder_suite import *  # noqa: F401,F403 — the shared cases, on SUITE
from decoder_suite import (f32, guards, highest, main, rel,
                           settled_counters, state)

from benchmark.models import trinity as adapter  # noqa: E402

CELL = "trinity_mini_ep16_s8192"


def _mixer_program(which, model, batch, seq):
    """The attention mixer or a feed-forward alone in a Program: `u` in,
    `y` out."""
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_parts, trinity as zoo

    cfg = adapter.config(model)
    u = fluid.layers.data("u", [batch, seq, cfg.hidden_size],
                          append_batch_size=False)
    if which in ("window", "full"):
        return zoo._attention(u, cfg, "m",
                              cfg.sliding_window if which == "window" else 0)
    if which == "dense":
        return decoder_parts.ffn(u, cfg.intermediate_size, "m.mlp", cfg)
    return decoder_parts.expert_ffn(u, cfg, "m")[0]


def _want_mixer(which, p, feeds, model, wrong=()):
    u = feeds["u"]
    if which == "dense":
        return highest(adapter._ffn, p, u, "m.mlp")
    if which == "experts":
        return highest(adapter.expert_ffn, p, u, "m", model)
    return highest(adapter.attention_mixer, p, u, "m", model,
                   model["sliding_window"] if which == "window" else 0)


KINDS = {
    "embedding": ("trinity.embed",), "head": ("trinity.head.w_0",),
    "rms_norm": (".input_norm.w_0", ".post_attn_norm.w_0",
                 ".pre_mlp_norm.w_0", ".post_mlp_norm.w_0",
                 "final_norm.w_0"),
    "qk_norm": (".q_norm.w_0", ".k_norm.w_0"),
    "attention": (".attn.q.w_0", ".attn.k.w_0", ".attn.v.w_0",
                  ".attn.o.w_0"),
    "attention_gate": (".attn.gate.w_0",),
    "dense_ffn": (".mlp.gate.w_0", ".mlp.up.w_0", ".mlp.down.w_0"),
    "shared_expert": (".shared.gate.w_0", ".shared.up.w_0", ".shared.down.w_0"),
    "router": (".moe.gate",),
    "experts": (".moe.w_gate", ".moe.w_up", ".moe.w_down"),
}

SUITE = Suite(  # noqa: F405
    CELL, adapter, kinds=KINDS,
    mixers=("window", "full", "dense", "experts"),
    mixer_program=_mixer_program, want_mixer=_want_mixer,
    # the reference with its last layer left out, every layer full, no
    # positions, no gate, no QK-norm, or the group mapped `n % g`: with
    # room, the mildest (no QK-norm) reads 11.7% against the cell's 6%
    wrong={"drop_layers": caught(amp=1.5, drop_layers=1),  # noqa: F405
           **{w: caught(amp=1.5, wrong=(w,))  # noqa: F405
              for w in adapter.WRONG}},
    # at 64 wide the router's logits spread by 0.16 and a correction of
    # 0.1 would pick the same two experts for every token, none of them
    # held in some layer; at the published width they spread by 0.9
    gradients_at={"router_bias_scale": 0.02},
    # under AMP the cell's own tolerance holds here, loss and all
    amp_loss_room=1,
    seed=33001,
    step_counters=("attn_dispatch_flash", "attn_dispatch_flash_window",
                   "attn_qk_prep_fused", "moe_dispatch_grouped",
                   "moe_dispatch_gmm"),
    gauges=("attn_kv_group", "moe_block_rows", "moe_experts_held",
            "moe_experts_total", "flash_blocks_visited",
            "flash_blocks_total"))


# ------------------------------------------------ the op rotary_embedding


def _rotary_program(shape, dtype="float32"):
    import paddle_tpu as fluid

    x = fluid.layers.data("x", list(shape), dtype=dtype,
                          append_batch_size=False)
    x.stop_gradient = False
    y = fluid.layers.rotary_embedding(x, theta=10000.0)
    return x, y


@pytest.mark.parametrize("shape", [(2, 40, 3, 16), (1, 70, 1, 128)])
def test_rotary_embedding_value_gradient_and_shape(shape):
    """Against the reference's written-out rotate-half form and, at one
    position, against the rotation itself: the pair (x_i, x_{i+d/2}) turns
    by p * theta^(-2i/d)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from tools.verify_bench_programs import compare_static_vs_traced

    x, y = _rotary_program(shape)
    assert tuple(y.shape) == shape
    w = np.random.RandomState(1).randn(*shape).astype(np.float32)
    loss = fluid.layers.reduce_sum(
        fluid.layers.elementwise_mul(y, fluid.layers.assign(w)))
    (dx,) = fluid.backward.calc_gradient(loss, [x])
    n, mismatches, unknown = compare_static_vs_traced(
        fluid.default_main_program(), {"x": (shape, "float32")})
    assert n >= 2 and mismatches == [] and unknown == []
    data = np.random.RandomState(0).randn(*shape).astype(np.float32)
    exe = fluid.Executor(fluid.CPUPlace())
    got, got_dx = exe.run(feed={"x": data}, fetch_list=[y, dx])
    want = highest(adapter._rope, jnp.asarray(data), 10000.0)
    # an ulp in a frequency (XLA folds the constant its own way) times the
    # position: 4e-6 rad at position 69
    np.testing.assert_allclose(got, want, atol=2e-5)
    want_dx = f32(jax.grad(lambda t: jnp.sum(adapter._rope(t, 10000.0) * w))(
        jnp.asarray(data)))
    np.testing.assert_allclose(got_dx, want_dx, atol=2e-5)
    d, pos, i = shape[3], shape[1] - 1, 1
    angle = pos * 10000.0 ** (-2 * i / d)
    a, b_ = data[0, pos, 0, i], data[0, pos, 0, i + d // 2]
    np.testing.assert_allclose(
        [got[0, pos, 0, i], got[0, pos, 0, i + d // 2]],
        [a * np.cos(angle) - b_ * np.sin(angle),
         b_ * np.cos(angle) + a * np.sin(angle)], atol=1e-5)
    np.testing.assert_allclose(got[:, 0], data[:, 0], atol=1e-7)  # position 0


def test_rotary_embedding_is_float32_inside_under_amp():
    """bf16 in and out, the angles and the products float32: at position
    8,191 a bf16 angle would be whole turns off."""
    import jax.numpy as jnp

    from paddle_tpu.ops.nn_ops import rotate_half

    r = np.random.RandomState(2)
    x = jnp.asarray(r.randn(1, 8192, 1, 16), jnp.bfloat16)
    got = rotate_half(x, 10000.0)
    assert got.dtype == jnp.bfloat16
    want = highest(adapter._rope, x.astype(jnp.float32), 10000.0)
    # one rounding of the output to bf16 and no more
    assert np.abs(np.asarray(got, np.float32) - want)[0, -64:].max() < 2e-2
    assert rel(np.asarray(got, np.float32)[0, -64:], want[0, -64:]) < 4e-3


# ------------------------------------------ the flash kernel, by name


@pytest.mark.parametrize("which", ["window", "full"])
def test_attention_through_the_flash_kernel(which, monkeypatch, attn_path):
    """The blocked kernel, interpreted, over two key/value heads with a
    window that is no multiple of anything: forced by name, since the
    CPU's dispatch never chooses it."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    attn_path("flash")
    m = SUITE.mixer(which, batch=1, seq=160, seed=2,
                    config={"sliding_window": 50})
    assert m.bumped("attn_dispatch_flash") == 1
    assert m.bumped("attn_dispatch_flash_window") == (which == "window")
    assert m.counters["attn_kv_group"] == 2
    assert rel(m.got, m.want()) < 2e-5


# ------------------------- QK-norm and positions inside the attention op


def _attention_separate(u, cfg, name, window):
    """`models/trinity.py::_attention` as it stood before the attention op
    took QK-norm and the positions: an op each, which is what the fused
    op has to mean everywhere."""
    from paddle_tpu import layers
    from paddle_tpu.models.decoder_parts import norm, proj

    b, s, _ = u.shape
    h, g, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q = layers.reshape(proj(u, h * d, name + ".q", cfg), [b, s, h, d])
    k = layers.reshape(proj(u, g * d, name + ".k", cfg), [b, s, g, d])
    v = layers.reshape(proj(u, g * d, name + ".v", cfg), [b, s, g, d])
    gate = layers.sigmoid(proj(u, h * d, name + ".gate", cfg))
    q = norm(q, name + ".q_norm", cfg, axis=3)
    k = norm(k, name + ".k_norm", cfg, axis=3)
    if window:
        q = layers.rotary_embedding(q, theta=cfg.rope_theta)
        k = layers.rotary_embedding(k, theta=cfg.rope_theta)
    a = layers.fused_multihead_attention(
        q, k, v, causal=True, sm_scale=1.0 / math.sqrt(d), layout="bshd",
        window=window)
    a = layers.elementwise_mul(layers.reshape(a, [b, s, h * d]), gate)
    return proj(a, cfg.hidden_size, name + ".o", cfg)


def _loss_and_gradients(model, traffic, seed=3):
    """(loss, {name: gradient}, the forward's count of fused lowerings,
    the train program's op types) in programs and a scope of its own."""
    import paddle_tpu as fluid
    from benchmark.runners import train_loop
    from paddle_tpu import profiler

    with guards():
        model = dict(model, optimizer={"type": "SGD", "learning_rate": 1.0})
        main, startup, built, eval_prog = train_loop.build_programs(
            fluid, adapter, model, traffic, seed)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        names = [p.name for p in main.global_block().all_parameters()]
        before = state(names)
        batch = SUITE.batch_for(model, traffic)
        count = profiler.counters().get("attn_qk_prep_fused", 0)
        (loss,) = exe.run(eval_prog, feed=batch, fetch_list=[built["loss"]])
        count = profiler.counters().get("attn_qk_prep_fused", 0) - count
        exe.run(main, feed=batch, fetch_list=[built["loss"]])
        grads = {n: before[n] - v for n, v in state(names).items()}
        ops = [op.type for op in main.global_block().ops]
    return float(np.asarray(loss)), grads, count, ops


@pytest.mark.parametrize("kernels", [True, False],
                         ids=["flash_interpreted", "plain_path"])
def test_fused_qk_prep_is_the_separate_ops_model(kernels, monkeypatch,
                                                 attn_path):
    """One window layer and one full layer at a head of 128 lanes: the
    model whose attention op norms and rotates q and k gives the loss and
    every parameter's gradient of the model built from `rms_norm` and
    `rotary_embedding`, under the same names; through the kernel pair
    where the flash path runs (interpreted here, forced by name), through
    the two ops' own functions on the plain path."""
    from paddle_tpu.models import trinity as zoo

    if kernels:
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
        attn_path("flash")
    model, traffic = SUITE.cell(precision="float32", head_dim=128,
                          num_hidden_layers=2, first_layer_held=2,
                          router_bias_scale=0.02)
    traffic = dict(traffic, seq_len=48)
    assert [w for _, w, _ in adapter.held_layers(model)] == [16, 0]
    loss, grads, count, ops = _loss_and_gradients(model, traffic)
    assert count == (2 if kernels else 0)
    assert "rotary_embedding" not in ops
    assert ops.count("rms_norm") == 2 * 4 + 1  # no QK-norm among them
    monkeypatch.setattr(zoo, "_attention", _attention_separate)
    want_loss, want, none, want_ops = _loss_and_gradients(model, traffic)
    assert none == 0 and want_ops.count("rotary_embedding") == 2
    assert want_ops.count("rms_norm") == 2 * 6 + 1
    assert sorted(grads) == sorted(want)
    assert abs(loss - want_loss) < 1e-5 * abs(want_loss)
    for name in sorted(want):
        if name.endswith(".moe.bias"):  # seeded, never trained
            assert not np.abs(grads[name]).any()
            continue
        assert np.abs(want[name]).max() > 0, name
        # a gradient read as before - after carries the parameter's own
        # float32 rounding (decoder_suite.check_gradients)
        room = 1.2e-7 * (1 + np.abs(want[name]).max())
        err = np.abs(grads[name] - want[name]).max()
        assert max(err - room, 0.0) < 1e-5 * np.abs(want[name]).max(), name


def test_attention_without_the_new_inputs_is_the_op_it_was():
    """Kimi's latent attention and BERT's pass no QNorm, KNorm or
    `rope_theta`: their op carries the slots and attributes it carried,
    their Programs the ops they had, and no lowering of theirs counts a
    fused one."""
    import paddle_tpu as fluid
    from benchmark.harness import spec
    from paddle_tpu import profiler

    attrs = {"causal", "attn_dropout", "sm_scale", "is_test", "layout",
             "window"}
    # the forward ops at the rehearsal size, as the parent of PR 34 builds
    # them (147 and 80)
    before = profiler.counters().get("attn_qk_prep_fused", 0)
    for cell_name, n_ops, n_attn in (("kimi_linear_ep32_s4096", 147, 1),
                                     ("bert_base_s128", 80, 2)):
        c = spec.cell(cell_name, rehearse=True)
        adapter = spec.plugin("models", c["config"]["adapter"])
        with guards():
            built = adapter.build(c["config"], c["traffic"])
            main = fluid.default_main_program()
            ops = main.global_block().ops
            attn = [op for op in ops
                    if op.type == "fused_multihead_attention"]
            assert (len(ops), len(attn)) == (n_ops, n_attn), (
                cell_name, len(ops), len(attn))
            for op in attn:
                assert set(op.inputs) <= {"Q", "K", "V", "KeyBias"}
                assert attrs <= set(op.attrs)
                assert not {"qk_norm_epsilon", "rope_theta"} & set(op.attrs)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            batch = adapter.make_batch(np.random.RandomState(0), c["config"],
                                       c["traffic"])
            exe.run(main, feed=batch, fetch_list=[built["loss"]])
    assert profiler.counters().get("attn_qk_prep_fused", 0) == before


def test_the_cell_declares_the_kernel_pairs_metric():
    """`qk_prep_hbm_roofline_pct` in `BENCHMARK.json` and beside the other
    metrics' files, for this cell and whichever other names the mechanism
    `qk_prep`."""
    import json

    from benchmark.harness import spec

    metric = "qk_prep_hbm_roofline_pct"
    with open(os.path.join(os.path.dirname(spec.BENCH_DIR),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    (declared,) = [m for m in bench["per_layer"] if m["name"] == metric]
    assert CELL in declared.pop("workloads")
    assert declared == {
        "name": metric, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "Pallas kernels",
        "moves": "train_examples_per_s"}
    m = spec.load("layer_metrics", metric)
    assert (m["kind"], m["where"]) == ("trace_roofline",
                                       {"config.mechanisms": ["qk_prep"]})
    assert "qk_prep" in spec.cell(CELL)["config"]["mechanisms"]
    assert metric in {x["name"] for x in spec.layer_metrics(spec.cell(CELL))}
    for other in ("kimi_linear_ep32_s4096", "bert_base_s128"):
        assert metric not in {
            x["name"] for x in spec.layer_metrics(spec.cell(other))}


# -------------------------------------------------- the expert layer


@pytest.mark.parametrize("total,held,k", [(16, 1, 2), (128, 8, 8)])
def test_the_16_shares_add_up_to_the_uncut_layer(total, held, k):
    """Sixteen shares' routed parts, and the shared expert counted once,
    equal the reference's layer with all the experts held, at this
    router's scale (2.826, renormalised): the published 128 experts 8 a
    share and 8 a token, and a small layer."""
    import paddle_tpu as fluid

    r = np.random.RandomState(total)
    hidden, width, shares = 16, 8, 16
    assert total == shares * held
    p = {"m.moe.gate": r.randn(hidden, total).astype(np.float32) * 0.3,
         "m.moe.bias": r.randn(total).astype(np.float32) * 0.1}
    for w, shape in (("w_gate", (total, hidden, width)),
                     ("w_up", (total, hidden, width)),
                     ("w_down", (total, width, hidden))):
        p["m.moe." + w] = r.randn(*shape).astype(np.float32) * 0.2
    for w, shape in (("gate", (hidden, width)), ("up", (hidden, width)),
                     ("down", (width, hidden))):
        p[f"m.shared.{w}.w_0"] = r.randn(*shape).astype(np.float32) * 0.2
    u = r.randn(2, 24, hidden).astype(np.float32)
    x = fluid.layers.data("u", list(u.shape), append_batch_size=False)
    outs = []
    for lo in range(0, total, held):
        outs += fluid.layers.moe_experts(
            x, experts_total=total, experts_held=held, d_ff=width, k=k,
            held_from=lo, scaling=2.826, renormalize=True,
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
             "route_norm": True, "route_scale": 2.826,
             "num_shared_experts": 1}
    shared = highest(adapter._ffn, p, u, "m.shared")
    uncut = highest(adapter.expert_ffn, p, u, "m", layer)
    assert rel(shared + sum(routed), uncut) < 1e-5
    # and one share alone is the reference's share
    p_share = dict(p, **{f"m.moe.{w}": p[f"m.moe.{w}"][held:2 * held]
                         for w in ("w_gate", "w_up", "w_down")})
    one = highest(adapter.expert_ffn, p_share, u, "m",
                  dict(layer, num_experts=held, held_from=held))
    assert rel(shared + routed[1], one) < 1e-5


# ----------------------------------------------- the cell's arithmetic


def _flash_kernels_declared(model, traffic) -> dict:
    """Kernel name -> the FLOPs each of its calls declares
    (`ops/pallas/cost.py`, what `flash_kernels_roofline_pct` reads) over
    the held layers of one train step at the cell's shapes, a call of
    `flash_attention` and its backward a layer."""
    import jax
    import jax.numpy as jnp
    from pallas_costs import declared

    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    b, s, d = traffic["batch"], traffic["seq_len"], model["head_dim"]
    q, kv = (jax.ShapeDtypeStruct((b, heads, s, d), jnp.bfloat16)
             for heads in (model["num_attention_heads"],
                           model["num_key_value_heads"]))
    found = {}
    for _, window, _ in adapter.held_layers(model):
        grads = jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, causal=True, window=window).astype(jnp.float32)),
            argnums=(0, 1, 2))
        for name, calls in declared(grads, q, kv, kv).items():
            found.setdefault(name, []).extend(c.flops for c in calls)
    return found


def test_counters_and_flops_of_the_cell(monkeypatch):
    from paddle_tpu import profiler

    model, traffic = SUITE.cell(rehearse=False)
    assert (traffic["batch"], traffic["seq_len"]) == (1, 8192)
    assert adapter.held_layers(model) == [
        (1, 2048, True), (2, 2048, False), (3, 0, False), (4, 2048, False),
        (5, 2048, False)]
    # ISSUE 33's arithmetic, redone: attention 27.26M a layer, dense FFN
    # 37.75M, shared expert 6.29M, router 0.26M, one routed expert's worth a
    # token (8 x 8 / 128 of 6.29M x ... ) 3.15M, the head 51.25M
    per_token = adapter.matrix_params_per_token(model)
    want = (5 * 27.263 + 37.749 + 4 * (6.291 + 0.262 + 3.146) + 51.249)
    assert abs(per_token / 1e6 - want) < 0.05
    # a window layer's queries see min(i + 1, 2048) keys
    assert adapter.admitted_pairs(8192, 2048) == sum(
        min(i + 1, 2048) for i in range(8192))
    assert adapter.admitted_pairs(8192, 0) == 8192 * 8193 // 2
    assert adapter.admitted_pairs(100, 2048) == 100 * 101 // 2
    pairs = 4 * adapter.admitted_pairs(8192, 2048) + 8192 * 8193 // 2
    assert abs(pairs / (5 * 8192 * 8192) - 0.275) < 0.001  # the masks admit
    flops = adapter.flops_per_example(model, traffic)
    assert flops == 3.0 * (2 * 8192 * per_token + pairs * 32 * 4 * 128)
    assert 17.0e12 < flops < 18.0e12
    with monkeypatch.context() as patch:  # a trace alone: nothing runs
        patch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
        kernels = _flash_kernels_declared(model, traffic)
    assert {name: len(calls) for name, calls in kernels.items()} == {
        "flash_fwd": 5, "flash_bwd_dkv_dq": 5}
    assert sum(kernels["flash_fwd"]) == 4 * 32 * 128 * pairs
    assert sum(kernels["flash_bwd_dkv_dq"]) == 10 * 32 * 128 * pairs
    # the attention part of flops_per_example, forward and backward, is
    # 12 a pair a lane; the kernels declare 14 because the backward
    # computes the scores again
    assert sum(map(sum, kernels.values())) * 12 == 14 * (
        flops - 3.0 * 2 * 8192 * per_token)

    c0 = profiler.counters()
    small, small_traffic = SUITE.cell()
    main, _, built, exe, _ = SUITE.built_model(small, small_traffic)
    batch = SUITE.batch_for(small, small_traffic)
    loads = exe.run(main, feed=batch, fetch_list=built["loads"])
    c1 = settled_counters()
    grouped = c1["moe_dispatch_grouped"] - c0.get("moe_dispatch_grouped", 0)
    assert grouped >= 4
    # the step's own count of the rows the held experts took: the
    # forward's, and nothing for the gradient ops' replays
    assert c1["moe_rows_live"] - c0.get("moe_rows_live", 0) == sum(
        int(np.sum(load)) for load in loads)
    # on the plain path: the rehearsal's widths are no lane multiple, and
    # there is no Mosaic here (ops/pallas/grouped_matmul.py)
    assert c1.get("moe_dispatch_gmm", 0) == c0.get("moe_dispatch_gmm", 0)
    assert (c1["moe_experts_held"], c1["moe_experts_total"]) == (2, 8)
    assert c1["attn_kv_group"] == 2
    assert c1["attn_dispatch_xla"] - c0.get("attn_dispatch_xla", 0) >= 5
    # and no counter that is another decoder's: no softmax router, no
    # scaled positions, no convolution at all
    for other in ("moe_route_softmax", "attn_rope_scaled",
                  "short_conv_linear_calls"):
        assert c1.get(other, 0) == c0.get(other, 0), other
    experts = [op for op in main.global_block().ops
               if op.type == "moe_experts"]
    assert all(op.attr("score_func") == "sigmoid" for op in experts)
    # the rehearsal's shares are 1/4 (2 of 8 experts): a block of 7/16
    tokens = small_traffic["batch"] * small_traffic["seq_len"]
    assert c1["moe_block_rows"] == math.ceil(
        1.75 * 0.25 * tokens * experts[0].attr("k"))
    assert len(loads) == 4 and all(x.shape == (2,) for x in loads)


if __name__ == "__main__":
    main(SUITE)
