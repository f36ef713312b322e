"""Mellum 2 against its plain reference (`benchmark/models/mellum.py`) at the
rehearsal size of the cell `mellum2_ep4_s8192`: what every decoder suite
holds (`tests/decoder_suite.py`: the attention mixer, window and full
(YaRN), the expert layer and the whole model; one train step's gradients
for every kind of parameter; that each wrong model is caught by the cell's
tolerance) on this model's data, and its own: YaRN's tables against the
formulas written out by hand; the softmax router against a hand count and
the sigmoid router against the parent's lines; the four shares against the
uncut layer; the block that follows the share held, and a load past it;
the cell's counters and FLOPs.

Run as a script on the attached TPU (`tests/decoder_suite.py` has the
arguments): the readings that place `TOLERANCE`, the share of the
assignments each layer's held experts take (what sized `_block_rows`), the
gradient comparison on one 1,024-token row:

    python3 tests/test_mellum_reference.py readings [seed ...]
    python3 tests/test_mellum_reference.py loads[@rate] [seed ...]
    python3 tests/test_mellum_reference.py gradients
"""

from __future__ import annotations

import math

import numpy as np
import pytest

from decoder_suite import *  # noqa: F401,F403 — the shared cases, on SUITE
from decoder_suite import highest, main, rel, settled_counters

from benchmark.models import mellum as adapter  # noqa: E402

CELL = "mellum2_ep4_s8192"

# At 64 wide, seeded as the cell is (matrices Normal(0, 0.02), the
# embedding Normal(0, 2)), a layer adds a thousandth of the residual stream
# and no wrong model shows in the logits; the router's logits spread by
# 0.16, so softmax and sigmoid weight the selected experts alike. With the
# matrices at 0.1 and the embedding at 0.3 the layers weigh in the stream
# as at the published width (0.02 x sqrt(2304) = 0.96 a product) and the
# router's logits spread by 0.8.
AS_AT_WIDTH = {"initializer_range": 0.1, "embedding_initializer_range": 0.3}



def _mixer_program(which, model, batch, seq):
    """The attention mixer or the expert layer alone in a Program: `u` in,
    `y` out."""
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_parts

    cfg = adapter.config(model)
    u = fluid.layers.data("u", [batch, seq, cfg.hidden_size],
                          append_batch_size=False)
    if which == "experts":
        return decoder_parts.expert_ffn(u, cfg, "m")[0]
    kind = {"window": "sliding_attention", "full": "full_attention"}[which]
    rope = cfg.rope_parameters[kind]
    return decoder_parts.attention(
        u, cfg, "m", window=cfg.sliding_window if which == "window" else 0,
        rope_theta=rope["rope_theta"], rope_scaling=rope)


def _want_mixer(which, p, feeds, model, wrong=()):
    u, rope = feeds["u"], model["rope_parameters"]
    if which == "experts":
        return highest(adapter.expert_ffn, p, u, "m", model)
    if which == "window":
        return highest(adapter.attention_mixer, p, u, "m", model,
                       model["sliding_window"], rope["sliding_attention"])
    return highest(adapter.attention_mixer, p, u, "m", model, 0,
                   rope["full_attention"])


KINDS = {
    "embedding": ("mellum.embed",), "head": ("mellum.head.w_0",),
    "rms_norm": (".input_norm.w_0", ".post_attn_norm.w_0", "final_norm.w_0"),
    "qk_norm": (".q_norm.w_0", ".k_norm.w_0"),
    "attention": (".attn.q.w_0", ".attn.k.w_0", ".attn.v.w_0",
                  ".attn.o.w_0"),
    "router": (".moe.gate",),
    "experts": (".moe.w_gate", ".moe.w_up", ".moe.w_down"),
}

SUITE = Suite(  # noqa: F405
    CELL, adapter, kinds=KINDS, as_at_width=AS_AT_WIDTH,
    mixers=("window", "full", "experts"),
    mixer_program=_mixer_program, want_mixer=_want_mixer,
    # the reference with its last layer left out, default tables on the
    # full layer, YaRN's tables without their factor, a sigmoid router, no
    # renormalisation, every layer full, or no QK-norm: refused by the
    # cell's tolerance
    wrong={"drop_layers": caught(amp=0, drop_layers=1),  # noqa: F405
           **{w: caught(amp=0, wrong=(w,))  # noqa: F405
              for w in adapter.WRONG}},
    # under AMP the cell's own tolerance holds here, loss and all
    amp_loss_room=1,
    seed=37001,
    step_counters=("attn_dispatch_flash", "attn_dispatch_flash_window",
                   "attn_qk_prep_fused", "attn_rope_scaled",
                   "moe_dispatch_grouped", "moe_dispatch_gmm",
                   "moe_route_softmax"),
    gauges=("attn_kv_group", "moe_block_rows", "moe_experts_held",
            "moe_experts_total", "flash_blocks_visited",
            "flash_blocks_total"))


# ------------------------------------------------------- YaRN's tables

HAND = {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
        "original_max_position_embeddings": 16, "beta_fast": 32.0,
        "beta_slow": 1.0, "attention_factor": 0.1 * math.log(4.0) + 1.0}


def test_yarn_tables_equal_the_formulas_written_out_by_hand():
    """d=16, theta 10,000, factor 4 over an original context of 16:
    c(32) = 16 ln(16 / 64 pi) / (2 ln 10000) = -2.20, so low = 0; c(1) =
    16 ln(16 / 2 pi) / (2 ln 10000) = 0.812, so high = 1; the ramp is 0 at
    i = 0 and 1 from i = 1 on: the fastest wave keeps its frequency and
    the seven others turn a quarter as fast; cos and sin carry the factor
    0.1 ln 4 + 1 = 1.1386."""
    from paddle_tpu.layers.nn import _yarn_attr
    from paddle_tpu.ops.nn_ops import rotary_tables, yarn_frequencies

    d, s = 16, 48
    c32 = 16 * math.log(16 / (64 * math.pi)) / (2 * math.log(10000.0))
    c1 = 16 * math.log(16 / (2 * math.pi)) / (2 * math.log(10000.0))
    assert (math.floor(c32), math.ceil(c1)) == (-3, 1)
    low, high = 0, 1
    e = np.array([10000.0 ** (-2 * i / d) for i in range(d // 2)])
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    assert ramp.tolist() == [0.0] + [1.0] * 7
    want = e / 4.0 * ramp + e * (1 - ramp)
    scaling = tuple(_yarn_attr(HAND))
    assert scaling == (4.0, 16.0, 32.0, 1.0, HAND["attention_factor"])
    freq, factor = yarn_frequencies(d, 10000.0, scaling)
    np.testing.assert_allclose(freq, want, rtol=1e-6)
    assert abs(factor - 1.1386294361119891) < 1e-15
    _, got_factor, got_low, got_high = adapter.yarn(d, HAND)
    assert (got_low, got_high, got_factor) == (low, high, factor)
    cos, sin = rotary_tables(s, d, 10000.0, scaling)
    angle = np.arange(s)[:, None] * want[None, :]
    np.testing.assert_allclose(
        cos, factor * np.concatenate([np.cos(angle)] * 2, -1), atol=2e-5)
    np.testing.assert_allclose(
        sin, factor * np.concatenate([-np.sin(angle), np.sin(angle)], -1),
        atol=2e-5)
    # what the absent keys default to, and what a default group means
    assert _yarn_attr({"factor": 4.0,
                       "original_max_position_embeddings": 16}) == list(scaling)
    assert _yarn_attr(None) is None
    assert _yarn_attr({"rope_type": "default", "rope_theta": 1e4}) is None
    with pytest.raises(ValueError, match="only 'yarn'"):
        _yarn_attr({"rope_type": "linear", "factor": 2.0})


def test_the_published_ramp_runs_from_18_to_35():
    """d=128, theta 500,000, the original context 8,192, beta 32 and 1:
    low = floor(18.08), high = ceil(34.98)."""
    from benchmark.harness import spec

    rope = spec.cell(CELL)["config"]["rope_parameters"]["full_attention"]
    freq, factor, low, high = adapter.yarn(128, rope)
    assert (low, high, factor) == (18, 35, 1.2772588722239782)
    e = 500000.0 ** (-np.arange(64) / 64)
    np.testing.assert_allclose(freq[:19], e[:19], rtol=1e-5)
    np.testing.assert_allclose(freq[35:], e[35:] / 16, rtol=1e-5)
    assert np.all(np.diff(np.asarray(freq)) < 0)


@pytest.mark.parametrize("s,d,theta", [(48, 16, 10000.0), (8192, 128, 500000.0)])
def test_unscaled_tables_are_todays_bit_for_bit(s, d, theta):
    """`rotary_tables` with no scaling, written out as the parent has it."""
    import jax.numpy as jnp

    from paddle_tpu.ops.nn_ops import rotary_tables

    freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)
    sin = jnp.concatenate([-jnp.sin(angle), jnp.sin(angle)], -1)
    for scaling in ((), (None,)):
        got_cos, got_sin = rotary_tables(s, d, theta, *scaling)
        assert np.array_equal(got_cos, cos) and np.array_equal(got_sin, sin)


def test_rotary_embedding_op_takes_the_scaling():
    """The op alone, scaled and not, against the reference's `_rope`."""
    import jax.numpy as jnp

    import paddle_tpu as fluid

    shape = (2, 48, 3, 16)
    x = fluid.layers.data("x", list(shape), append_batch_size=False)
    plain = fluid.layers.rotary_embedding(x, theta=10000.0)
    scaled = fluid.layers.rotary_embedding(x, theta=10000.0, rope_scaling=HAND)
    ops = fluid.default_main_program().global_block().ops
    assert "scaling" not in ops[0].attrs and len(ops[1].attr("scaling")) == 5
    data = np.random.RandomState(0).randn(*shape).astype(np.float32)
    got_plain, got_scaled = fluid.Executor(fluid.CPUPlace()).run(
        feed={"x": data}, fetch_list=[plain, scaled])
    default = {"rope_type": "default", "rope_theta": 10000.0}
    np.testing.assert_allclose(
        got_plain, highest(adapter._rope, jnp.asarray(data), default), atol=2e-5)
    np.testing.assert_allclose(
        got_scaled, highest(adapter._rope, jnp.asarray(data), HAND), atol=2e-5)
    assert rel(got_scaled, got_plain) > 0.1


# ------------------------------------------------------------ the router


def test_softmax_router_against_a_hand_count():
    """Two tokens over four experts, two a token: logits ln 1, ln 2, ln 3,
    ln 4 give probabilities 0.1 to 0.4, the two largest 0.4 and 0.3, and
    renormalised 4/7 and 3/7; without, 0.4 and 0.3 times the scaling. The
    correction enters the selection and not the weights."""
    import jax.numpy as jnp

    from paddle_tpu.parallel.moe import moe_route

    x = jnp.eye(2, dtype=jnp.float32)
    gate = jnp.log(jnp.asarray([[1.0, 2.0, 3.0, 4.0], [4.0, 1.0, 3.0, 2.0]]))
    none = jnp.zeros(4)
    idx, w = moe_route(x, gate, none, 2, 1.0, True, "softmax")
    assert idx.tolist() == [[3, 2], [0, 2]]
    np.testing.assert_allclose(w, [[4 / 7, 3 / 7]] * 2, rtol=1e-6)
    _, w = moe_route(x, gate, none, 2, 2.0, False, "softmax")
    np.testing.assert_allclose(w, [[0.8, 0.6]] * 2, rtol=1e-6)
    idx, w = moe_route(x, gate, jnp.asarray([0.0, 1.0, 0.0, 0.0]), 2, 1.0,
                       True, "softmax")
    assert idx.tolist() == [[1, 3], [1, 0]]
    np.testing.assert_allclose(w, [[1 / 3, 2 / 3], [0.2, 0.8]], rtol=1e-6)
    with pytest.raises(ValueError, match="score_func"):
        moe_route(x, gate, none, 2, 1.0, True, "tanh")


def test_sigmoid_router_is_the_parents_bit_for_bit():
    """`moe_route` without `score_func`, and with "sigmoid", against the
    parent's lines written out."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel.moe import moe_route

    r = np.random.RandomState(5)
    x = jnp.asarray(r.randn(96, 32), jnp.float32)
    gate = jnp.asarray(r.randn(32, 16) * 0.3, jnp.float32)
    bias = jnp.asarray(r.randn(16) * 0.1, jnp.float32)
    scores = jax.nn.sigmoid(jnp.dot(x, gate,
                                    precision=jax.lax.Precision.HIGHEST))
    _, want_idx = jax.lax.top_k(scores + bias, 4)
    want = jnp.take_along_axis(scores, want_idx, axis=-1)
    want = 2.446 * (want / jnp.sum(want, axis=-1, keepdims=True))
    for extra in ((), ("sigmoid",)):
        idx, w = moe_route(x, gate, bias, 4, 2.446, True, *extra)
        assert np.array_equal(idx, want_idx) and np.array_equal(w, want)


# ------------------------------------------ the kernels, by name


@pytest.mark.parametrize("which", ["window", "full"])
def test_attention_through_the_flash_and_qk_prep_kernels(which, monkeypatch,
                                                         attn_path):
    """The blocked kernel and the kernel pair before it, interpreted, at a
    head of 128 lanes over two key/value heads, forced by name since the
    CPU's dispatch never chooses them: the full layer's kernels take
    YaRN's tables as the window layer's take the plain ones."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    attn_path("flash")
    m = SUITE.mixer(which, batch=1, seq=160, seed=2,
                    config={"sliding_window": 50, "head_dim": 128})
    assert m.bumped("attn_dispatch_flash") == 1
    assert m.bumped("attn_qk_prep_fused") == 1
    assert m.bumped("attn_dispatch_flash_window") == (which == "window")
    assert m.bumped("attn_rope_scaled") == (which == "full")
    assert rel(m.got, m.want()) < 2e-5


# -------------------------------------------------- the expert layer


def _softmax_layer(r, hidden, width, total):
    p = {"m.moe.gate": r.randn(hidden, total).astype(np.float32) * 0.5}
    for w, shape in (("w_gate", (total, hidden, width)),
                     ("w_up", (total, hidden, width)),
                     ("w_down", (total, width, hidden))):
        p["m.moe." + w] = r.randn(*shape).astype(np.float32) * 0.2
    return p


@pytest.mark.parametrize("total,held,k", [(8, 2, 2), (64, 16, 8)])
def test_the_4_shares_add_up_to_the_uncut_layer(total, held, k):
    """Four shares' parts equal the reference's layer with all the experts
    held, under the softmax router, renormalised: the published 64
    experts 16 a share and 8 a token, and a small layer. There is no
    shared expert to count once."""
    import paddle_tpu as fluid

    r = np.random.RandomState(total)
    hidden, width, shares = 16, 8, 4
    assert total == shares * held
    p = _softmax_layer(r, hidden, width, total)
    u = r.randn(2, 24, hidden).astype(np.float32)
    x = fluid.layers.data("u", list(u.shape), append_batch_size=False)
    outs = []
    for lo in range(0, total, held):
        outs += fluid.layers.moe_experts(
            x, experts_total=total, experts_held=held, d_ff=width, k=k,
            held_from=lo, score_func="softmax",
            param_attr=fluid.ParamAttr(name=f"share{lo}"))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    for lo in range(0, total, held):
        assert not np.asarray(scope.get(f"share{lo}.bias")).any()
        scope.set(f"share{lo}.gate", p["m.moe.gate"])
        for w in ("w_gate", "w_up", "w_down"):
            scope.set(f"share{lo}.{w}", p[f"m.moe.{w}"][lo:lo + held])
    got = exe.run(feed={"u": u}, fetch_list=outs)
    routed, loads = got[0::2], got[1::2]
    assert len(routed) == shares
    assert int(np.sum(loads)) == u.shape[0] * u.shape[1] * k
    layer = {"num_experts_per_tok": k, "num_experts": total, "held_from": 0,
             "norm_topk_prob": True}
    uncut = highest(adapter.expert_ffn, p, u, "m", layer)
    assert rel(sum(routed), uncut) < 1e-5
    # and one share alone is the reference's share
    p_share = dict(p, **{f"m.moe.{w}": p[f"m.moe.{w}"][held:2 * held]
                         for w in ("w_gate", "w_up", "w_down")})
    one = highest(adapter.expert_ffn, p_share, u, "m",
                  dict(layer, num_experts=held, held_from=held))
    assert rel(routed[1], one) < 1e-5


@pytest.mark.parametrize("total,held,tokens,k,rows", [
    (256, 8, 4096, 8, 8192),    # Kimi's share: today's quarter
    (128, 8, 8192, 8, 16384),   # Trinity's: today's quarter
    (64, 16, 8192, 8, 28672),   # a chip of a four-chip host: 1.75 balanced loads
    (8, 2, 96, 2, 84),          # the rehearsal's
    (4, 4, 10, 2, 20),          # everything held: one block holds it all
    (1024, 1, 3, 1, 1),         # never none
])
def test_block_rows_follow_the_share_held(total, held, tokens, k, rows):
    from paddle_tpu.parallel.moe import _block_rows

    assert _block_rows(tokens * k, held / total) == rows


@pytest.mark.parametrize("pushed,blocks", [(0.0, 1), (10.0, 3)])
def test_a_load_past_the_first_block_is_still_dropless(pushed, blocks):
    """4 of 16 experts held under the softmax router, so a block of 7/16
    of the assignments: with the held experts' logits pushed up every
    assignment lands here, three blocks' worth (the last one runs past
    the assignments' end), and the output and the
    gradients in x, the three weights and the router's gate are still
    those of the loop over the experts."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import moe

    r = np.random.RandomState(11)
    hidden, width, total, held, k, tokens = 16, 8, 16, 4, 2, 64
    p = _softmax_layer(r, hidden, width, total)
    p = {n: v[:held] if n.startswith("m.moe.w_") else v for n, v in p.items()}
    u = r.randn(tokens, hidden).astype(np.float32)
    # a column of ones in u, and the held experts' logits read it
    u[:, 0] = 1.0
    p["m.moe.gate"][0, :held] += pushed
    cotangent = r.randn(tokens, hidden).astype(np.float32)
    model = {"num_experts_per_tok": k, "num_experts": held, "held_from": 0,
             "norm_topk_prob": True}
    trained = ("m.moe.gate", "m.moe.w_gate", "m.moe.w_up", "m.moe.w_down")

    def ours(u, *w):
        q = dict(p, **dict(zip(trained, w)))
        y, load = moe.moe_experts(
            u, q["m.moe.gate"], jnp.zeros(total), q["m.moe.w_gate"],
            q["m.moe.w_up"], q["m.moe.w_down"], k=k, scaling=1.0,
            experts_held=held, held_from=0, score_func="softmax")
        return jnp.sum(y * cotangent), (y, load)

    def theirs(u, *w):
        y = adapter.expert_ffn(dict(p, **dict(zip(trained, w))), u, "m", model)
        return jnp.sum(y * cotangent), y

    args = (u, *(p[n] for n in trained))
    grad = range(len(args))
    (_, (y, load)), got = jax.jit(jax.value_and_grad(
        ours, grad, has_aux=True))(*args)
    (_, want_y), want = highest(jax.jit(jax.value_and_grad(
        theirs, grad, has_aux=True)), *args)
    rows = moe._block_rows(tokens * k, held / total)
    assert rows == 56 and -(-int(np.sum(load)) // rows) == blocks, load
    assert rel(y, want_y) < 1e-5
    for name, g, w in zip(("x", *trained), got, want):
        assert np.abs(w).max() > 0, name
        assert rel(g, w) < 1e-5, name


# ----------------------------------------------- the cell's arithmetic


def test_counters_and_flops_of_the_cell():
    from paddle_tpu import profiler

    model, traffic = SUITE.cell(rehearse=False)
    assert (traffic["batch"], traffic["seq_len"]) == (1, 8192)
    assert adapter.held_layers(model) == [
        (0, 1024, "sliding_attention"), (1, 1024, "sliding_attention"),
        (2, 1024, "sliding_attention"), (3, 0, "full_attention")]
    # ISSUE 37's arithmetic, redone: attention 21.23M a layer, the router
    # 0.147M, two held experts' worth a token (8 x 16 / 64 of 6.19M)
    # 12.39M, the head 56.62M
    per_token = adapter.matrix_params_per_token(model)
    assert per_token == 4 * (21233664 + 147456 + 2 * 6193152) + 2304 * 24576
    held = 4 * (21233664 + 147456 + 16 * 6193152 + 2 * 2304 + 2 * 128) + (
        2 * 2304 * 24576 + 2304)
    assert abs(held / 1e6 - 595.2) < 0.05  # the parameters held
    # a window layer's queries see min(i + 1, 1024) keys
    assert adapter.admitted_pairs(8192, 1024) == sum(
        min(i + 1, 1024) for i in range(8192))
    assert adapter.admitted_pairs(8192, 0) == 8192 * 8193 // 2
    pairs = 3 * adapter.admitted_pairs(8192, 1024) + 8192 * 8193 // 2
    assert abs(pairs / (4 * 8192 * 8192) - 0.213) < 0.001  # the masks admit
    assert abs(adapter.admitted_pairs(8192, 1024) / 8192 ** 2 - 0.117) < 0.001
    flops = adapter.flops_per_example(model, traffic)
    assert flops == 3.0 * (2 * 8192 * per_token + pairs * 32 * 4 * 128)
    assert 12.0e12 < flops < 12.5e12

    c0 = profiler.counters()
    small, small_traffic = SUITE.cell()
    main, _, built, exe, _ = SUITE.built_model(small, small_traffic)
    batch = SUITE.batch_for(small, small_traffic)
    loads = exe.run(main, feed=batch, fetch_list=built["loads"])
    c1 = settled_counters()

    def bumped(name):
        return c1.get(name, 0) - c0.get(name, 0)

    # four layers, the forward op's lowering and the gradient op's replay
    assert bumped("moe_dispatch_grouped") == 8
    # ... and the step's own count of the rows the held experts took
    assert bumped("moe_rows_live") == sum(int(np.sum(load)) for load in loads)
    # on the plain path: the rehearsal's widths are no lane multiple, and
    # there is no Mosaic here (ops/pallas/grouped_matmul.py)
    assert bumped("moe_dispatch_gmm") == 0
    assert bumped("moe_route_softmax") == 8
    # one full layer, forward and replay
    assert bumped("attn_rope_scaled") == 2
    assert bumped("attn_dispatch_xla") == 8
    assert (c1["moe_experts_held"], c1["moe_experts_total"]) == (2, 8)
    assert c1["attn_kv_group"] == 2
    # 2 x 48 tokens x 2 a token = 192 assignments, 2 of 8 held: 7/16 of them
    assert c1["moe_block_rows"] == 84
    # and no counter that is another decoder's: no compressed query, no
    # rotation by pairs, no convolution at all
    for other in ("attn_latent_q_lora", "rope_interleaved",
                  "short_conv_linear_calls"):
        assert c1.get(other, 0) == c0.get(other, 0), other
    assert len(loads) == 4 and all(x.shape == (2,) for x in loads)


if __name__ == "__main__":
    main(SUITE)
