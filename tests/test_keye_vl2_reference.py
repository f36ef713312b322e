"""Keye-VL-2.0's language model against its plain reference
(`benchmark/models/keye_vl2.py`) at the rehearsal size of the cell
`keye_vl2_ep16_s8192` (`topk` 16 below its 48 and 80 tokens, so the
selection bites): what every decoder suite holds (`tests/decoder_suite.py`:
the sparse attention block and the expert layer alone, the whole model in
float32 and under AMP, one train step's gradients for every kind of
parameter, each wrong model that shows in the logits caught by the cell's
tolerance) on this model's data, and its own: the selected sets against the
reference's sort; the indexer's loss and the wrong models that show in it
alone; the two stop-gradients, read off the gradients (the indexer's
parameters moved by its loss alone, every other by the language-model
loss alone, and the two wrong models that break that); `mrope_section`
against the plain rotation; the selection's bisection against a sort; the
attention op with an admission, on the plain path and in the kernels; the
shares against the uncut layer; the cell's counters, gauges and FLOPs.

Run as a script on the attached TPU (`tests/decoder_suite.py` has the
arguments): the readings that place `TOLERANCE` (each with the share of
pairs on which program and reference select differently), the held loads
and the two loss terms over a window, the gradient comparison:

    python3 tests/test_keye_vl2_reference.py readings [seed ...]
    python3 tests/test_keye_vl2_reference.py loads[@rate] [seed ...]
    python3 tests/test_keye_vl2_reference.py gradients
    python3 tests/test_keye_vl2_reference.py pairs [seed ...]
    python3 tests/test_keye_vl2_reference.py index_loss [seed ...]
"""

from __future__ import annotations

import numpy as np
import pytest

from decoder_suite import *  # noqa: F401,F403 — the shared cases, on SUITE
from decoder_suite import compiled, f32, guards, highest, main, rel, state

from benchmark.models import keye_vl2 as adapter  # noqa: E402

CELL = "keye_vl2_ep16_s8192"

# as Mellum's suite: at 64 wide, seeded as the cell is, a layer adds a
# thousandth of the residual stream and no wrong model shows. With the
# matrices at 0.1 and the embedding at 0.3 the layers weigh in the stream
# as at the published width, and the indexer's scores spread enough that
# the 16 largest of a row are no accident of rounding.
AS_AT_WIDTH = {"initializer_range": 0.1, "embedding_initializer_range": 0.3}

INDEXER = (".indexer.q.w_0", ".indexer.k.w_0", ".indexer.w.w_0",
           ".indexer.k_norm.w_0", ".indexer.k_norm.b_0")

# the wrong models by where they show: in the logits (the selection or the
# scores change), in the indexer's loss alone, in the gradients alone
IN_LOGITS = ("dense_attention", "select_before_causal", "no_relu",
             "unit_index_weights", "no_key_layernorm")
IN_INDEX_LOSS = ("kl_over_all_keys", "target_mean_of_logits")
# (wrong model, the ending of a parameter it moves the gradient of)
IN_GRADIENTS = (("target_not_detached", ".attn.q.w_0"),
                ("indexer_reads_live_stream", ".input_norm.w_0"))


def _positions(b, s):
    return np.broadcast_to(np.arange(s), (3, b, s))


def _mixer_program(which, model, batch, seq):
    """The sparse attention block or the expert layer alone in a Program:
    `u` in, `y` out."""
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_parts

    cfg = adapter.config(model)
    u = fluid.layers.data("u", [batch, seq, cfg.hidden_size],
                          append_batch_size=False)
    if which == "experts":
        return decoder_parts.expert_ffn(u, cfg, "m")[0]
    return decoder_parts.sparse_attention(u, cfg, "m", cfg.rope_theta)[0]


def _want_mixer(which, p, feeds, model, wrong=()):
    u = feeds["u"]
    if which == "experts":
        return highest(adapter.expert_ffn, p, u, "m", model)
    return highest(adapter.sparse_attention, p, u, "m", model,
                   _positions(*u.shape[:2]), wrong)[0]


KINDS = {
    "embedding": ("keye.embed",), "head": ("keye.head.w_0",),
    "rms_norm": (".input_norm.w_0", ".post_attn_norm.w_0", "final_norm.w_0"),
    "qk_norm": (".q_norm.w_0", ".k_norm.w_0"),
    "attention": (".attn.q.w_0", ".attn.k.w_0", ".attn.v.w_0",
                  ".attn.o.w_0"),
    "indexer": INDEXER[:3], "indexer_norm": INDEXER[3:],
    "router": (".moe.gate",),
    "experts": (".moe.w_gate", ".moe.w_up", ".moe.w_down"),
}


def _split_gradients(step):
    """What `on_gradients` adds to the shared case: the reference's two
    terms differentiated apart. The indexer's parameters get exactly the
    indexer loss's gradient and nothing of the language-model loss; every
    other parameter the reverse; the program's step moved each by its
    own."""
    import jax

    def term(which):
        def fn(p):
            t = adapter.reference_terms(p, step.batch, step.model)
            return t[which] / t["count"]
        return f32(compiled(jax.grad(fn), step.before))

    lm, index = term("nll"), term("index")
    for n in step.want:
        mine, other = (index, lm) if n.endswith(INDEXER) else (lm, index)
        if n.endswith(".moe.bias"):
            continue
        assert not np.abs(other[n]).any(), n
        assert np.abs(mine[n]).max() > 0, n
        assert rel(step.got[n], mine[n]) < 2e-4, n
    # and the wrong models that leave the forward pass as it is
    for wrong, moved in IN_GRADIENTS:
        theirs = f32(compiled(jax.grad(lambda p: SUITE.loss(
            p, step.batch, step.model, wrong=(wrong,))), step.before))
        worst = max(rel(step.got[n], theirs[n]) for n in step.want
                    if n.endswith(moved))
        assert worst > 0.01, (wrong, worst)


SUITE = Suite(  # noqa: F405
    CELL, adapter, kinds=KINDS, as_at_width=AS_AT_WIDTH,
    mixers=("sparse", "experts"),
    mixer_program=_mixer_program, want_mixer=_want_mixer,
    wrong_by_mixer={"sparse": IN_LOGITS},
    # the reference with its last layer left out, or with one of the
    # departures that change the selection or the scores: refused by the
    # cell's tolerance
    wrong={"drop_layers": caught(amp=0, drop_layers=1),  # noqa: F405
           **{w: caught(float32=100, amp=0, wrong=(w,))  # noqa: F405
              for w in IN_LOGITS}},
    amp_loss_room=1,
    on_gradients=_split_gradients,
    seed=60001,
    step_counters=("attn_dispatch_flash", "attn_qk_prep_fused",
                   "attn_qk_prep_handed_back", "attn_qk_prep_rope_only",
                   "moe_dispatch_grouped",
                   "moe_dispatch_gmm", "moe_route_softmax",
                   "sparse_attn_layers", "attn_pairs_admitted",
                   "attn_pairs_causal", "index_kl_kernel_calls",
                   "index_kl_fused"),
    gauges=("attn_kv_group", "sparse_attn_topk", "sparse_index_heads",
            "loss_terms", "moe_block_rows", "moe_experts_held",
            "moe_experts_total", "flash_blocks_visited",
            "flash_blocks_total"))


# ------------------------------------------- the selection and the loss


def test_float32_program_and_reference_select_the_same_sets(float32_run):
    """In float32 on the CPU the two select the same keys, pair for pair,
    in every layer: the selection through the layers API on the
    reference's own scores is the reference's sort, and the whole model's
    loss terms agree to float32's rounding, which a single pair on the
    other side of a threshold would not leave."""
    import paddle_tpu as fluid

    model, batch, p, got = float32_run
    terms = highest(adapter.reference_terms, p, batch, model)
    b, s = batch["tokens"].shape
    k = model["sa_config"]["topk"]
    assert k < s
    for kept in terms["kept"]:
        # min(t + 1, K) keys a query, and more only on ties at the
        # threshold: with two indexer heads a quarter of the scores are
        # exactly 0 (both relus shut), and a threshold of 0 keeps them all
        least = np.broadcast_to(np.minimum(np.arange(s) + 1, k), (b, s))
        sizes = kept.sum(-1)
        assert (sizes >= least).all() and (sizes == least).mean() > 0.7
    lm, index = (float(np.asarray(x).reshape(-1)[0]) for x in got[2:4])
    assert abs(lm - terms["nll"] / terms["count"]) < 1e-5
    assert abs(index - terms["index"] / terms["count"]) < 1e-5
    assert index > 0.01  # a real term, not rounding
    # the op on the reference's scores against the reference's sort
    a = adapter._rms(p["keye.embed"][batch["tokens"]],
                     p["keye.layer0.input_norm.w_0"], model["rms_norm_eps"])
    scores = highest(adapter.index_scores, p, a, "keye.layer0.attn", model,
                     _positions(b, s))
    causal = np.tril(np.ones((s, s), bool))
    with guards():
        x = fluid.layers.data("x", [b, s, s], append_batch_size=False)
        admit, tau = fluid.layers.sparse_select(x, k)
        exe = fluid.Executor(fluid.CPUPlace())
        got_admit, got_tau = exe.run(
            feed={"x": np.where(causal, scores, -np.inf).astype(np.float32)},
            fetch_list=[admit, tau])
    np.testing.assert_array_equal(got_admit.astype(bool), terms["kept"][0])
    assert np.isneginf(got_tau[:, :k]).all()
    assert np.isfinite(got_tau[:, k:]).all()


@pytest.mark.parametrize("wrong", IN_INDEX_LOSS)
def test_a_wrong_indexer_loss_is_caught(float32_run, wrong):
    """The dense stage's loss (the indexer's softmax over every causal
    key) and a target averaged over the heads before the softmax leave
    the logits as they are and move the indexer's loss by more than 1% of
    itself, fifty times what the float32 program is held to."""
    model, batch, p, got = float32_run
    index = float(np.asarray(got[3]).reshape(-1)[0])
    right = highest(adapter.reference_terms, p, batch, model)
    theirs = highest(adapter.reference_terms, p, batch, model, wrong=(wrong,))
    np.testing.assert_array_equal(right["logits"], theirs["logits"])
    want = right["index"] / right["count"]
    assert abs(index - want) < 2e-4 * want
    assert abs(index - theirs["index"] / theirs["count"]) > 0.01 * want


def _pairs_differing(admits, kept):
    """By layer, the share of the reference's admitted pairs on which the
    program's selection differs from it (either way)."""
    return [float(np.sum((np.asarray(a) != 0) != (np.asarray(k) != 0))
                  / np.sum(np.asarray(k) != 0))
            for a, k in zip(admits, kept)]


def test_under_amp_the_loss_terms_and_the_pairs_that_change_sides():
    """In float32 no pair differs; under bf16 AMP a few pairs near the
    threshold change sides: under 6% of the admitted pairs a layer here
    (the chip readings report the share at the published widths), and the
    indexer's loss stays within 3% of the reference's."""
    for precision, limit in (("float32", 0.0), ("bf16_amp", 0.06)):
        model, traffic = SUITE.cell(precision=precision, **AS_AT_WIDTH)
        with guards():
            _, eval_prog, built, exe, names = SUITE.built_model(model, traffic)
            batch = SUITE.batch_for(model, traffic)
            got = exe.run(eval_prog, feed=batch,
                          fetch_list=built["terms"] + built["admits"])
            p = state(names)
        terms = highest(adapter.reference_terms, p, batch, model)
        shares = _pairs_differing(got[2:], terms["kept"])
        assert len(shares) == 2 and max(shares) <= limit, (precision, shares)
        if limit:
            assert max(shares) > 0  # rounding does move a pair
        index = float(np.asarray(got[1]).reshape(-1)[0])
        want = terms["index"] / terms["count"]
        assert abs(index - want) < (0.03 if limit else 2e-4) * want


def _chip_pairs(seeds, wrong=False):
    """At the published widths on the attached TPU: the program in bf16
    AMP against the float32 reference on the same parameters, the two loss
    terms and, layer by layer, the share of the reference's admitted pairs
    on which the two select differently; with `wrong` (`index_loss`) the
    indexer's loss of the wrong models that show in it alone beside
    them."""
    import jax

    import paddle_tpu as fluid

    model, traffic = SUITE.cell(rehearse=False)
    for seed in seeds:
        with guards():
            _, eval_prog, built, exe, names = SUITE.built_model(
                model, traffic, seed, fluid.TPUPlace(), as_seeded=True)
            batch = SUITE.batch_for(model, traffic, seed)
            got = exe.run(eval_prog, feed=batch,
                          fetch_list=built["terms"] + built["admits"])
            p = state(names)
        with jax.default_matmul_precision("highest"):
            terms = jax.jit(lambda p, b: adapter.reference_terms(
                p, b, model))(p, batch)
        lm, index = (float(np.asarray(x).reshape(-1)[0]) for x in got[:2])
        count = float(terms["count"])
        shares = _pairs_differing(got[2:], terms["kept"])
        sizes = [int(np.asarray(a, np.int64).sum()) for a in got[2:]]
        print(f"seed {seed}: lm_loss {lm:.5f} (reference "
              f"{float(terms['nll']) / count:.5f}), index_loss {index:.5f} "
              f"(reference {float(terms['index']) / count:.5f}); pairs "
              f"admitted by layer {sizes} of "
              f"{adapter.admitted_pairs(traffic['seq_len'], model['sa_config']['topk'])}"
              f" without ties; share of the reference's pairs selected "
              f"differently by layer "
              + " ".join(f"{x:.5f}" for x in shares), flush=True)
        for w in IN_INDEX_LOSS if wrong else ():
            with jax.default_matmul_precision("highest"):
                theirs = jax.jit(lambda p, b: adapter.reference_terms(
                    p, b, model, wrong=(w,))["index"])(p, batch)
            print(f"seed {seed} {w}: index_loss {float(theirs) / count:.5f}",
                  flush=True)


# ----------------------------------------------------------- positions


def test_mrope_with_three_equal_channels_is_the_plain_rotation():
    """`mrope_section` [2, 3, 3] over a head of 16: with the three
    channels all 0..s-1 the angles are the plain rotation's, which is what
    the program's op makes; with the channels unequal they are not."""
    import paddle_tpu as fluid

    b, s, d, theta = 2, 48, 16, 1e7
    plain = np.arange(s)[None, :, None] * (
        1.0 / theta ** (np.arange(0, d, 2) / d))[None, None, :]
    same = highest(adapter.mrope_angles, _positions(b, s), d, theta,
                   [2, 3, 3])
    np.testing.assert_allclose(same, np.broadcast_to(plain, same.shape),
                               rtol=1e-6)
    uneven = np.stack([np.broadcast_to(np.arange(s), (b, s)),
                       np.broadcast_to(np.arange(s) // 4, (b, s)),
                       np.broadcast_to(np.arange(s) % 4, (b, s))])
    other = highest(adapter.mrope_angles, uneven, d, theta, [2, 3, 3])
    np.testing.assert_allclose(other[..., :2], same[..., :2], rtol=1e-6)
    assert rel(other[..., 2:], same[..., 2:]) > 0.5
    # the program's rotation on a head against the reference's on text
    x = np.random.RandomState(0).randn(b, s, 3, d).astype(np.float32)
    data = fluid.layers.data("x", list(x.shape), append_batch_size=False)
    y = fluid.layers.rotary_embedding(data, theta=theta)
    (got,) = fluid.Executor(fluid.CPUPlace()).run(feed={"x": x},
                                                  fetch_list=[y])
    assert rel(got, highest(adapter._rope, x, same)) < 1e-6
    assert rel(got, highest(adapter._rope, x, other)) > 0.1
    with pytest.raises(ValueError, match="mrope_section"):
        adapter.mrope_angles(_positions(b, s), d, theta, [2, 3, 4])


# ---------------------------------------------------------- the new ops


@pytest.mark.parametrize("n,k", [(64, 1), (64, 16), (64, 64), (300, 37)])
def test_the_kth_largest_by_bisection_is_the_sorts(n, k):
    """Exact on positive, negative, zero, repeated and infinite values."""
    import jax.numpy as jnp

    from paddle_tpu.ops import sparse_attn_ops as ops

    r = np.random.RandomState(n + k)
    x = r.randn(7, n).astype(np.float32)
    x[0, :n // 2] = x[0, 0]  # ties across the threshold
    x[1] = np.abs(x[1])
    x[2, ::3] = 0.0
    x[2, 1::3] = -0.0
    x[3, : n - k] = -np.inf
    x[4] = np.round(x[4])
    keys = ops.sortable(jnp.asarray(x))
    order = np.asarray(keys)  # a smaller float is a smaller key
    assert np.all((x[:, :, None] < x[:, None, :])
                  <= (order[:, :, None] < order[:, None, :]))
    got = np.asarray(ops.unsortable(ops.kth_largest(keys, k)))
    want = np.sort(x, -1)[:, n - k]
    assert np.array_equal(got, want)
    assert np.array_equal(np.asarray(ops.unsortable(keys)), x)


def test_select_keeps_ties_and_every_key_of_a_short_row():
    from paddle_tpu.ops import sparse_attn_ops as ops

    s, k = 12, 4
    index = np.where(np.tril(np.ones((s, s), bool)), 0.0, -np.inf)[None]
    index = index.astype(np.float32)
    index[0, 8, :3] = 2.0  # three above, nine tied at the threshold
    index[0, 9, :6] = 1.0
    admit, tau = ops.select(index, k)
    admit, tau = np.asarray(admit), np.asarray(tau)
    assert np.isneginf(tau[0, :k]).all() and (tau[0, k:] <= 1.0).all()
    assert admit[0, 3].sum() == 4 and admit[0, 5].sum() == 6  # ties: all
    assert admit[0, 8].sum() == 9 and tau[0, 8] == 0.0
    assert admit[0, 9].sum() == 6 and tau[0, 9] == 1.0
    assert not np.triu(admit[0], 1).any()


def test_index_scores_and_index_kl_in_blocks_equal_the_whole(monkeypatch):
    """The ops' functions at a block of 16 queries against one block, and
    `index_kl`'s gradient against the formula: `(softmax over the
    admitted - p)` a pair."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import sparse_attn_ops as ops

    r = np.random.RandomState(3)
    b, s, h, g, d, hi, di, k = 2, 64, 4, 2, 16, 2, 8, 16
    q, kk = r.randn(b, s, h, d), r.randn(b, s, g, d)
    qi, ki, w = r.randn(b, s, hi, di), r.randn(b, s, di), r.randn(b, s, hi)
    q, kk, qi, ki, w = (jnp.asarray(x, jnp.float32)
                        for x in (q, kk, qi, ki, w))
    whole = ops.index_scores(qi, ki, w, 0.25)
    admit, _ = ops.select(whole, k)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(kk, h // g, 2)) / 4
    scores = jnp.where(admit[:, None] != 0, scores, -jnp.inf)
    lse = jax.nn.logsumexp(scores, -1)
    kl = lambda index: ops.index_kl_rows(q, kk, lse, index, admit, 0.25)
    rows, grad = kl(whole), jax.grad(lambda i: jnp.sum(kl(i)))(whole)
    monkeypatch.setattr(ops, "QUERY_BLOCK", 16)
    assert len(ops.query_blocks(s)) == 4
    assert rel(ops.index_scores(qi, ki, w, 0.25)[:, :, :1], whole[:, :, :1]) \
        < 1e-6
    np.testing.assert_allclose(
        np.tril(np.asarray(ops.index_scores(qi, ki, w, 0.25))),
        np.tril(np.asarray(whole)), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(kl(whole), rows, rtol=1e-5, atol=1e-6)
    p = jnp.mean(jax.nn.softmax(scores, -1), 1)
    soft = jax.nn.softmax(jnp.where(admit != 0, whole, -jnp.inf), -1)
    np.testing.assert_allclose(grad, jnp.where(admit != 0, soft - p, 0.0),
                               rtol=1e-4, atol=1e-6)
    assert float(jnp.min(rows)) > -1e-6  # a divergence


# -------------------------------- the attention op with an admission


def _attn_with_admission(fluid, shape, group, layout="bshd"):
    b, s, h, d = shape
    q = fluid.layers.data("q", [b, s, h, d], append_batch_size=False)
    k = fluid.layers.data("k", [b, s, h // group, d], append_batch_size=False)
    v = fluid.layers.data("v", [b, s, h // group, d], append_batch_size=False)
    for t in (q, k, v):
        t.stop_gradient = False
    plain = fluid.layers.fused_multihead_attention(
        q, k, v, causal=True, sm_scale=d ** -0.5, layout=layout)
    admit = fluid.layers.data("admit", [b, s, s], dtype="int8",
                              append_batch_size=False)
    chosen, lse = fluid.layers.fused_multihead_attention(
        q, k, v, causal=True, sm_scale=d ** -0.5, layout=layout, admit=admit,
        admit_keys=s, return_lse=True)
    return plain, chosen, lse


@pytest.mark.parametrize("path", ["xla", "flash"])
def test_an_admission_of_every_causal_pair_changes_no_bit(path, attn_path,
                                                          monkeypatch):
    """`fused_multihead_attention` with an admission of every causal pair
    gives, bit for bit in float32, what the call without one gives, on the
    plain path and in the kernels (the interpreter; the next case has the
    gradients); with a real selection it is the explicit softmax over the
    kept keys, and the log-sum-exp rows are those scores'."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid

    if path == "flash":
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    attn_path(path)
    b, s, h, d, group = 2, 160, 4, 16, 2
    plain, chosen, lse = _attn_with_admission(fluid, (b, s, h, d), group)
    r = np.random.RandomState(0)
    feed = {"q": r.randn(b, s, h, d), "k": r.randn(b, s, h // group, d),
            "v": r.randn(b, s, h // group, d)}
    feed = {n: x.astype(np.float32) for n, x in feed.items()}
    exe = fluid.Executor(fluid.CPUPlace())
    every = np.tril(np.ones((s, s), np.int8))[None].repeat(b, 0)
    got_plain, got_all = exe.run(feed=dict(feed, admit=every),
                                 fetch_list=[plain, chosen])
    assert np.array_equal(got_plain, got_all)
    # a real selection: the 24 keys a random score ranks highest
    from paddle_tpu.ops import sparse_attn_ops as ops

    score = np.where(np.tril(np.ones((s, s), bool)), r.randn(b, s, s),
                     -np.inf).astype(np.float32)
    admit = np.asarray(ops.select(score, 24)[0])
    got, got_lse = exe.run(feed=dict(feed, admit=admit),
                           fetch_list=[chosen, lse])
    q, k, v = (jnp.asarray(feed[n]) for n in "qkv")
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, group, 2)) \
        * d ** -0.5
    scores = jnp.where(admit[:, None] != 0, scores, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                      jnp.repeat(v, group, 2))
    assert rel(got, want) < 2e-5
    assert got_lse.shape == (b, h, s)
    np.testing.assert_allclose(got_lse, jax.nn.logsumexp(scores, -1),
                               rtol=2e-5, atol=2e-5)
    assert rel(got, got_plain) > 0.1


@pytest.mark.parametrize("fused", [True, False])
def test_the_kernels_gradients_under_an_admission(fused, monkeypatch):
    """dq, dk, dv of the flash kernels under a selection, the one-visit
    backward and the pair, against `jax.grad` of the explicit softmax; an
    admission of every causal pair gives the bits of the call without
    one."""
    import importlib

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import sparse_attn_ops as ops

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    if not fused:
        monkeypatch.setattr(fa, "_BWD_FUSED_VMEM_BYTES", 0)
    r = np.random.RandomState(1)
    b, h, g, s, d = 1, 4, 2, 320, 32
    q, k, v = (jnp.asarray(r.randn(b, n, s, d), jnp.float32)
               for n in (h, g, g))
    score = np.where(np.tril(np.ones((s, s), bool)), r.randn(b, s, s),
                     -np.inf).astype(np.float32)
    admit = ops.select(score, 40)[0]
    cot = jnp.asarray(r.randn(b, h, s, d), jnp.float32)

    def ours(q, k, v, admit):
        return jnp.sum(fa.flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128, admit=admit,
            admit_keys=40) * cot)

    def theirs(q, k, v):
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, h // g, 1)) \
            * d ** -0.5
        scores = jnp.where(admit[:, None] != 0, scores, -jnp.inf)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(
            scores, -1), jnp.repeat(v, h // g, 1)) * cot)

    got = jax.grad(ours, (0, 1, 2))(q, k, v, admit)
    want = jax.grad(theirs, (0, 1, 2))(q, k, v)
    for name, x, y in zip("qkv", got, want):
        assert rel(x, y) < 2e-5, name
    every = jnp.asarray(np.tril(np.ones((b, s, s), np.int8)))
    plain = jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128) * cot), (0, 1, 2))(
            q, k, v)
    for x, y in zip(jax.grad(ours, (0, 1, 2))(q, k, v, every), plain):
        assert np.array_equal(x, y)


def test_the_kernels_declare_the_admitted_pairs_and_the_operands_bytes(
        monkeypatch):
    """With an admission the flash kernels' declared FLOPs count
    `sum_t min(t + 1, K)` pairs a head, a window's count, and the bytes
    one more operand of a byte a pair, once."""
    import importlib

    import jax.numpy as jnp

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    b, h, g, s, d, k = 1, 8, 2, 1024, 128, 256
    q = jnp.zeros((b * h, s, d), jnp.bfloat16)
    kk = jnp.zeros((b * g, s, d), jnp.bfloat16)
    admit = jnp.zeros((b, s, s), jnp.int8)
    masks = fa._Masks.of(s, s, causal=True, causal_offset=0, window=0,
                         block_q=512, block_k=512)
    for kernel in fa._PRODUCTS:
        plain = fa._cost(kernel, q, kk, None, masks, (s, s, d, d))
        window = fa._cost(kernel, q, kk, None, masks._replace(window=k),
                          (s, s, d, d))
        chosen = fa._cost(kernel, q, kk, None, masks, (s, s, d, d), admit, k)
        assert chosen.flops == window.flops < plain.flops
        assert chosen.bytes_accessed == plain.bytes_accessed + b * s * s
    assert adapter.admitted_pairs(s, k) * b * h * 4 * d == fa._cost(
        "flash_fwd", q, kk, None, masks, (s, s, d, d), admit, k).flops


@pytest.mark.parametrize("head_dim", [None, 128],
                         ids=["rehearsal_heads", "heads_of_128"])
def test_the_path_the_chip_takes_in_the_interpreter_is_the_reference(
        attn_path, monkeypatch, head_dim):
    """One 512-token row with the interpreter on and the attention op on
    its flash path: the four kernels of `ops/pallas/sparse_index.py` and
    the flash kernels with their admission and their log-sum-exp rows, as
    on the chip; the block's output and the indexer's loss against the
    reference's, and the counters that say the kernels ran. At the
    published 128 lanes a head q and k are normed and turned by `qk_prep`
    and `index_kl` reads the pair it hands back; at the rehearsal's 64
    the `jnp` preparation's, transposed."""
    import paddle_tpu as fluid
    from paddle_tpu import profiler
    from paddle_tpu.models import decoder_parts

    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    attn_path("flash")
    model, _ = SUITE.cell(**AS_AT_WIDTH)
    if head_dim:  # and the published sections of its 64 frequencies
        model.update(head_dim=head_dim, rope_scaling={
            **model["rope_scaling"], "mrope_section": [16, 24, 24]})
    cfg = adapter.config(model)
    b, s = 1, 512
    u = fluid.layers.data("u", [b, s, cfg.hidden_size],
                          append_batch_size=False)
    y, kl, _ = decoder_parts.sparse_attention(u, cfg, "m", cfg.rope_theta)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    names = [p.name for p in
             fluid.default_main_program().global_block().all_parameters()]
    feed = {"u": np.random.RandomState(2).randn(
        b, s, cfg.hidden_size).astype(np.float32)}
    c0 = profiler.counters()
    got, got_kl = exe.run(feed=feed, fetch_list=[y, kl])
    c1 = profiler.counters()
    for name in ("sparse_index_kernel_calls", "index_kl_kernel_calls",
                 "index_kl_fused", "attn_dispatch_flash"):
        assert c1.get(name, 0) - c0.get(name, 0) == 1, name
    # 64 lanes a head at the rehearsal's widths: `qk_prep` refuses them
    for name in ("attn_qk_prep_fused", "attn_qk_prep_handed_back"):
        assert c1.get(name, 0) - c0.get(name, 0) == (head_dim == 128), name
    assert c1.get("attn_qk_prep_rope_only", 0) == c0.get(
        "attn_qk_prep_rope_only", 0)
    ops = [op.type for op in
           fluid.default_main_program().global_block().ops]
    assert ops.count("rotary_embedding") == 2  # the indexer's own
    assert ops.count("rms_norm") == 0 and ops.count("transpose2") == 0
    want, want_kl, kept = highest(
        adapter.sparse_attention, state(names), feed["u"], "m", model,
        _positions(b, s))
    # the selection bites: 16 keys a query, more where the threshold is
    # an exact 0 (both of the rehearsal's two relus shut) and ties stay
    assert np.median(kept.sum(-1)) == 16
    assert rel(got, want) < 2e-5
    assert abs(got_kl.sum() - want_kl) < 2e-4 * want_kl


# ------------------------------------------ the shares and the uncut model


def test_the_16_shares_add_up_to_the_uncut_layer_and_the_8_slices_to_the_logits():
    """A layer at 32 experts, 2 a share: the 16 shares' routed parts, with
    attention and the indexer counted once, add up to the reference's
    layer with every expert held; and the eight vocabulary slices' logits
    side by side are the uncut reference's."""
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_parts

    model, _ = SUITE.cell(**AS_AT_WIDTH)
    total, held, shares, slices, vocab = 32, 2, 16, 8, 128
    model = dict(model, num_experts_published=total, num_experts=held)
    b, s, hidden = 2, 48, model["hidden_size"]
    r = np.random.RandomState(5)
    x = fluid.layers.data("x", [b, s, hidden], append_batch_size=False)
    cfg = adapter.config(model)
    mixed = decoder_parts.sparse_attention(
        decoder_parts.norm(x, "m.input_norm", cfg), cfg, "m.attn",
        cfg.rope_theta)[0]
    h1 = fluid.layers.elementwise_add(x, mixed)
    u = decoder_parts.norm(h1, "m.post_attn_norm", cfg)
    routed = []
    for share in range(shares):
        cfg_share = adapter.config(dict(model, held_from=share * held))
        routed.append(decoder_parts.expert_ffn(u, cfg_share,
                                               f"share{share}")[0])
    final = decoder_parts.norm(fluid.layers.elementwise_add(
        h1, fluid.layers.sums(routed)), "m.final_norm", cfg)
    logits = [decoder_parts.proj(final, vocab // slices, f"head{i}", cfg)
              for i in range(slices)]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    names = [p.name for p in
             fluid.default_main_program().global_block().all_parameters()]
    p = state(names)
    experts = {"gate": r.randn(hidden, total) * 0.5,
               "w_gate": r.randn(total, hidden, 32) * 0.2,
               "w_up": r.randn(total, hidden, 32) * 0.2,
               "w_down": r.randn(total, 32, hidden) * 0.2}
    experts = {n: v.astype(np.float32) for n, v in experts.items()}
    for share in range(shares):
        lo = share * held
        scope.set(f"share{share}.moe.gate", experts["gate"])
        for w in ("w_gate", "w_up", "w_down"):
            scope.set(f"share{share}.moe.{w}", experts[w][lo:lo + held])
    head = r.randn(hidden, vocab).astype(np.float32) * 0.2
    for i in range(slices):
        scope.set(f"head{i}.w_0", head[:, i * 16:(i + 1) * 16])
    feed = {"x": r.randn(b, s, hidden).astype(np.float32)}
    got = exe.run(feed=feed, fetch_list=logits)
    # the uncut reference: every expert held, the whole head
    p.update({"m.moe." + n: v for n, v in experts.items()})
    uncut = dict(model, num_experts=total, held_from=0)

    def layer(p, x):
        a = adapter._rms(x, p["m.input_norm.w_0"], 1e-6)
        h1 = x + adapter.sparse_attention(p, a, "m.attn", uncut,
                                          _positions(b, s))[0]
        u = adapter._rms(h1, p["m.post_attn_norm.w_0"], 1e-6)
        out = h1 + adapter.expert_ffn(p, u, "m", uncut)
        return adapter._rms(out, p["m.final_norm.w_0"], 1e-6) @ head

    want = highest(layer, p, feed["x"])
    assert rel(np.concatenate(got, -1), want) < 2e-5
    assert rel(got[1], want[..., :16]) > 0.5  # a slice is its own rows


# ----------------------------------------------- the cell's arithmetic


def test_counters_gauges_and_flops_of_the_cell():
    from paddle_tpu import profiler

    model, traffic = SUITE.cell(rehearse=False)
    assert (traffic["batch"], traffic["seq_len"]) == (1, 8192)
    assert adapter.held_layers(model) == [0, 1, 2, 3]
    assert model["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert model["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    # ISSUE 60's arithmetic, redone: attention 18.87M a layer, the
    # indexer's three matrices 2.23M, the router 0.26M, half a held
    # expert's worth a token (8 x 8 / 128 of 4.72M), the head 38.90M
    attn, indexer, router, expert = (
        2 * 2048 * 4096 + 2 * 2048 * 512, 2048 * (1024 + 64 + 16),
        2048 * 128, 3 * 2048 * 768)
    per_token = adapter.matrix_params_per_token(model)
    assert per_token == 4 * (attn + indexer + router + expert // 2) + (
        2048 * 18992)
    held = 4 * (attn + indexer + 128 + router + 8 * expert + 2 * 2048
                + 2 * 128) + 2 * 2048 * 18992 + 2048
    assert abs(held / 1e6 - 314.4) < 0.05  # the parameters held
    admitted = adapter.admitted_pairs(8192, 2048)
    assert admitted == sum(min(t + 1, 2048) for t in range(8192))
    causal = 8192 * 8193 // 2
    assert abs(admitted / causal - 0.437) < 0.001
    assert abs(adapter.admitted_pairs(4096, 2048) / (4096 * 4097 // 2)
               - 0.75) < 0.001
    flops = adapter.flops_per_example(model, traffic)
    assert flops == 3.0 * (2 * 8192 * per_token + 4 * (
        admitted * 32 * 4 * 128 + causal * 16 * 2 * 64))
    assert 9.5e12 < flops < 10.5e12

    c0 = profiler.counters()
    small, small_traffic = SUITE.cell()
    main, _, built, exe, _ = SUITE.built_model(small, small_traffic)
    batch = SUITE.batch_for(small, small_traffic)
    got = exe.run(main, feed=batch,
                  fetch_list=[built["loss"]] + built["terms"] + built["loads"])
    c1 = profiler.counters()

    def bumped(name):
        return c1.get(name, 0) - c0.get(name, 0)

    b, s, k = 2, 48, 16
    assert bumped("sparse_attn_layers") == 2  # at build time, once a layer
    assert bumped("attn_pairs_admitted") == 2 * b * sum(
        min(t + 1, k) for t in range(s))
    assert bumped("attn_pairs_causal") == 2 * b * s * (s + 1) // 2
    assert (c1["sparse_attn_topk"], c1["sparse_index_heads"],
            c1["loss_terms"], c1["attn_kv_group"]) == (16, 2, 2, 2)
    # two layers, the forward op's lowering and the gradient op's replay
    assert bumped("attn_dispatch_xla") == 4
    assert bumped("moe_dispatch_grouped") == 4
    assert bumped("moe_route_softmax") == 4
    assert (c1["moe_experts_held"], c1["moe_experts_total"]) == (2, 8)
    # (and no kernel at the rehearsal's rows: the plain path's loss)
    for other in ("attn_latent_q_lora", "rope_interleaved",
                  "attn_qk_prep_fused", "attn_rope_scaled",
                  "index_kl_kernel_calls", "index_kl_fused"):
        assert c1.get(other, 0) == c0.get(other, 0), other
    loss, lm, index = (float(np.asarray(x).reshape(-1)[0]) for x in got[:3])
    assert abs(loss - (lm + index)) < 1e-5 and index > 0
    assert len(got[3:]) == 2 and all(x.shape == (2,) for x in got[3:])


if __name__ == "__main__":
    import sys

    if sys.argv[1:2] in (["pairs"], ["index_loss"]):
        _chip_pairs([int(a) for a in sys.argv[2:]] or [SUITE.seed],
                    wrong=sys.argv[1] == "index_loss")
    else:
        main(SUITE)
