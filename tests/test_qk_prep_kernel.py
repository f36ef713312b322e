"""The kernel pair between the projections and the flash kernels
(`ops/pallas/qk_prep.py`) under the Pallas interpreter: the head-major q,
k, v and every gradient (the projections' outputs and both norm weights)
against `jax.grad` of the chain it replaces, `rms_norm`, `rotate_half` and
a transpose in float32 at `highest`; with and without positions, grouped
and equal head counts, a row count that is no multiple of the block, a
block that holds position 8,191, and the published widths."""

import numpy as np
import pytest

from kernel_cases import written_out

EPS, THETA = 1e-5, 10000.0


@pytest.fixture(autouse=True)
def interpreter(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


def rel(got, want):
    """The largest difference over the largest value: position 8,191's
    lanes are not lost among the others, as they would be in a norm."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


def chain(q, k, v, wq, wk, theta):
    """What the Program ran before: float32 throughout, head-major out."""
    import jax.numpy as jnp

    from paddle_tpu.ops.nn_ops import rms_norm, rotate_half

    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    q, k = rms_norm(q, wq, EPS, 3), rms_norm(k, wk, EPS, 3)
    if theta:
        q, k = rotate_half(q, theta), rotate_half(k, theta)
    return tuple(jnp.transpose(t, (0, 2, 1, 3)) for t in (q, k, v))


def _args(b, s, h, g, d, dtype, seed=0):
    import jax.numpy as jnp

    r = np.random.RandomState(seed)
    acts = [jnp.asarray(r.randn(b, s, n, d) * (1 + r.rand(1, 1, n, 1)), dtype)
            for n in (h, g, g)]
    weights = [jnp.asarray(1 + 0.2 * r.randn(d), jnp.float32)
               for _ in range(2)]
    # what flows back into a bf16 output is bf16: the same on both sides
    cotangents = [jnp.asarray(r.randn(b, n, s, d), dtype).astype(jnp.float32)
                  for n in (h, g, g)]
    return acts + weights, cotangents


def _both(fn, args, cotangents):
    """Outputs, and the gradients of sum(out * cotangent) in every input:
    jitted, as the step is (XLA folds the frequencies' constant one way
    inside a jit and another outside: 5e-4 rad at position 8,191)."""
    import jax
    import jax.numpy as jnp

    def loss(*a):
        outs = fn(*a)
        return sum(jnp.sum(o.astype(jnp.float32) * c)
                   for o, c in zip(outs, cotangents)), outs

    with jax.default_matmul_precision("highest"):  # one trace, one compile
        (_, outs), grads = jax.jit(jax.value_and_grad(
            loss, argnums=range(len(args)), has_aux=True))(*args)
    return outs, grads


# b, s, heads, key/value heads, d, rows a block
SHAPES = [
    pytest.param(2, 128, 4, 2, 128, 64, id="grouped"),
    pytest.param(1, 96, 2, 2, 128, 32, id="equal_heads"),
    pytest.param(2, 200, 4, 1, 128, 64, id="rows_not_a_multiple_of_the_block"),
    pytest.param(1, 8192, 2, 1, 128, 1024, id="position_8191"),
    pytest.param(1, 50, 2, 1, 256, 1024, id="two_lane_slices_a_head"),
]


@pytest.mark.parametrize("theta", [0.0, THETA], ids=["no_positions", "rope"])
@pytest.mark.parametrize("b,s,h,g,d,rows", SHAPES)
def test_float32_equals_the_chain_and_its_gradients(b, s, h, g, d, rows,
                                                    theta):
    from paddle_tpu.ops.pallas.qk_prep import qk_prep

    args, cotangents = _args(b, s, h, g, d, "float32")
    got, got_grads = _both(
        lambda *a: qk_prep(*a, epsilon=EPS, theta=theta, rows=rows),
        args, cotangents)
    want, want_grads = _both(lambda *a: chain(*a, theta), args, cotangents)
    for name, a, w in zip("qkv", got, want):
        assert a.shape == w.shape and a.dtype == w.dtype
        assert rel(a, w) < 1e-5, name
    np.testing.assert_array_equal(got[2], want[2])  # v is only moved
    for name, a, w in zip(("dq", "dk", "dv", "dwq", "dwk"), got_grads,
                          want_grads):
        assert a.shape == w.shape and a.dtype == w.dtype
        assert rel(a, w) < 1e-5, name


@pytest.mark.parametrize("b,s,h,g,d,rows,lanes", [
    pytest.param(1, 96, 2, 1, 256, 32, 64, id="a_quarter_of_256"),
    pytest.param(2, 200, 4, 2, 128, 64, 32, id="a_quarter_of_128"),
    pytest.param(1, 64, 2, 2, 128, 64, 2, id="one_pair"),
    pytest.param(1, 64, 2, 1, 128, 64, 128, id="the_whole_head_named"),
])
def test_a_part_of_the_head_turned_equals_a_written_out_rotation(
        b, s, h, g, d, rows, lanes):
    """`rotary_dim`: the first lanes of a head turn as a head of that
    width and the rest pass as normed, forward and every gradient,
    against the norm and a rotation written out with a concatenation;
    `rotate_half(x, theta, None, rotary_dim)` is that rotation too. Named
    as the whole head it is the default's call."""
    import jax.numpy as jnp

    from paddle_tpu.ops.nn_ops import rms_norm, rotate_half
    from paddle_tpu.ops.pallas.qk_prep import qk_prep

    def chain_part(q, k, v, wq, wk):
        q, k = rms_norm(q, wq, EPS, 3), rms_norm(k, wk, EPS, 3)
        q, k = written_out(q, THETA, lanes), written_out(k, THETA, lanes)
        return tuple(jnp.transpose(t, (0, 2, 1, 3)) for t in (q, k, v))

    args, cotangents = _args(b, s, h, g, d, "float32")
    got, got_grads = _both(
        lambda *a: qk_prep(*a, epsilon=EPS, theta=THETA, rows=rows,
                           rotary_dim=lanes), args, cotangents)
    want, want_grads = _both(chain_part, args, cotangents)
    for name, a, w in zip("qkv", got, want):
        assert a.shape == w.shape and rel(a, w) < 1e-5, name
    for name, a, w in zip(("dq", "dk", "dv", "dwq", "dwk"), got_grads,
                          want_grads):
        assert a.shape == w.shape and rel(a, w) < 1e-5, name
    q = args[0]
    assert rel(rotate_half(q, THETA, None, lanes),
               written_out(q, THETA, lanes)) < 1e-6
    if lanes < d:  # the lanes past the turned ones are the norm's alone
        normed = jnp.transpose(rms_norm(q, args[3], EPS, 3), (0, 2, 1, 3))
        np.testing.assert_allclose(got[0][..., lanes:], normed[..., lanes:],
                                   rtol=1e-6, atol=1e-6)
        assert rel(got[0], chain(*args, THETA)[0]) > 0.05
    else:
        whole, _ = _both(lambda *a: qk_prep(*a, epsilon=EPS, theta=THETA,
                                            rows=rows), args, cotangents)
        np.testing.assert_array_equal(got[0], whole[0])


def test_a_part_turned_declares_two_more_flops_and_a_third_table():
    from pallas_costs import declared, numbers

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.qk_prep import qk_prep

    b, s, h, g, d = 1, 64, 2, 1, 256
    args, cotangents = _args(b, s, h, g, d, jnp.bfloat16)

    def both(lanes):
        def fn(*a):
            out, pull = jax.vjp(lambda *a: qk_prep(
                *a, epsilon=EPS, theta=THETA, rotary_dim=lanes), *a)
            return pull(tuple(c.astype(jnp.bfloat16) for c in cotangents))
        return {k: numbers(v[0]) for k, v in declared(fn, *args).items()}

    part, whole = both(64), both(0)
    elements = b * s * (h + g) * d
    for name, base in (("qk_prep_fwd", 4), ("qk_prep_bwd", 11)):
        assert whole[name][0] == (base + 3) * elements
        assert part[name][0] == (base + 5) * elements
        assert part[name][1] == whole[name][1]
        assert part[name][2] - whole[name][2] == s * d * 4


@pytest.mark.parametrize("theta", [0.0, THETA], ids=["no_positions", "rope"])
def test_bf16_in_and_out_is_one_rounding_from_the_float32_chain(theta):
    """bf16 as the projections write it under AMP, at the published
    widths (32 query heads over 4 key/value heads of 128; the row cut to
    256): float32 inside, so the output is the float32 chain's rounded
    once, and the gradients the chain's rounded where they are written."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.qk_prep import qk_prep

    args, cotangents = _args(1, 256, 32, 4, 128, jnp.bfloat16, seed=1)
    got, got_grads = _both(
        lambda *a: qk_prep(*a, epsilon=EPS, theta=theta, rows=128),
        args, cotangents)
    want, want_grads = _both(lambda *a: chain(*a, theta), args, cotangents)
    for a, w in zip(got, want):
        assert a.dtype == jnp.bfloat16
        # half a bf16 ulp is 2^-9 of the value's power of two
        assert rel(a.astype(jnp.float32), w) < 2.0 ** -8
    for a, w in zip(got_grads[:3], want_grads[:3]):
        # both sides rounded to bf16 where they are written: one ulp apart
        assert a.dtype == w.dtype == jnp.bfloat16
        assert rel(a.astype(jnp.float32), w.astype(jnp.float32)) < 2.0 ** -7
    for a, w in zip(got_grads[3:], want_grads[3:]):  # summed in float32
        assert a.dtype == jnp.float32 and rel(a, w) < 1e-5


def test_float32_in_bf16_out_as_the_attention_casts():
    """Without a bf16 projection in front (an fc kept in float32 under
    AMP) the kernel reads float32 and writes the attention's dtype; the
    gradients come back in float32."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.qk_prep import qk_prep

    args, cotangents = _args(1, 64, 2, 1, 128, "float32", seed=2)
    got, got_grads = _both(
        lambda *a: qk_prep(*a, epsilon=EPS, theta=THETA,
                           out_dtype=jnp.bfloat16), args, cotangents)
    want, _ = _both(lambda *a: chain(*a, THETA), args, cotangents)
    for a, w in zip(got, want):
        assert a.dtype == jnp.bfloat16
        assert rel(a.astype(jnp.float32), w) < 2.0 ** -8
    assert all(g.dtype == jnp.float32 for g in got_grads)


def test_a_head_that_is_no_lane_slice_is_refused():
    from paddle_tpu.ops.pallas.qk_prep import qk_prep, qk_prep_viable

    assert qk_prep_viable(128, 128) and qk_prep_viable(256, 256)
    assert not qk_prep_viable(64, 64) and not qk_prep_viable(192, 128)
    args, _ = _args(1, 32, 2, 1, 64, "float32")
    with pytest.raises(ValueError, match="lane"):
        qk_prep(*args, epsilon=EPS)


def test_the_kernels_names_are_not_the_flash_kernels():
    """`flash_ms_per_step` matches the four flash kernels by name;
    `qk_prep_hbm_roofline_pct` matches these two and nothing of theirs."""
    import json
    import os
    import re

    import jax

    from paddle_tpu.ops.pallas.qk_prep import qk_prep

    args, cotangents = _args(1, 32, 2, 1, 128, "float32")
    text = str(jax.make_jaxpr(lambda *a: jax.vjp(
        lambda *a: qk_prep(*a, epsilon=EPS, theta=THETA), *a)[1](
            tuple(cotangents)))(*args))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "qk_prep_hbm_roofline_pct.json")) as f:
        mine = json.load(f)["args"]["name"]
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "flash_ms_per_step.json")) as f:
        theirs = json.load(f)["args"]["name"]
    for name in ("qk_prep_fwd", "qk_prep_bwd"):
        assert name in text
        assert re.search(mine, name) and not re.search(theirs, name)
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                 "flash_bwd_dkv_dq"):
        assert re.search(theirs, name) and not re.search(mine, name)


@pytest.mark.parametrize("kernel", ["qk_prep_fwd", "qk_prep_bwd"])
@pytest.mark.parametrize("theta", [0.0, THETA], ids=["no_positions", "rope"])
def test_declared_cost_against_a_count_by_hand(kernel, theta):
    """ops/pallas/cost.py's convention: no product, so FLOPs an element of
    q and k (4 forward, 11 backward, 3 more with positions) and an rsqrt
    a row of a head; q, k, v once in (bf16) and once out, the weights and
    the tables float32, and backward q and k again and the weights'
    partial sums, [8, d] a block of 64 rows."""
    import jax
    import jax.numpy as jnp
    from pallas_costs import declared, numbers

    from paddle_tpu.ops.pallas.qk_prep import qk_prep

    b, s, h, g, d, rows = 2, 200, 4, 1, 128, 64
    args, _ = _args(b, s, h, g, d, jnp.bfloat16)
    found = declared(jax.grad(lambda *a: sum(
        jnp.sum(o.astype(jnp.float32)) for o in qk_prep(
            *a, epsilon=EPS, theta=theta, rows=rows)), argnums=range(5)),
        *args)
    (got,) = found[kernel]
    backward, rope = kernel == "qk_prep_bwd", bool(theta)
    normed = b * s * (h + g)  # rows of one head of q and k
    qkv = 2 * b * s * (h + 2 * g) * d  # bytes of q, k and v together
    moved = 2 * qkv + 2 * 4 * d + (2 * 4 * s * d if rope else 0)
    if backward:
        moved += 2 * b * s * (h + g) * d + 2 * 4 * b * 4 * 8 * d
    assert numbers(got) == (
        ((11 if backward else 4) + (3 if rope else 0)) * normed * d,
        normed, moved)


# ------------------------------------------------- without a norm (PR 61)


def turned(q, k, v, theta, scaling=None, lanes=0):
    """`rotate_half` and a transpose in `jnp`, float32 throughout."""
    import jax.numpy as jnp

    from paddle_tpu.ops.nn_ops import rotate_half

    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    if theta:
        q, k = (rotate_half(t, theta, scaling, lanes) for t in (q, k))
    return tuple(jnp.transpose(t, (0, 2, 1, 3)) for t in (q, k, v))


YARN = (8.0, 4096.0, 32.0, 1.0, 1.2079441541679836)


@pytest.mark.parametrize("b,s,h,g,d,rows,kw", [
    pytest.param(1, 96, 16, 16, 128, 32, {}, id="16_over_16"),
    pytest.param(1, 64, 32, 4, 128, 64, {}, id="32_over_4"),
    pytest.param(2, 200, 4, 2, 128, 64, {},
                 id="rows_not_a_multiple_of_the_block"),
    pytest.param(1, 8192, 2, 1, 128, 1024, {}, id="position_8191"),
    pytest.param(1, 96, 4, 2, 128, 32, {"scaling": YARN}, id="yarn"),
    pytest.param(1, 96, 2, 1, 256, 32, {"rotary_dim": 64},
                 id="a_quarter_of_256_turned"),
    pytest.param(2, 200, 4, 2, 128, 64, {"rotary_dim": 32},
                 id="a_quarter_of_128_turned"),
    pytest.param(1, 50, 2, 1, 128, 1024, {"theta": 0.0}, id="only_moved"),
])
def test_without_weights_it_turns_and_moves_and_so_does_its_backward(
        b, s, h, g, d, rows, kw):
    """No norm: forward `rotate_half` and the transpose, backward the
    rotation by the negative angle of the cotangents alone, against
    `jax.grad` of the `jnp` chain in float32."""
    from paddle_tpu.ops.pallas.qk_prep import qk_prep

    kw = {"theta": THETA, **kw}
    (*acts, _, _), cotangents = _args(b, s, h, g, d, "float32")
    got, got_grads = _both(
        lambda *a: qk_prep(*a, rows=rows, **kw), acts, cotangents)
    want, want_grads = _both(
        lambda *a: turned(*a, kw["theta"], kw.get("scaling"),
                          kw.get("rotary_dim", 0)), acts, cotangents)
    for name, a, w in zip("qkv", got, want):
        assert a.shape == w.shape and a.dtype == w.dtype
        assert rel(a, w) < 1e-5, name
    np.testing.assert_array_equal(got[2], want[2])  # v is only moved
    for name, a, w in zip(("dq", "dk", "dv"), got_grads, want_grads):
        assert a.shape == w.shape and a.dtype == w.dtype
        assert rel(a, w) < 1e-5, name
    if not kw["theta"]:  # and q and k are
        np.testing.assert_array_equal(got[0], want[0])


def test_without_weights_bf16_is_one_rounding_from_the_float32_chain():
    """Ouro's call under AMP, the row cut to 256: bf16 in and out, float32
    inside; the gradients come back in bf16, q's and k's each in its own
    dtype."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.qk_prep import qk_prep

    (*acts, _, _), cotangents = _args(1, 256, 16, 16, 128, jnp.bfloat16,
                                      seed=1)
    got, got_grads = _both(lambda *a: qk_prep(*a, theta=THETA, rows=128),
                            acts, cotangents)
    want, want_grads = _both(lambda *a: turned(*a, THETA), acts, cotangents)
    for a, w in zip(got, want):
        assert a.dtype == jnp.bfloat16
        assert rel(a.astype(jnp.float32), w) < 2.0 ** -8
    for a, w in zip(got_grads, want_grads):
        assert a.dtype == w.dtype == jnp.bfloat16
        assert rel(a.astype(jnp.float32), w.astype(jnp.float32)) < 2.0 ** -7
    mixed = [acts[0].astype(jnp.float32), *acts[1:]]
    _, grads = _both(lambda *a: qk_prep(*a, theta=THETA, rows=128,
                                         out_dtype=jnp.bfloat16),
                      mixed, cotangents)
    assert [g.dtype for g in grads] == [jnp.float32, jnp.bfloat16,
                                        jnp.bfloat16]


def test_one_weight_alone_is_refused():
    from paddle_tpu.ops.pallas.qk_prep import qk_prep

    args, _ = _args(1, 32, 2, 1, 128, "float32")
    with pytest.raises(ValueError, match="together"):
        qk_prep(*args[:4], None, epsilon=EPS)


@pytest.mark.parametrize("lanes", [0, 64], ids=["whole_head", "a_part"])
def test_without_weights_the_backward_reads_and_declares_no_forward_operand(
        lanes):
    """The custom-vjp keeps nothing: `qk_prep_bwd`'s operands are the
    three cotangents and the tables, its results the three gradients;
    both calls declare the turning's FLOPs alone (3 an element of q and
    k, 5 where a part turns), no rsqrt, q, k and v once in and once out
    and the tables."""
    import jax
    import jax.numpy as jnp
    from pallas_costs import declared, numbers, operand_shapes

    from paddle_tpu.ops.pallas.qk_prep import qk_prep

    b, s, h, g, d, rows = 2, 200, 4, 1, 256, 64
    (*acts, _, _), cotangents = _args(b, s, h, g, d, jnp.bfloat16)

    def fn(*a):
        out, pull = jax.vjp(lambda *a: qk_prep(
            *a, theta=THETA, rows=rows, rotary_dim=lanes), *a)
        return pull(tuple(c.astype(jnp.bfloat16) for c in cotangents))

    tables = [(s, d)] * (3 if lanes else 2)
    flat = [(b, s, n * d) for n in (h, g, g)]
    major = [(b, n, s, d) for n in (h, g, g)]
    shapes = operand_shapes(fn, *acts)
    assert shapes["qk_prep_fwd"] == [(flat + tables, major)]
    assert shapes["qk_prep_bwd"] == [(major + tables, flat)]
    found = declared(fn, *acts)
    qkv = 2 * b * s * (h + 2 * g) * d  # bytes of q, k and v together
    for name in ("qk_prep_fwd", "qk_prep_bwd"):
        assert numbers(found[name][0]) == (
            (5 if lanes else 3) * b * s * (h + g) * d, 0,
            2 * qkv + len(tables) * 4 * s * d)


def test_the_normed_modes_jaxpr_at_trinitys_shape_is_the_parents():
    """What PR 61 added is chosen by the weights' absence: with weights,
    at the shape of Trinity's, Mellum's and Keye's calls (32 query heads
    over 4 key/value heads of 128, 8,192 tokens, bf16), the pair under
    `jax.vjp` traces the equations PR 61's parent (commit f0576ce) traced,
    the full layer's and the window layer's; taken by running this test's
    body against that commit (`pallas_costs.jaxpr_digest`)."""
    import jax
    import jax.numpy as jnp
    from pallas_costs import jaxpr_digest

    from paddle_tpu.ops.pallas.qk_prep import qk_prep

    b, s, h, g, d = 1, 8192, 32, 4, 128
    shapes = [jax.ShapeDtypeStruct((b, s, n, d), jnp.bfloat16)
              for n in (h, g, g)] + [jax.ShapeDtypeStruct((d,), jnp.float32)] * 2

    def pair(theta):
        def fn(*a):
            out, pull = jax.vjp(lambda *a: qk_prep(
                *a, epsilon=EPS, theta=theta), *a)
            return out, pull(out)
        return jaxpr_digest(fn, *shapes)

    assert (pair(0.0), pair(THETA)) == PARENTS


PARENTS = ("dbf91ca1f6a7a973", "5b2ea252ef69473c")
