"""The short convolution's backward kernel (`ops/pallas/short_conv.py`)
under the Pallas interpreter: dx, the taps' and the bias's gradients
against `jax.vjp` of the taps written out; the zero state at a row's
start and nothing past its end; bf16 rows with float32 inside; the
declared cost by hand; where `short_conv_viable` admits a call; which
path `short_conv`'s backward takes and what the counters say; a batch
mesh per shard; the same without the SiLU (`activation` "none") at three
and four taps; and that with the default the traced jaxpr is what the
parent of PR 47 traced."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pallas_costs
from paddle_tpu import profiler
from paddle_tpu.ops import linear_attn_ops as ops
from paddle_tpu.ops.pallas import short_conv as kernel


@pytest.fixture
def interpreter(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


def plain(x, w, bias=None, silu=True):
    """The forward with no gradient of its own: `jax.vjp` of it is the
    oracle."""
    out = ops._conv_taps(x, w, bias)[1]
    return (out * jax.nn.sigmoid(out) if silu else out).astype(x.dtype)


def operands(b, s, c, width, bias, dtype, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(b, s, c), dtype)
    w = jnp.asarray(rng.randn(c, width) * 0.5, jnp.float32)
    dy = jnp.asarray(rng.randn(b, s, c), dtype)
    return (x, w, jnp.asarray(rng.randn(c) * 0.5, jnp.float32)
            if bias else None), dy


def close(got, want, rel):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= rel * max(np.abs(want).max(), 1.0)


# (b, s, c, width, bias, dtype): the shortest row, rows of one and of
# three channel blocks, batch 1 to 3, two to seven taps, AMP's bf16 rows
CASES = {
    "shortest_row": (1, 16, 128, 4, False, jnp.float32),
    "three_blocks_b2_bias": (2, 48, 384, 4, True, jnp.float32),
    "bf16_b3_bias": (3, 64, 256, 4, True, jnp.bfloat16),
    "bf16_no_bias": (1, 160, 128, 4, False, jnp.bfloat16),
    "two_taps": (2, 32, 128, 2, True, jnp.float32),
    "seven_taps": (1, 32, 256, 7, False, jnp.float32),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_equals_the_vjp_of_the_taps(case, interpreter):
    b, s, c, width, bias, dtype = CASES[case]
    (x, w, bb), dy = operands(b, s, c, width, bias, dtype)
    args = (x, w, bb) if bias else (x, w)
    want = jax.vjp(plain, *args)[1](dy)
    dx, dw, dbias = kernel.short_conv_bwd(x, w, bb, dy)
    assert dx.dtype == x.dtype and dw.dtype == jnp.float32
    # dx to a rounding of its own dtype, the sums to float32's
    close(dx, want[0], 1e-2 if dtype == jnp.bfloat16 else 1e-6)
    close(dw, want[1], 1e-6)
    if bias:
        close(dbias, want[2], 1e-6)
    else:
        assert dbias is None


def test_the_rows_start_from_zero_and_end_at_their_end(interpreter):
    """A cotangent on the first token alone reaches x_0 through the last
    tap and no other token; one on the last token reaches the last
    `width` tokens; rows of a batch do not see each other (the rotation
    wraps inside a block, and the wrapped rows are masked)."""
    b, s, c, width = 2, 32, 128, 4
    (x, w, _), _ = operands(b, s, c, width, False, jnp.float32, seed=1)
    for token, reached in ((0, [0]), (s - 1, [s - 4, s - 3, s - 2, s - 1])):
        dy = jnp.zeros((b, s, c)).at[0, token].set(1.0)
        dx, dw, _ = kernel.short_conv_bwd(x, w, None, dy)
        hit = np.flatnonzero(np.abs(np.asarray(dx[0])).sum(axis=1))
        assert hit.tolist() == reached
        assert not np.asarray(dx[1]).any()
        close(dw, jax.vjp(plain, x, w)[1](dy)[1], 1e-6)


def test_declared_cost_against_a_count_by_hand(interpreter):
    """2 rows of 64 tokens, 256 channels, four taps, bf16: 34 FLOPs and two
    transcendentals an element; x, dy and dx at two bytes, the five rows
    of taps and bias and the partial's eight rows a batch row at four."""
    (x, w, bb), dy = operands(2, 64, 256, 4, True, jnp.bfloat16)
    (found,) = pallas_costs.declared(
        lambda *a: kernel.short_conv_bwd(*a), x, w, bb, dy)["short_conv_bwd"]
    elements = 2 * 64 * 256
    assert found.flops == elements * 34
    assert found.transcendentals == elements * 2
    assert found.bytes_accessed == (3 * elements * 2 + 5 * 256 * 4
                                    + 2 * 8 * 256 * 4)
    ((read, written),) = pallas_costs.operand_shapes(
        lambda *a: kernel.short_conv_bwd(*a), x, w, bb, dy)["short_conv_bwd"]
    assert read == [(2, 64, 256), (4, 256), (1, 256), (2, 64, 256)]
    assert written == [(2, 64, 256), (2, 8, 256)]
    (grid, blocks), = pallas_costs.block_shapes(
        lambda *a: kernel.short_conv_bwd(*a), x, w, bb, dy)["short_conv_bwd"]
    assert grid == (2, 2) and blocks[0][-2:] == (64, 128)


def _batch_mesh(n=2):
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:n]), ("batch",))


def _tensor_parallel_mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("batch", "model"))


def test_viable_is_shapes_mesh_and_backend(interpreter, monkeypatch):
    viable = kernel.short_conv_viable
    assert viable(1, 4096, 4096, 4, None)  # Kimi's
    assert viable(1, 4096, 5120, 4, None)  # the tenth cell's
    assert viable(1, 8192, 2048, 3, None)  # the eleventh's: the longest row
    assert viable(2, 16, 128, 1, None)
    assert not viable(1, 4096, 4000, 4, None)  # no whole lanes
    assert not viable(1, 21, 128, 4, None)  # no whole packed sublanes
    assert not viable(1, 8, 128, 4, None)
    assert not viable(1, 16384, 128, 4, None)  # past VMEM
    assert not viable(1, 64, 128, 8, None)  # no row left for the bias
    assert viable(2, 64, 128, 4, _batch_mesh())
    assert not viable(3, 64, 128, 4, _batch_mesh())
    assert not viable(2, 64, 128, 4, _tensor_parallel_mesh())
    with pytest.raises(ValueError, match="short_conv_bwd"):
        kernel.short_conv_bwd(jnp.zeros((1, 21, 128)), jnp.zeros((128, 4)),
                              None, jnp.zeros((1, 21, 128)))
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET")
    assert not viable(1, 4096, 4096, 4, None)  # a CPU, no interpreter
    with pytest.raises(RuntimeError, match="short_conv_bwd"):
        kernel.short_conv_bwd(jnp.zeros((1, 16, 128)), jnp.zeros((128, 4)),
                              None, jnp.zeros((1, 16, 128)))


@pytest.mark.parametrize("path", ["pallas", "xla_shape", "xla_backend"])
def test_short_convs_backward_takes_the_path_its_shapes_allow(
        path, monkeypatch):
    """`linear_attn_ops.short_conv` under `jax.vjp`: the kernel where
    `short_conv_viable` admits the call, the written-out XLA backward for
    a row that is not whole packed sublanes and on a CPU without the
    interpreter; the same gradients and one counter bumped either way."""
    if path == "xla_backend":  # whatever an earlier test of the worker left
        monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    else:
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    s = 21 if path == "xla_shape" else 32
    (x, w, bb), dy = operands(2, s, 128, 4, True, jnp.bfloat16, seed=4)
    before = profiler.counters()
    y, pull = jax.vjp(lambda *a: ops.short_conv(*a), x, w, bb)
    got = pull(dy)
    after = profiler.counters()
    bumped = {n: v - before.get(n, 0) for n, v in after.items()
              if n.startswith("short_conv_dispatch_") and v != before.get(n)}
    assert bumped == {"short_conv_dispatch_pallas" if path == "pallas"
                      else "short_conv_dispatch_xla": 1}
    y_want, pull_want = jax.vjp(plain, x, w, bb)
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(y_want, np.float32))
    for g, g_want, like, rel in zip(got, pull_want(dy), (x, w, bb),
                                    (1e-2, 1e-6, 1e-6)):
        assert g.dtype == like.dtype
        close(g, g_want, rel)


def test_a_batch_mesh_runs_the_kernel_per_shard(interpreter):
    """Four rows over two devices: the same gradients as on one, the
    partial rows of the shards added up outside."""
    (x, w, bb), dy = operands(4, 32, 256, 4, True, jnp.float32, seed=5)
    want = kernel.short_conv_bwd(x, w, bb, dy)
    mesh = _batch_mesh()
    with mesh:
        got = jax.jit(lambda *a: kernel.short_conv_bwd(*a, mesh=mesh))(
            x, w, bb, dy)
    for g, g_want in zip(got, want):
        close(g, g_want, 1e-6)


# ------------------------------------------- with no activation after it

# (b, s, c, width, bias, dtype): LFM2's three taps and the four of the two
# models that have the SiLU, AMP's bf16 rows and float32
LINEAR_CASES = {
    "three_taps_bf16": (1, 64, 256, 3, False, jnp.bfloat16),
    "three_taps_b2": (2, 48, 128, 3, False, jnp.float32),
    "four_taps_bf16_bias": (2, 32, 256, 4, True, jnp.bfloat16),
    "four_taps": (1, 16, 128, 4, False, jnp.float32),
}


@pytest.mark.parametrize("case", list(LINEAR_CASES))
def test_kernel_without_the_silu_equals_the_vjp_of_the_taps(case, interpreter):
    b, s, c, width, bias, dtype = LINEAR_CASES[case]
    (x, w, bb), dy = operands(b, s, c, width, bias, dtype, seed=6)
    args = (x, w, bb) if bias else (x, w)
    want = jax.vjp(lambda *a: plain(*a, silu=False), *args)[1](dy)
    dx, dw, dbias = kernel.short_conv_bwd(x, w, bb, dy, activation="none")
    assert dx.dtype == x.dtype and dw.dtype == jnp.float32
    close(dx, want[0], 1e-2 if dtype == jnp.bfloat16 else 1e-6)
    close(dw, want[1], 1e-6)
    if bias:
        close(dbias, want[2], 1e-6)
    else:
        assert dbias is None
    # and it is no rounding of the SiLU's backward
    with_silu = kernel.short_conv_bwd(x, w, bb, dy)
    assert np.abs(np.asarray(with_silu[1] - dw)).max() > 0.1


@pytest.mark.parametrize("path", ["pallas", "xla"])
@pytest.mark.parametrize("width", [3, 4])
def test_short_conv_without_the_silu_on_both_paths(path, width, monkeypatch):
    """`short_conv(..., activation="none")` under `jax.vjp`: the taps'
    sum itself forward, the cotangent as `dpre` backward, through the
    kernel where it is viable and the written-out XLA form elsewhere; one
    dispatch counter and `short_conv_linear_calls` bumped either way, and
    never by the default."""
    if path == "xla":
        monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    else:
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    (x, w, _), dy = operands(2, 32, 128, width, False, jnp.bfloat16, seed=7)
    before = profiler.counters()
    y, pull = jax.vjp(lambda *a: ops.short_conv(*a, None, None, "none"), x, w)
    got = pull(dy)
    after = profiler.counters()
    bumped = {n: v - before.get(n, 0) for n, v in after.items()
              if n.startswith("short_conv_") and v != before.get(n)}
    assert bumped == {f"short_conv_dispatch_{path}": 1,
                      "short_conv_linear_calls": 1}
    y_want, pull_want = jax.vjp(lambda *a: plain(*a, silu=False), x, w)
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(y_want, np.float32))
    for g, g_want, like, rel in zip(got, pull_want(dy), (x, w), (1e-2, 1e-6)):
        assert g.dtype == like.dtype
        close(g, g_want, rel)
    jax.vjp(lambda *a: ops.short_conv(*a), x, w)[1](dy)
    assert profiler.counters()["short_conv_linear_calls"] == after[
        "short_conv_linear_calls"]
    with pytest.raises(ValueError, match="activation"):
        ops.short_conv(x, w, None, None, "relu")


def test_declared_cost_without_the_silu_against_a_count_by_hand(interpreter):
    """1 row of 64 tokens, 256 channels, three taps, bf16: the taps'
    gradients and dx at two FLOPs a tap each and the bias's sum, 13 an
    element, and no transcendental; x, dy and dx at two bytes, the four
    rows of taps and bias and the partial's eight rows at four."""
    (x, w, _), dy = operands(1, 64, 256, 3, False, jnp.bfloat16)
    call = lambda *a: kernel.short_conv_bwd(*a, activation="none")  # noqa: E731
    (found,) = pallas_costs.declared(call, x, w, None, dy)["short_conv_bwd"]
    elements = 64 * 256
    assert found.flops == elements * 13
    assert found.transcendentals == 0
    assert found.bytes_accessed == (3 * elements * 2 + 4 * 256 * 4
                                    + 8 * 256 * 4)
    # the SiLU's kernel at the same shape declares what it declared
    (silu,) = pallas_costs.declared(
        lambda *a: kernel.short_conv_bwd(*a), x, w, None, dy)["short_conv_bwd"]
    assert (silu.flops, silu.transcendentals) == (elements * 28, elements * 2)
    assert silu.bytes_accessed == found.bytes_accessed


# sha256 of the jaxpr's text (object addresses blanked) as the parent of
# PR 47 traces `short_conv` on these operands, under jax 0.9.0: (forward,
# backward) by (path, taps, bias). Taken by running `traced_digests` against
# a copy of that commit.
PARENTS_JAXPRS = {
    ("xla", 4, True): ("e75a379dafe84d4b", "22e0ea1f2d24eda3"),
    ("xla", 3, False): ("6019ac4a4afdc62d", "f82d3cea4a635f1f"),
    ("pallas", 4, True): ("e75a379dafe84d4b", "9d3452911d856ea1"),
    ("pallas", 4, False): ("ebeb63a206fab37f", "08e528e6a68ce274"),
}


def traced_digests(path, width, bias, conv=None):
    """(forward's, backward's) digest of `conv` (the default call of
    `ops.short_conv`) on the operands the table above was taken on."""
    conv = conv or (lambda *a: ops.short_conv(*a))
    (x, w, bb), dy = operands(2, 32, 128, width, bias, jnp.bfloat16, seed=8)
    args = (x, w, bb) if bias else (x, w)
    return (pallas_costs.jaxpr_digest(conv, *args),
            pallas_costs.jaxpr_digest(
                lambda *a: jax.vjp(conv, *a)[1](dy), *args))


@pytest.mark.parametrize("case", list(PARENTS_JAXPRS),
                         ids=lambda c: f"{c[0]}-{c[1]}taps-bias{c[2]}")
def test_the_defaults_jaxpr_is_the_parents(case, monkeypatch):
    """With the SiLU, named or by default, `short_conv` traces forward and
    backward what it traced before it took an activation: Kimi's and
    Phi-4's steps compile to what they compiled to."""
    path, width, bias = case
    if path == "xla":
        monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    else:
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert traced_digests(path, width, bias) == PARENTS_JAXPRS[case]
    named = traced_digests(
        path, width, bias,
        lambda x, w, b=None: ops.short_conv(x, w, b, None, "silu"))
    assert named == PARENTS_JAXPRS[case]
    linear = traced_digests(
        path, width, bias,
        lambda x, w, b=None: ops.short_conv(x, w, b, None, "none"))
    assert linear[0] != named[0] and linear[1] != named[1]
