"""The expert layer's grouped products as Pallas kernels
(`ops/pallas/grouped_matmul.py`) under the interpreter: the product, `dx`
(the same kernel on the weights read transposed, out in the rows' dtype)
and `dw` against `jax.lax.ragged_dot` and its `jax.vjp` at the three
expert cells' groups and widths with the rows cut down, over loads with
an empty group, a group that ends inside a tile, one group that holds
everything, a block filled exactly and no row at all; the dead rows of
the input and of the cotangent hold NaN throughout, and reach nothing (a
dead row's `y` and `dx` are zeros), alone and through `grouped_matmul`'s
`jax.vjp`. Then the layer that
calls them (`parallel/moe.py::moe_experts`) with and without the kernels at
every block count, what the calls declare, and where the kernels are
taken."""

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def interpreter(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


def rel(got, want):
    """The largest difference over the largest entry of `want`, the
    difference formed in float64. A group at a time: a cell's `dw` is 33
    million entries, and float64 copies of both sides and of their
    difference took the test longer than the kernels did."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    off = max(float(np.abs(np.subtract(g, w, dtype=np.float64)).max())
              for g, w in zip(got, want))
    return off / max(float(np.abs(want).max()), 1e-30)


# groups, K, N as `_block` calls the product: gate and up, then down
CELLS = {
    "mellum_gate": (16, 2304, 896), "mellum_down": (16, 896, 2304),
    "trinity_gate": (8, 2048, 1024), "trinity_down": (8, 1024, 2048),
    "kimi_gate": (8, 2304, 1024), "kimi_down": (8, 1024, 2304),
}
ROWS, TM = 192, 32


def load(pattern, groups):
    """Rows a group, by the name of what the load is there to meet."""
    rows = ROWS
    sizes = np.zeros(groups, np.int64)
    if pattern == "an_empty_group":
        sizes[[0, 2, 3, groups - 1]] = 40, 2 * TM, 13, 30  # 1 and 4.. empty
    elif pattern == "a_group_ends_inside_a_tile":
        sizes[:] = 1 + np.arange(groups) % 11  # every one does
        sizes[1] += 50
    elif pattern == "one_group_holds_everything":
        sizes[groups // 2] = rows - 37
    elif pattern == "fills_the_block_exactly":
        sizes[:] = rows // groups
        sizes[-1] += rows - sizes.sum()
    elif pattern != "no_row_at_all":
        raise ValueError(pattern)
    assert sizes.sum() <= rows
    return sizes.astype(np.int32)


PATTERNS = ["an_empty_group", "a_group_ends_inside_a_tile",
            "one_group_holds_everything", "fills_the_block_exactly",
            "no_row_at_all"]


def _operands(rows, groups, k, n, sizes, dtype, seed=0):
    """x, w, the cotangent, and the three with zeros where the kernels'
    carry NaN: the dead rows."""
    import jax.numpy as jnp

    r = np.random.RandomState(seed)
    live = (np.arange(rows) < sizes.sum())[:, None]
    x = r.randn(rows, k).astype(np.float32)
    ct = r.randn(rows, n).astype(np.float32)
    # drawn as float32: a cell's weights are 33 million entries
    w = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (groups, k, n), np.float32) * np.float32(k ** -0.5))
    clean = [jnp.asarray(np.where(live, t, 0.0), dtype) for t in (x, ct)]
    poisoned = [jnp.asarray(np.where(live, t, np.nan), dtype) for t in (x, ct)]
    return w, clean, poisoned, live


def _oracle(x, w, ct, sizes):
    """`ragged_dot` as the plain path calls it, the last group stretched
    over the dead rows, and its `jax.vjp`, float32 at `highest`."""
    import jax
    import jax.numpy as jnp

    part = jnp.asarray(sizes).at[-1].add(x.shape[0] - int(sizes.sum()))
    with jax.default_matmul_precision("highest"):
        y, pull = jax.vjp(lambda x, w: jax.lax.ragged_dot(
            x, w.astype(x.dtype), part, preferred_element_type=jnp.float32),
            x, w)
        return (y, *pull(ct.astype(jnp.float32)))


def _ours(x, w, ct, sizes, tm):
    import jax

    from paddle_tpu.ops.pallas import grouped_matmul as gm

    # the kernel calls of `grouped_matmul` and its backward, at small tiles
    # so that 192 rows are six of them and the widths are cut in two where
    # a half is whole lane slices (not 896 into its seven: the interpreter
    # walks every step), so `dx` contracts the down product's 2,048 or
    # 2,304 in two visits of a tile and its sum waits in VMEM between them
    def cut(width):
        return min(d for d in gm._divisors(width)[:2] if d >= 256)

    def tile(c, o):
        return tm, cut(c), cut(o)

    k, n = w.shape[1:]

    @jax.jit
    def three(x, w, ct, sizes):
        return (gm.moe_gmm(x, w, sizes, tiling=tile(k, n)),
                gm.moe_gmm(ct, w, sizes, tiling=tile(n, k),
                           transpose_rhs=True, out_dtype=x.dtype),
                gm.moe_tgmm(x, ct, sizes, tiling=tile(k, n)))

    return three(x, w.astype(x.dtype), ct.astype(x.dtype),
                 jax.numpy.asarray(sizes))


@pytest.mark.parametrize("pattern", PATTERNS)
@pytest.mark.parametrize("cell", list(CELLS))
def test_product_and_weight_gradient_equal_ragged_dot(cell, pattern):
    import jax.numpy as jnp

    groups, k, n = CELLS[cell]
    sizes = load(pattern, groups)
    w, clean, poisoned, live = _operands(ROWS, groups, k, n, sizes,
                                         jnp.float32)
    want = _oracle(clean[0], w, clean[1], sizes)
    got = _ours(poisoned[0], w, poisoned[1], sizes, TM)
    assert [g.shape for g in got] == [(ROWS, n), (ROWS, k), (groups, k, n)]
    for name, g, o in zip(("y", "dx", "dw"), got, want):
        assert np.isfinite(np.asarray(g)).all(), name
        if pattern == "no_row_at_all":
            assert not np.asarray(g).any(), name
        else:
            assert rel(g, o) < 2e-5, name
    # the dead rows are exactly zero, not small: the product's and dx's
    for g in got[:2]:
        assert not np.asarray(g)[~live[:, 0]].any()


@pytest.mark.parametrize("pattern", PATTERNS)
def test_a_float32_cotangent_under_bfloat16_rows_enters_at_full_precision(
        pattern):
    """`moe_tgmm` with x in bfloat16 and dy in float32 (the expert layer's
    sums onto the tokens, whose x is a 0/1 matrix): the kernel cuts dy
    into three bfloat16 slices a tile and adds their products, so the
    result is the float64 product of the bfloat16 x with the float32 dy
    to float32's rounding, where dy rounded to bfloat16 would be 2e-3
    off; the three slices of any float32 add up to it exactly; NaN in the
    dead rows reaches nothing. The other mixed pair is refused."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import grouped_matmul as gm

    groups, k, n = 4, 256, 384
    sizes = load(pattern, groups)
    _, clean, poisoned, live = _operands(ROWS, groups, k, n, sizes,
                                         jnp.float32, seed=2)
    x = clean[0].astype(jnp.bfloat16)
    ct = clean[1] * jnp.exp2(jnp.arange(ROWS) % 24 - 12.0)[:, None]
    got = gm.moe_tgmm(jnp.where(live, x, jnp.nan),
                      jnp.where(live, ct, jnp.nan), jnp.asarray(sizes),
                      tiling=(TM, 256, 128))
    assert got.shape == (groups, k, n) and got.dtype == jnp.float32
    ends = np.cumsum(sizes)
    x64, ct64 = (np.asarray(a.astype(jnp.float32), np.float64)
                 for a in (x, ct))
    want = np.stack([x64[e - s:e].T @ ct64[e - s:e]
                     for s, e in zip(sizes, ends)])
    assert np.isfinite(np.asarray(got)).all()
    if pattern == "no_row_at_all":
        assert not np.asarray(got).any()
    else:
        assert rel(got, want) < 1e-6
        rounded = np.stack([
            x64[e - s:e].T @ np.asarray(
                ct.astype(jnp.bfloat16).astype(jnp.float32),
                np.float64)[e - s:e] for s, e in zip(sizes, ends)])
        assert rel(rounded, want) > 1e-4
    slices = gm._bf16_slices(ct)
    assert all(s.dtype == jnp.bfloat16 for s in slices)
    np.testing.assert_array_equal(
        np.asarray(sum(s.astype(jnp.float32) for s in slices)),
        np.asarray(ct))
    with pytest.raises(ValueError, match="moe_tgmm: x"):
        gm.moe_tgmm(x.astype(jnp.float32), ct.astype(jnp.bfloat16),
                    jnp.asarray(sizes))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows", [512, 600, 40],
                         ids=["whole_tiles", "rows_no_multiple_of_the_tile",
                              "one_short_tile"])
def test_grouped_matmul_and_its_vjp_with_nan_in_the_dead_rows(rows, dtype):
    """`grouped_matmul` itself, through `jax.vjp`, at the tile sizes it
    picks (256 rows; the ids say what each row count meets there): NaN in
    the dead rows of x and of the cotangent gives, to the last bit, what
    zeros there give, and a dead row's y and dx are zeros; bf16 operands
    as the cells send them, float32 out; dx in x's dtype, written by the
    kernel and rounded once, and dw in the weights'."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import grouped_matmul as gm

    groups, k, n = 4, 256, 128
    sizes = np.asarray([rows // 3, 0, rows // 4, 5], np.int32)
    w, clean, poisoned, live = _operands(rows, groups, k, n, sizes,
                                         jnp.dtype(dtype))

    def both(x, ct):
        y, pull = jax.vjp(
            lambda x, w: gm.grouped_matmul(x, w, jnp.asarray(sizes)), x, w)
        return (y, *pull(ct.astype(jnp.float32)))

    got, same = jax.jit(both)(*poisoned), jax.jit(both)(*clean)
    y, dx, dw = got
    assert (y.dtype, dx.dtype, dw.dtype) == (jnp.float32, jnp.dtype(dtype),
                                             jnp.float32)
    for g, s in zip(got, same):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(s, np.float32))
    assert not np.asarray(y)[~live[:, 0]].any()
    assert not np.asarray(dx, np.float32)[~live[:, 0]].any()
    # rounded once: the float32 sums of the same call, rounded here (a
    # tile two groups share is written twice, and read back in between)
    once = gm.moe_gmm(clean[1], w.astype(dtype), jnp.asarray(sizes),
                      transpose_rhs=True).astype(dtype)
    np.testing.assert_array_equal(np.asarray(same[1], np.float32),
                                  np.asarray(once, np.float32))
    assert not np.asarray(dw)[1].any()  # the empty group's gradient
    want = _oracle(clean[0], w, clean[1], sizes)
    # dx is rounded to bf16 on one side; dw was on the other
    limit = 2e-5 if dtype == "float32" else 1e-2
    for name, g, o in zip(("y", "dx", "dw"), same, want):
        assert rel(g, o) < limit, name


TOTAL, K_ROUTED, TOKENS = 16, 2, 64


@pytest.mark.parametrize("held,correction,blocks", [
    (2, -10.0, 0), (4, 0.0, 1), (16, 0.0, 1), (8, 10.0, 2), (4, 10.0, 3),
    (2, 10.0, 4)])
def test_the_layer_with_the_kernels_equals_the_layer_without(
        held, correction, blocks):
    """`moe_experts` at lane-multiple widths with `kernel` and without, the
    loads of `tests/test_moe_experts.py` (from a block of dead rows to three
    trips of the overflow loop): the output, the load and the gradients in
    x, the router's gate and the three weights."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import moe

    r = np.random.RandomState(3)
    hidden, width = 128, 128
    args = [r.randn(TOKENS, hidden), r.randn(hidden, TOTAL) * 0.1,
            r.randn(held, hidden, width) * 0.1,
            r.randn(held, hidden, width) * 0.1,
            r.randn(held, width, hidden) * 0.1]
    args = [jnp.asarray(a, jnp.float32) for a in args]
    bias = np.zeros(TOTAL, np.float32)
    bias[:held] += correction
    cotangent = jnp.asarray(r.randn(TOKENS, hidden), jnp.float32)

    def layer(kernel):
        def f(x, gate, w_gate, w_up, w_down):
            y, load = moe.moe_experts(
                x, gate, jnp.asarray(bias), w_gate, w_up, w_down, k=K_ROUTED,
                scaling=2.446, experts_held=held, held_from=0, kernel=kernel)
            return jnp.sum(y * cotangent), (y, load)
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(f, range(5), has_aux=True))(
                *args)

    (_, (y, load)), got = layer(True)
    (_, (want_y, want_load)), want = layer(False)
    rows = moe._block_rows(TOKENS * K_ROUTED, held / TOTAL)
    assert -(-int(np.sum(load)) // rows) == blocks, load
    np.testing.assert_array_equal(load, want_load)
    if blocks == 0:
        assert not np.asarray(y).any()
        assert all(not np.asarray(g).any() for g in got)
        return
    assert rel(y, want_y) < 1e-5
    for name, g, w in zip(("x", "gate", "w_gate", "w_up", "w_down"), got,
                          want):
        assert rel(g, w) < 1e-5, name


@pytest.mark.parametrize("kernel", ["moe_gmm", "moe_tgmm"])
def test_declared_cost_against_a_count_by_hand(kernel):
    """A block of 96 rows, 4 groups, 256 x 128 in bf16: the static count
    (`cost.py`: work that is data), every row through one group's weights
    and every operand and output once, whatever the load."""
    import jax
    import jax.numpy as jnp

    from pallas_costs import declared, numbers

    from paddle_tpu.ops.pallas import grouped_matmul as gm

    rows, groups, k, n = 96, 4, 256, 128
    x = jnp.zeros((rows, k), jnp.bfloat16)
    w = jnp.zeros((groups, k, n), jnp.float32)
    sizes = jnp.asarray([10, 0, 30, 7], jnp.int32)
    found = declared(jax.grad(lambda x, w: jnp.sum(
        gm.grouped_matmul(x, w, sizes)), (0, 1)), x, w)
    flops = 2 * 96 * 256 * 128
    weights = 4 * 256 * 128 * 2  # read in bf16, all four groups
    if kernel == "moe_gmm":
        forward, dx = found[kernel]
        # x bf16, the weights, y float32
        assert numbers(forward) == (flops, 0,
                                    96 * 256 * 2 + weights + 96 * 128 * 4)
        # the widths swapped: dy bf16, the same weights as they lie, dx
        # bf16 (the kernel's own output, no float32 [rows, K] beside it)
        assert numbers(dx) == (flops, 0,
                               96 * 128 * 2 + weights + 96 * 256 * 2)
    else:
        (dw,) = found[kernel]
        # x and dy bf16, the gradient float32
        assert numbers(dw) == (
            flops, 0, 96 * 256 * 2 + 96 * 128 * 2 + 4 * 256 * 128 * 4)


@pytest.mark.parametrize("k,n,dtype,want", [
    (2304, 896, "bfloat16", True), (896, 2304, "bfloat16", True),
    (2048, 1024, "bfloat16", True), (2304, 1024, "float32", True),
    (32, 64, "bfloat16", False), (64, 32, "float32", False),
    (2304, 900, "bfloat16", False), (2304, 896, "float16", False)],
    ids=["mellum", "mellum_down", "trinity", "kimi_float32", "rehearsal",
         "rehearsal_down", "no_lane_multiple", "float16"])
def test_viable_at_the_cells_widths_and_not_at_the_rehearsals(
        k, n, dtype, want, monkeypatch):
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul_viable

    assert grouped_matmul_viable(k, n, dtype) is want
    # and nowhere without Mosaic or the interpreter
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET")
    assert grouped_matmul_viable(k, n, dtype) is False


def test_a_kernel_asked_for_where_it_cannot_run_raises(monkeypatch):
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import grouped_matmul as gm

    sizes = jnp.asarray([4, 4], jnp.int32)
    x = jnp.zeros((16, 128), jnp.float32)
    with pytest.raises(ValueError, match="128-lane"):
        gm.moe_gmm(x[:, :64], jnp.zeros((2, 64, 128)), sizes)
    with pytest.raises(ValueError, match="moe_gmm: x"):  # w is [G, O, C]
        gm.moe_gmm(x, jnp.zeros((2, 128, 256)), sizes, transpose_rhs=True)
    with pytest.raises(ValueError, match="moe_tgmm: x"):
        gm.moe_tgmm(x, jnp.zeros((8, 128)), sizes)
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET")
    with pytest.raises(RuntimeError, match="moe_gmm"):
        gm.moe_gmm(x, jnp.zeros((2, 128, 128)), sizes)
