"""The embedding table's gradient as grouped products over the tokens
sorted by id (`ops/pallas/embedding_grad.py`) under the Pallas interpreter:
against `jax.vjp` of `jnp.take` in float32 and in bf16; empty runs; where
`embedding_grad_viable` admits a call; `lookup_table` in a Program on both
paths with the two counters, negative ids and a `padding_idx`; a batch
mesh per shard; a tied table; and the call's name, which the expert cells'
metrics must not count, with nothing declared."""

from __future__ import annotations

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pallas_costs
from paddle_tpu.ops.pallas import embedding_grad as eg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def interpreter(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


def scatter(ids, dy, vocab):
    """The oracle: the gradient XLA gives `take`, in float32."""
    w = jnp.zeros((vocab, dy.shape[1]), jnp.float32)
    return jax.vjp(lambda w: jnp.take(w, ids, axis=0), w)[1](
        dy.astype(jnp.float32))[0]


def _zipf(rng, tokens, vocab):
    return np.minimum(rng.zipf(1.1, tokens) - 1, vocab - 1)


# (tokens, vocabulary, width, how the ids are drawn): a vocabulary that is
# no multiple of 128 nor of the run (three runs, the last of 276 rows); a
# table of 2 rows (one run of 128); one of 512 (one whole run); every token
# the same id of the second run; Zipf-drawn ids, most in the first run
CASES = {
    "ragged_vocabulary": (700, 1300, 256, lambda r: r.integers(0, 1300, 700)),
    "two_rows": (300, 2, 128, lambda r: r.integers(0, 2, 300)),
    "one_whole_run": (64, 512, 128, lambda r: r.integers(0, 512, 64)),
    "one_id": (520, 1100, 128, lambda r: np.full(520, 777)),
    "zipf": (1000, 2048, 128, lambda r: _zipf(r, 1000, 2048)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_float32_equals_the_scatter(case, interpreter):
    tokens, vocab, width, draw = CASES[case]
    rng = np.random.default_rng(3)
    ids = jnp.asarray(draw(rng), jnp.int32)
    dy = jnp.asarray(rng.standard_normal((tokens, width)), jnp.float32)
    got = eg.embedding_grad(ids, dy, vocab)
    want = scatter(ids, dy, vocab)
    assert got.shape == want.shape and got.dtype == jnp.float32
    # float32 sums in another order
    assert float(jnp.abs(got - want).max()) < 1e-5 * max(
        float(jnp.abs(want).max()), 1.0)


@pytest.mark.parametrize("case", list(CASES))
def test_bf16_cotangents_sum_in_float32(case, interpreter):
    """The 0/1 matrix times a bf16 value is the value: the float32
    scatter of the same bf16 values, to summation order; at `one_id` 520
    values add into one row, which a bf16 sum would lose."""
    tokens, vocab, width, draw = CASES[case]
    rng = np.random.default_rng(4)
    ids = jnp.asarray(draw(rng), jnp.int32)
    dy = jnp.asarray(rng.standard_normal((tokens, width)), jnp.bfloat16)
    got = eg.embedding_grad(ids, dy, vocab)
    want = scatter(ids, dy, vocab)
    assert got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) < 1e-5 * max(
        float(jnp.abs(want).max()), 1.0)


def test_empty_runs_are_written_as_zeros(interpreter):
    """Five runs of 512 rows, tokens in the second and the last alone: the
    others are visited to write their zeros (`moe_tgmm`'s contract), NaN
    nowhere."""
    vocab, width = 2500, 128
    ids = jnp.asarray([600, 2499, 601, 600, 2100], jnp.int32)
    dy = jnp.asarray(np.random.default_rng(5).standard_normal((5, width)),
                     jnp.bfloat16)
    got = np.asarray(eg.embedding_grad(ids, dy, vocab))
    assert eg.run_rows(vocab) == 512
    hit = np.zeros(vocab, bool)
    hit[[600, 601, 2100, 2499]] = True
    assert not got[~hit].any() and np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(scatter(ids, dy, vocab)),
                               atol=1e-6)


def test_run_rows_follow_the_table():
    assert [eg.run_rows(v) for v in (2, 128, 129, 512, 25008, 30522)] == [
        128, 128, 256, 512, 512, 512]


def _tensor_parallel_mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("batch", "model"))


def _batch_mesh(n=2):
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:n]), ("batch",))


def test_viable_is_shapes_dtype_mesh_and_backend(interpreter, monkeypatch):
    from jax.sharding import PartitionSpec as P

    viable = eg.embedding_grad_viable
    bf16 = jnp.bfloat16
    assert viable(4096, 25008, 2560, bf16, None)
    assert viable(32768, 2, 768, bf16, None)
    assert viable(1, 1, 768, "bfloat16", None)
    # narrow rows (the measured pair on each side is beside the constant);
    # no triple of tokens, rows and width is refused for itself
    assert not viable(16384, 37000, 512, bf16, None)
    assert not viable(32768, 512, 640, bf16, None)
    assert viable(4096, 20480, 2304, bf16, None)
    assert viable(8192, 24576, 2304, bf16, None)
    assert not viable(4096, 25008, 2560, jnp.float32, None)  # the scatter
    assert not viable(4096, 25008, 2560, jnp.float16, None)
    assert not viable(4096, 25008, 96, bf16, None)  # no whole lanes
    assert not viable(4096, 25008, 2600, bf16, None)
    assert viable(4096, 25008, 2560, bf16, _batch_mesh())
    assert not viable(4097, 25008, 2560, bf16, _batch_mesh())
    assert not viable(4096, 25008, 2560, bf16, _tensor_parallel_mesh())
    # the table sharded over its rows or its width; stated whole
    assert not viable(4096, 25008, 2560, bf16, _batch_mesh(),
                      P("batch", None))
    assert not viable(4096, 25008, 2560, bf16, None, P(None, "model"))
    assert viable(4096, 25008, 2560, bf16, _batch_mesh(), P(None, None))
    assert viable(4096, 25008, 2560, bf16, _batch_mesh(), P())
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET")
    assert not viable(4096, 25008, 2560, bf16, None)  # a CPU, no interpreter
    with pytest.raises(RuntimeError, match="embed_tgmm"):
        eg.embedding_grad(jnp.zeros(8, jnp.int32),
                          jnp.zeros((8, 128), bf16), 300)


def test_the_call_is_the_expert_kernel_under_its_own_name(interpreter):
    """700 tokens into 1,300 rows of 256 lanes in bf16: one call named
    `embed_tgmm` over the 0/1 matrix and the sorted cotangent, three runs
    of 512 rows out, nothing declared (the module's docstring has what a
    declaration cost on the chip)."""
    tokens, vocab, width = 700, 1300, 256
    ids = jnp.zeros(tokens, jnp.int32)
    dy = jnp.zeros((tokens, width), jnp.bfloat16)
    fn = lambda i, d: eg.embedding_grad(i, d, vocab)  # noqa: E731
    assert pallas_costs.declared(fn, ids, dy) == {"embed_tgmm": [None]}
    ((operands, results),) = pallas_costs.operand_shapes(
        fn, ids, dy)["embed_tgmm"]
    assert operands[-2:] == [(tokens, 512), (tokens, width)]
    assert results == [(3, 512, width)]


def test_the_expert_cells_metrics_do_not_count_the_kernel(interpreter):
    """`moe_gmm_ms_per_step` and `moe_gmm_calls_per_step` read the events
    named `moe_gmm` and `moe_tgmm`: the same kernel under this layer's
    name is not theirs, and the expert layer's call keeps its name and
    its declaration."""
    from paddle_tpu.ops.pallas import grouped_matmul as gm

    for name in ("moe_gmm_ms_per_step", "moe_gmm_calls_per_step"):
        with open(os.path.join(REPO, "benchmark", "layer_metrics",
                               name + ".json")) as f:
            pattern = re.compile(json.load(f)["args"]["name"])
        assert pattern.search("moe_tgmm") and pattern.search("%moe_tgmm.3")
        assert not pattern.search("embed_tgmm")
        assert not pattern.search("%embed_tgmm.1")
    x = jnp.zeros((64, 128), jnp.bfloat16)
    sizes = jnp.asarray([40, 24], jnp.int32)
    calls = pallas_costs.declared(lambda a, b, s: gm.moe_tgmm(a, b, s),
                                  x, x, sizes)
    assert list(calls) == ["moe_tgmm"]
    assert calls["moe_tgmm"][0].flops == 2 * 64 * 128 * 128


# ------------------------------------------------------------ the Program op


def _program(vocab, width, ids_shape, amp, padding_idx=None, tied=False):
    """An embedding (and, tied, a head over the same table) under a loss
    that weighs every output; the table's gradient fetched."""
    import paddle_tpu as fluid

    L = fluid.layers
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = L.data("ids", list(ids_shape), dtype="int64",
                     append_batch_size=False)
        weigh = L.data("weigh", [*ids_shape[:2], width],
                       append_batch_size=False)
        emb = L.embedding(ids, size=[vocab, width], padding_idx=padding_idx,
                          param_attr=fluid.ParamAttr(name="table"))
        loss = L.reduce_sum(L.elementwise_mul(L.cast(emb, "float32"), weigh))
        if tied:
            table = main.global_block().var("table")
            logits = L.matmul(L.cast(emb, "float32"), table, transpose_y=True)
            loss = loss + L.reduce_sum(L.elementwise_mul(logits, logits))
        (grad,) = fluid.backward.calc_gradient(
            loss, [main.global_block().var("table")])
    if amp:
        main._amp_dtype = jnp.bfloat16
    return main, startup, emb, grad


def _counters_of(run):
    from paddle_tpu import profiler

    before = profiler.counters()
    out = run()
    after = profiler.counters()
    bumped = {k: after.get(k, 0) - before.get(k, 0)
              for k in ("embed_grad_dispatch_grouped",
                        "embed_grad_dispatch_scatter")}
    return out, bumped, after


def _run(main, startup, feed, fetch):
    import paddle_tpu as fluid

    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        table = np.asarray(scope.get("table"))
        out = exe.run(main, feed=feed, fetch_list=fetch)
    return table, out


@pytest.mark.parametrize("path", ["grouped", "scatter_fp32", "scatter_cpu",
                                  "scatter_narrow"])
def test_op_in_a_program_takes_the_path_the_counters_show(path, monkeypatch):
    """Under AMP with the interpreter the grouped products; in float32,
    with no kernel to run, or at rows of 512 lanes, the scatter. Ids below
    0 read row 0 and a `padding_idx` row reads zeros and receives nothing,
    on both paths: both lie outside the `custom_vjp`."""
    if path == "scatter_cpu":
        monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    else:
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    amp = path != "scatter_fp32"
    vocab, pad = 700, 3
    width = 512 if path == "scatter_narrow" else eg.MIN_WIDTH
    rng = np.random.default_rng(7)
    ids = rng.integers(0, vocab, (4, 40, 1))
    ids[0, :5, 0] = -2
    ids[1, :7, 0] = pad
    weigh = rng.standard_normal((4, 40, width)).astype(np.float32)
    main, startup, emb, grad = _program(vocab, width, (4, 40, 1), amp, pad)
    (table, (out, got)), bumped, after = _counters_of(lambda: _run(
        main, startup, {"ids": ids, "weigh": weigh}, [emb, grad]))
    other = "scatter" if path == "grouped" else "grouped"
    # the forward op's lowering and the gradient op's replay of it
    assert bumped == {f"embed_grad_dispatch_{path.split('_')[0]}": 2,
                      f"embed_grad_dispatch_{other}": 0}
    if path == "grouped":
        assert after["embed_grad_run_rows"] == 512
    flat = ids[..., 0]
    rows = table[np.maximum(flat, 0)] * (flat != pad)[..., None]
    dy = weigh * (flat != pad)[..., None]
    if amp:
        rows = np.asarray(jnp.asarray(rows, jnp.bfloat16), np.float32)
        dy = np.asarray(jnp.asarray(dy, jnp.bfloat16), np.float32)
    np.testing.assert_allclose(np.asarray(out, np.float32), rows, atol=1e-6)
    want = np.zeros((vocab, width), np.float32)
    np.add.at(want, np.maximum(flat, 0).reshape(-1), dy.reshape(-1, width))
    assert got.dtype == np.float32 and not want[pad].any()
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_a_tied_tables_two_gradients_add_up(interpreter):
    """The table read by the embedding and again by a head: the grouped
    products' gradient and the product's are summed, as the scatter's was."""
    vocab, width = 640, eg.MIN_WIDTH
    rng = np.random.default_rng(8)
    ids = rng.integers(0, vocab, (2, 24, 1))
    weigh = rng.standard_normal((2, 24, width)).astype(np.float32)
    feed = {"ids": ids, "weigh": weigh}
    got, want = {}, {}
    for amp, into in ((True, got), (False, want)):
        main, startup, emb, grad = _program(vocab, width, (2, 24, 1), amp,
                                            tied=True)
        (_, (g,)), bumped, _ = _counters_of(
            lambda: _run(main, startup, feed, [grad]))
        into["grad"], into["bumped"] = g, bumped
    assert got["bumped"]["embed_grad_dispatch_grouped"] == 2
    assert want["bumped"]["embed_grad_dispatch_scatter"] == 2
    # bf16 rows and products against float32's
    scale = np.abs(want["grad"]).max()
    assert np.abs(got["grad"] - want["grad"]).max() < 2e-2 * scale


def _lowered(mesh, table, ids, dy, spec=None):
    """`lookup_table` and its gradient op lowered under `mesh`, as the
    executor calls them."""
    from paddle_tpu.ops.registry import LoweringContext, lower_op

    vocab, width = table.shape
    main = _program(vocab, width, ids.shape, amp=True)[0]
    if spec is not None:
        main._sharding_specs["table"] = spec
    ops = main.global_block().ops
    fwd = next(op for op in ops if op.type == "lookup_table")
    bwd = next(op for op in ops if op.type == "__auto_grad__"
               and op.attr("fwd_type") == "lookup_table")

    @jax.jit
    def run(table, ids, dy):
        ctx = LoweringContext(main, mesh=mesh)
        ctx.values.update({"table": table, "ids": ids})
        lower_op(ctx, fwd)
        ctx.values[bwd.input("GRAD_Out")[0]] = dy
        lower_op(ctx, bwd)
        return (ctx.values[fwd.output("Out")[0]],
                ctx.values[bwd.output("IGRAD_W")[0]])

    return run(table, ids, dy)


WIDTH = eg.MIN_WIDTH  # the narrowest rows the lowering admits


def _mesh_operands(seed):
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.standard_normal((700, WIDTH)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 700, (4, 24, 1)), jnp.int32)
    dy = jnp.asarray(rng.standard_normal((4, 24, WIDTH)), jnp.bfloat16)
    return table, ids, dy


def test_a_batch_mesh_runs_the_products_per_shard(interpreter):
    """Four rows on a mesh that shards `batch` two ways: each chip's
    tokens are a whole problem and the two tables are added up outside;
    equal to one device's."""
    table, ids, dy = _mesh_operands(9)
    (out, got), bumped, _ = _counters_of(
        lambda: _lowered(_batch_mesh(), table, ids, dy))
    assert bumped == {"embed_grad_dispatch_grouped": 2,
                      "embed_grad_dispatch_scatter": 0}
    (_, one), _, _ = _counters_of(lambda: _lowered(None, table, ids, dy))
    want = scatter(ids.reshape(-1), dy.reshape(-1, WIDTH), 700)
    assert got.shape == (700, WIDTH) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, one, atol=1e-5)
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("case", ["tensor_parallel", "table_by_rows"])
def test_every_other_mesh_keeps_the_scatter(case, interpreter):
    from jax.sharding import PartitionSpec as P

    table, ids, dy = _mesh_operands(10)
    mesh, spec = ((_tensor_parallel_mesh(), None) if case == "tensor_parallel"
                  else (_batch_mesh(), P("batch", None)))
    (out, got), bumped, _ = _counters_of(
        lambda: _lowered(mesh, table, ids, dy, spec))
    assert bumped == {"embed_grad_dispatch_grouped": 0,
                      "embed_grad_dispatch_scatter": 2}
    np.testing.assert_allclose(
        got, scatter(ids.reshape(-1), dy.reshape(-1, WIDTH), 700), atol=1e-5)
