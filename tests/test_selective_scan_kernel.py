"""The selective scan's kernel pair (`ops/pallas/selective_scan.py`) under
the Pallas interpreter: value and all six gradients against Mamba-1's
recurrence taken one token at a time and against the chunked form of
`ops/ssm_ops.py` (each side and each gradient compiled); float32 inside under bf16 operands; the state carried
over grid steps and reset between rows; the declared cost; which path
the two ops take, what `Starts` they declare and what the counters say;
and the benchmark's data file for the kernels' time."""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import ssm_ops
from paddle_tpu.ops.pallas import selective_scan as kernel
from kernel_cases import SSM_NAMES as NAMES
from kernel_cases import loss_grads, value_and_grads
from kernel_cases import ssm_operands as operands
from kernel_cases import ssm_recurrence

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def interpreter(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


def close(got, want, name="y", rel=5e-6):
    """To `rel` of the largest value: float32 sums in another order."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, name
    assert np.abs(got - want).max() < rel * max(np.abs(want).max(), 1.0), name


def in_kernels(*args):
    return kernel.selective_scan(*args)[0]


recurrence = jax.jit(ssm_recurrence)


# (b, s, d_inner, d_state): rows of whole blocks (64), of one block and a
# ragged second, shorter than a block, one token; channels of one register
# (1,024), of a register padded from 128 and from 384 lanes; states of 16
# and 8; batch 1, 2 and 3
CASES = {"two_blocks_b2": (2, 128, 1024, 16),
         "ragged_b2": (2, 70, 128, 8),
         "shorter_than_a_block": (3, 37, 384, 16),
         "one_token": (1, 1, 128, 8),
         "two_registers_b1": (1, 65, 2048, 8)}


@pytest.mark.parametrize("case", list(CASES))
def test_kernels_equal_the_recurrence_and_the_chunked_form(case, interpreter):
    args = operands(*CASES[case], seed=2)
    w = jnp.asarray(np.random.RandomState(1).randn(*args[0].shape),
                    jnp.float32)
    (got, grads), (want, grads_want), (chunked, grads_chunked) = (
        value_and_grads(fn, args, w)
        for fn in (in_kernels, ssm_recurrence, ssm_ops.selective_scan))
    starts = jax.jit(lambda *t: kernel.selective_scan(*t)[1])(*args)
    b, s, d, n = CASES[case]
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert starts.shape == (-(-s // kernel.BLOCK), b, n, d)
    scale = max(float(jnp.abs(want).max()), 1.0)
    assert float(jnp.abs(got - want).max()) < 2e-6 * scale
    assert float(jnp.abs(got - chunked).max()) < 2e-6 * scale
    for name, g, g_want, g_chunked in zip(NAMES, grads, grads_want,
                                          grads_chunked):
        assert g.shape == g_want.shape and g.dtype == g_want.dtype, name
        scale = max(float(jnp.abs(g_want).max()), 1.0)
        assert float(jnp.abs(g - g_want).max()) < 5e-6 * scale, name
        assert float(jnp.abs(g - g_chunked).max()) < 5e-6 * scale, name


def test_the_states_kept_are_the_recurrences(interpreter):
    """`Starts[j]` is the state after `j * BLOCK` tokens, 0 for the first
    block of every row: the scratch is zeroed between rows."""
    b, s, d, n = 2, 150, 128, 8
    x, delta, a, bm, cm, dskip = operands(b, s, d, n, seed=7)
    _, starts = kernel.selective_scan(x, delta, a, bm, cm, dskip)
    assert starts.shape == (3, b, n, d) and starts.dtype == jnp.float32
    assert not bool(jnp.any(starts[0]))

    def token(h, xs):
        x, delta, bm = xs
        h = (jnp.exp(delta[..., None] * a) * h
             + (delta * x)[..., None] * bm[:, None, :])
        return h, h

    _, states = jax.lax.scan(
        token, jnp.zeros((b, d, n), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, delta, bm)))
    for j in (1, 2):
        want = jnp.swapaxes(states[j * kernel.BLOCK - 1], 1, 2)
        assert float(jnp.abs(starts[j] - want).max()) < 2e-6 * max(
            float(jnp.abs(want).max()), 1.0)


def test_steps_of_any_size_overflow_nothing_in_the_kernels(interpreter):
    """`tests/test_selective_scan.py`'s case on the kernels: steps up to
    e^6 a token under A = -15. The exponent is `Delta A`, at most 0."""
    args = operands(1, 32, 128, 8, seed=4, step=(-8.0, 6.0))
    got = jax.jit(in_kernels)(*args)
    grads = loss_grads(in_kernels, args, 1.0)
    assert all(bool(jnp.isfinite(t).all()) for t in (got, *grads))
    want = recurrence(*args)
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(jnp.abs(want).max())


def test_float32_inside_the_kernels_under_bf16_operands(interpreter):
    """bf16 in and out, the state and the decays float32: against the
    recurrence in float32 on the same rounded operands the output differs
    by its own rounding to bf16; the gradients leave in their operands'
    dtypes and differ from float32's on the same operands by theirs."""
    x, delta, a, bm, cm, dskip = operands(1, 256, 128, 8, seed=5,
                                          step=(-6.0, -3.0))
    low = [t.astype(jnp.bfloat16) for t in (x, delta, bm, cm)]
    args = (low[0], low[1], a, low[2], low[3], dskip)
    got = in_kernels(*args)
    assert got.dtype == jnp.bfloat16
    up = [t.astype(jnp.float32) for t in low]
    wide = (up[0], up[1], a, up[2], up[3], dskip)

    def rms(got, want):
        got = got.astype(jnp.float32)
        return float(jnp.sqrt(jnp.mean((got - want) ** 2))
                     / jnp.sqrt(jnp.mean(want ** 2)))

    assert rms(got, recurrence(*wide)) < 3e-3  # half an ulp of bf16, 2^-9
    grads = loss_grads(in_kernels, args, 1.0)
    grads_want = loss_grads(recurrence, wide, 1.0)
    for name, t, g, g_want in zip(NAMES, args, grads, grads_want):
        assert g.dtype == t.dtype, name
        assert rms(g, g_want) < (3e-3 if g.dtype == jnp.bfloat16 else 1e-5), \
            name


def test_declared_cost_is_the_count_by_hand():
    """A row of 100 tokens (two blocks kept), 1,024 channels, 16 state
    lanes, bf16 rows: one FLOP an operation of the docstring's formulas,
    one exponential a state element a pass, every operand and output
    once at its unpadded shape."""
    b, s, d, n = 2, 100, 1024, 16
    bf16, f32 = jnp.bfloat16, jnp.float32
    fwd = kernel._cost(False, b, s, d, n, (bf16, bf16, bf16, bf16))
    bwd = kernel._cost(True, b, s, d, n, (bf16, bf16, bf16, bf16))
    state = b * s * d * n
    # h: Delta A, decay h, u B, +; y: C h, +    u, D x, +
    assert fwd.flops == 6 * state + 3 * b * s * d
    # h again 4; g 2; dC 2; dB 2; du 2; decay g, h_prev 2; dDelta 2; dA 2
    # u; dx 3; dDelta's du x 2; dD 2
    assert bwd.flops == 18 * state + 8 * b * s * d
    assert fwd.transcendentals == bwd.transcendentals == state
    rows = 2 * b * s * d * 2 + 2 * b * s * n * 2  # x, Delta, B, C
    params = d * n * 4 + d * 4
    starts = b * 2 * n * d * 4
    assert fwd.bytes_accessed == rows + params + starts + b * s * d * 2
    assert bwd.bytes_accessed == 2 * (rows + params) + starts + b * s * d * 2


def _tensor_parallel_mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("batch", "model"))


def _batch_mesh(n=2):
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:n]), ("batch",))


def test_viable_is_shapes_mesh_and_backend(interpreter, monkeypatch):
    viable = kernel.selective_scan_viable
    assert viable(4096, 5120, 16, None)
    assert viable(1, 128, 8, None)
    assert not viable(4096, 5120, 4, None)  # the rehearsal preset's state
    assert not viable(4096, 96, 16, None)  # no whole group of lanes
    assert not viable(4096, 5120, 16, _tensor_parallel_mesh())
    assert viable(4096, 5120, 16, _batch_mesh(), batch=2)
    assert not viable(4096, 5120, 16, _batch_mesh(), batch=3)
    # what the op declares of Starts follows the same function
    assert ssm_ops.n_chunks(4096, 5120, 16) == 4096 // kernel.BLOCK
    assert ssm_ops.n_chunks(4096, 5120, 4) == 4096 // ssm_ops.CHUNK
    assert ssm_ops.n_chunks(37, 128, 8) == 1
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET")
    assert not viable(4096, 5120, 16, None)  # a CPU, no interpreter
    assert ssm_ops.n_chunks(4096, 5120, 16) == 4096 // ssm_ops.CHUNK
    with pytest.raises(RuntimeError, match="selective_scan"):
        kernel.selective_scan(*operands(1, 8, 128, 8))


def _program(b, s, d, n):
    import paddle_tpu as fluid

    L = fluid.layers
    shapes = {"x": (b, s, d), "delta": (b, s, d), "a": (d, n),
              "b": (b, s, n), "c": (b, s, n), "d": (d,)}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        vs = [L.data(name, list(shapes[name]), append_batch_size=False)
              for name in NAMES]
        for v in vs:
            v.stop_gradient = False
        y = L.selective_scan(*vs)
        w = np.random.RandomState(1).randn(b, s, d).astype(np.float32)
        loss = L.reduce_sum(L.elementwise_mul(y, L.assign(w)))
        grads = fluid.backward.calc_gradient(loss, vs)
    return main, shapes, y, grads, w


def _counters_of(run):
    from paddle_tpu import profiler

    before = profiler.counters()
    out = run()
    after = profiler.counters()
    bumped = {k: after.get(k, 0) - before.get(k, 0)
              for k in ("ssm_dispatch_pallas", "ssm_dispatch_chunked")}
    return out, bumped, after


@pytest.mark.parametrize("path", ["pallas", "chunked"])
def test_op_in_a_program_takes_the_path_the_call_shows(path, monkeypatch):
    """The same Program with and without the interpreter: the kernels and
    `Starts` a block of 64, or the chunked form and a chunk of 8; the
    declared shapes are the traced ones either way; one bump a lowering
    (the gradient op reads `Starts` and lowers no forward)."""
    import paddle_tpu as fluid
    from tools.verify_bench_programs import compare_static_vs_traced

    if path == "pallas":
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    b, s, d, n = 2, 70, 128, 8
    args = operands(b, s, d, n, seed=6)
    main, shapes, y, grads, w = _program(b, s, d, n)
    starts = main.global_block().ops[
        [op.type for op in main.global_block().ops].index("selective_scan")
    ].output("Starts")[0]
    kept = kernel.BLOCK if path == "pallas" else ssm_ops.CHUNK
    assert tuple(main.global_block().var(starts).shape) == (
        -(-s // kept), b, n, d)
    n_ops, mismatches, unknown = compare_static_vs_traced(
        main, {k: (v, "float32") for k, v in shapes.items()})
    assert n_ops >= 2 and mismatches == [] and unknown == []
    exe = fluid.Executor(fluid.CPUPlace())
    got, bumped, after = _counters_of(lambda: exe.run(
        main, feed={k: np.asarray(v) for k, v in zip(NAMES, args)},
        fetch_list=[y, *grads]))
    other = "chunked" if path == "pallas" else "pallas"
    assert bumped == {f"ssm_dispatch_{path}": 1, f"ssm_dispatch_{other}": 0}
    assert (after["ssm_state_size"], after["ssm_chunk_len"]) == (n, kept)
    close(got[0], recurrence(*args))
    want = loss_grads(ssm_recurrence, args, w)
    for name, g, g_want in zip(NAMES, got[1:], want):
        close(g, g_want, name)


def _lowered(mesh, args, dy):
    """The two ops' lowerings under `mesh`, as the executor calls them."""
    from paddle_tpu.ops.registry import LoweringContext, lower_op

    b, s, d, n = (*args[0].shape, args[2].shape[1])
    main = _program(b, s, d, n)[0]
    ops = {op.type: op for op in main.global_block().ops}

    @jax.jit
    def run(*values):
        ctx = LoweringContext(main, mesh=mesh)
        ctx.values.update(dict(zip(NAMES, values[:6])))
        lower_op(ctx, ops["selective_scan"])
        fwd = ops["selective_scan"]
        ctx.values[ops["selective_scan_grad"].input("GRAD_Y")[0]] = values[6]
        lower_op(ctx, ops["selective_scan_grad"])
        return (ctx.values[fwd.output("Y")[0]],
                ctx.values[fwd.output("Starts")[0]],
                *(ctx.values[ops["selective_scan_grad"].output(
                    "IGRAD_" + slot)[0]] for slot in ssm_ops._SLOTS))

    return run(*args, dy)


def test_a_batch_mesh_runs_the_kernels_per_shard(interpreter):
    """Two rows on a mesh that shards `batch` two ways: a chip's row is a
    whole problem, the parameters' gradients are the rows' partials added
    up outside."""
    args = operands(2, 70, 128, 8, seed=8)
    dy = jnp.asarray(np.random.RandomState(2).randn(2, 70, 128), jnp.float32)
    (y, starts, *grads), bumped, _ = _counters_of(
        lambda: _lowered(_batch_mesh(), args, dy))
    assert bumped == {"ssm_dispatch_pallas": 1, "ssm_dispatch_chunked": 0}
    assert starts.shape == (2, 2, 8, 128)
    close(y, recurrence(*args))
    want = loss_grads(ssm_recurrence, args, dy)
    for name, g, g_want in zip(NAMES, grads, want):
        close(g, g_want, name)


def test_a_tensor_parallel_mesh_keeps_the_chunked_form(interpreter):
    """Shapes and backend admit the kernels, the mesh does not: the
    chunked form runs, `Starts` leaves thinned to the declared blocks and
    the gradient op rebuilds its chunks' states."""
    args = operands(2, 70, 128, 8, seed=9)
    dy = jnp.asarray(np.random.RandomState(3).randn(2, 70, 128), jnp.float32)
    (y, starts, *grads), bumped, after = _counters_of(
        lambda: _lowered(_tensor_parallel_mesh(), args, dy))
    assert bumped == {"ssm_dispatch_pallas": 0, "ssm_dispatch_chunked": 1}
    assert after["ssm_chunk_len"] == ssm_ops.CHUNK
    assert starts.shape == (ssm_ops.n_chunks(70, 128, 8), 2, 8, 128) == (
        2, 2, 8, 128)
    _, kept = kernel.selective_scan(*args)
    close(starts, kept, "Starts")
    close(y, recurrence(*args))
    want = loss_grads(ssm_recurrence, args, dy)
    for name, g, g_want in zip(NAMES, grads, want):
        close(g, g_want, name)


def test_the_kernels_time_has_a_metric_in_phi4s_cell_alone():
    """`benchmark/layer_metrics/phi4_ssm_kernel_ms_per_step.json` loads
    through the harness, admits `phi4_mini_flash_vp8_longdoc` and no other
    cell, matches the kernels' names, and `BENCHMARK.json` mirrors it."""
    import re

    from benchmark.harness import spec

    name = "phi4_ssm_kernel_ms_per_step"
    metric = spec.load("layer_metrics", name)
    assert metric["kind"] == "trace_kernel"
    assert metric["where"] == {"config.adapter": ["phi4_flash"]}
    pattern = re.compile(metric["args"]["name"])
    assert all(pattern.search(n) for n in (
        "ssm_scan_fwd", "ssm_scan_bwd", "%ssm_scan_bwd.3"))
    assert not any(pattern.search(n) for n in (
        "flash_fwd", "kda_bwd", "fusion.ssm", "bwd/selective_scan_grad"))
    cells = [c for c in spec.names("workloads")
             if name in {m["name"] for m in spec.layer_metrics(spec.cell(c))}]
    assert cells == ["phi4_mini_flash_vp8_longdoc"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:  # by name: later
        (entry,) = [m for m in json.load(f)["per_layer"]  # PRs append
                    if m["name"] == name]
    assert entry == {"name": name, "workloads": cells,
                     **{k: metric[k] for k in ("unit", "better", "source",
                                               "layer", "moves")}}
    assert (entry["unit"], entry["better"], entry["layer"], entry["moves"]) \
        == ("ms", "lower", "Pallas kernels", "train_examples_per_s")
