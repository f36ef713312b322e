"""The Pallas kernels on a data-parallel mesh (ops/pallas/on_mesh.py): on a
mesh that shards `batch` alone each shard calls the kernel on its rows, and
the result is the one-device kernel's on the global batch, dropout masks
included; every other mesh keeps XLA's lowering. In the Pallas interpreter
on four of the suite's eight virtual CPU devices."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu.ops.pallas import on_mesh
from paddle_tpu.ops.pallas.layer_norm import ln_bwd
from paddle_tpu.ops.pallas.mha_short import mha_short
from paddle_tpu.parallel.mesh import build_mesh
from test_flash_attention import _attn_program  # the op with its gradients

KEY = jax.random.key(0)


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.delenv("PADDLE_TPU_ATTN_DISPATCH", raising=False)
    monkeypatch.delenv("PADDLE_TPU_SP_MODE", raising=False)


def _mesh(batch=4, model=1):
    return build_mesh(batch=batch, model=model,
                      devices=jax.devices()[:batch * model])


def _qkv(b, h, sq, sk, d, use_bias):
    q, k, v = (jax.random.normal(jax.random.fold_in(KEY, i), (b, s, h * d))
               for i, s in ((1, sq), (2, sk), (3, sk)))
    bias = None
    if use_bias:
        bias = jnp.where(
            jax.random.uniform(jax.random.fold_in(KEY, 4), (b, sk)) > 0.2,
            0.0, -1e30).astype(jnp.float32).at[:, 0].set(0.0)
    return q, k, v, bias


def _out_and_grads(fn, q, k, v):
    """The output and dq, dk, dv of a scalar of it, jitted as a step is."""
    def both(q, k, v):
        return fn(q, k, v), jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2))(q, k, v)

    out, grads = jax.jit(both)(q, k, v)
    return [out, *grads]


# ------------------------------------------------------------- the rule


@pytest.mark.parametrize("batch,model,leading,want", [
    (None, 1, (6,), 1),       # no mesh: the kernel, directly
    (1, 1, (6,), 1),          # one device
    (4, 1, (8, 8), 4),        # batch alone, dividing: per shard
    (4, 1, (8, 6), 0),        # a batch the axis does not divide
    (2, 2, (8,), 0),          # tensor parallel beside it
    (1, 4, (8,), 0),
])
def test_batch_shards_reads_the_mesh_and_the_leading_dimensions(
        batch, model, leading, want):
    mesh = None if batch is None else _mesh(batch, model)
    assert on_mesh.batch_shards(mesh, *leading) == want


# ------------------------------------------------------------ attention


@pytest.mark.parametrize("use_bias", [False, True])
@pytest.mark.parametrize("causal", [False, True])
def test_mha_short_per_shard_equals_one_device_on_the_global_batch(
        use_bias, causal):
    """b=8 over four shards, sq != sk with a padded key length: forward
    and dq, dk, dv, to the tolerance of tests/test_mha_short.py."""
    b, h, sq, sk, d = 8, 2, 32, 40, 64
    q, k, v, bias = _qkv(b, h, sq, sk, d, use_bias)
    want = _out_and_grads(
        lambda q, k, v: mha_short(q, k, v, h, bias=bias, causal=causal),
        q, k, v)
    mesh = _mesh()
    got = _out_and_grads(
        lambda q, k, v: mha_short(q, k, v, h, bias=bias, causal=causal,
                                  mesh=mesh), q, k, v)
    assert got[0].sharding.is_equivalent_to(
        NamedSharding(mesh, P("batch")), got[0].ndim)
    for a, b_ in zip(want, got):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-5)


def test_dropout_on_the_mesh_is_the_global_batchs_mask():
    """The hash sees the global row: the mesh result equals the one-device
    kernel's on the whole batch, gradients included, and no two (row,
    head) pairs of the global batch share a mask."""
    b, h, s, d = 8, 4, 64, 64
    q, k, v, _ = _qkv(b, h, s, s, d, False)
    rng = jax.random.fold_in(KEY, 7)
    mesh = _mesh()

    def attend(mesh):
        return lambda q, k, v: mha_short(q, k, v, h, dropout=0.3,
                                         rng_key=rng, mesh=mesh)

    want = _out_and_grads(attend(None), q, k, v)
    got = _out_and_grads(attend(mesh), q, k, v)
    for a, b_ in zip(want, got):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-6)
    # uniform probabilities and v == ones: an output row is its mask's
    # kept share, so equal masks would read as equal rows
    zeros, ones = jnp.zeros_like(q), jnp.ones_like(v)
    kept = np.asarray(jax.jit(attend(mesh))(zeros, zeros, ones))
    assert abs(kept.mean() - 1.0) < 0.02
    per_head = kept.reshape(b, s, h, d)[..., 0]
    assert len({per_head[i, :, j].tobytes()
                for i in range(b) for j in range(h)}) == b * h
    local = np.asarray(attend(None)(zeros[:2], zeros[:2], ones[:2]))
    np.testing.assert_array_equal(kept[:2], local)   # shard 0 starts at row 0
    assert not np.array_equal(kept[2:4], local)      # shard 1 does not


# ------------------------------------------------------------ LayerNorm


@pytest.mark.parametrize("n,k", [(4 * 512, 128), (4 * 300, 256)])
def test_ln_bwd_per_shard_equals_one_device(n, k):
    """dx row for row; dscale and dbias as the sum of the shards' partial
    rows (300 rows a shard: each shard pads to its own whole blocks)."""
    x, dy = (jax.random.normal(jax.random.fold_in(KEY, i), (n, k))
             for i in range(2))
    mean = x.mean(1)
    rstd = jax.lax.rsqrt(x.var(1) + 1e-5)
    scale = jax.random.normal(jax.random.fold_in(KEY, 2), (k,))
    want = ln_bwd(x, dy, mean, rstd, scale)
    mesh = _mesh()
    got = jax.jit(lambda *a: ln_bwd(*a, mesh=mesh))(x, dy, mean, rstd, scale)
    np.testing.assert_array_equal(np.asarray(want[0]), np.asarray(got[0]))
    for a, b_ in zip(want[1:], got[1:]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-5,
                                   atol=1e-4)


# ------------------------------------------------- what a shard declares


@pytest.mark.parametrize("kernel", ["mha_short_fwd", "mha_short_bwd"])
@pytest.mark.parametrize("use_bias", [False, True])
def test_a_quarter_of_the_batch_declares_a_quarter_of_the_cost(kernel,
                                                               use_bias):
    """The `cost_estimate` is computed inside the manual region from the
    rows the shard holds, so each of four chips' custom calls carries its
    own work: a quarter of the one-device call's FLOPs, transcendentals
    and bytes (every operand is split along the batch)."""
    from pallas_costs import declared, numbers

    b, h, s, d = 8, 2, 32, 64
    q, k, v, bias = _qkv(b, h, s, s, d, use_bias)

    def call(mesh):
        return jax.grad(lambda q, k, v: jnp.sum(mha_short(
            q, k, v, h, bias=bias, causal=True, mesh=mesh)),
            argnums=(0, 1, 2))

    (one,) = declared(call(None), q, k, v)[kernel]
    (shard,) = declared(call(_mesh()), q, k, v)[kernel]
    assert all(n % 4 == 0 for n in numbers(one))
    assert numbers(shard) == tuple(n // 4 for n in numbers(one))


# ------------------------------------------------- through the lowering


def _run_attn_program(b, mesh, s=32, nh=2, dh=64):
    """The op's values and gradients, and the counters its lowering left."""
    rng = np.random.RandomState(3)
    main, startup, fetches = _attn_program(b, s, s, nh, dh, "bshd")
    feed = {n: rng.randn(b, s, nh * dh).astype("float32") for n in "qkv"}
    feed["bias"] = np.where(rng.rand(b, s) > 0.2, 0.0, -1e9).astype("float32")
    feed["bias"][:, 0] = 0.0
    exe = fluid.Executor(fluid.CPUPlace())
    profiler.reset_profiler()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        program = main
        if mesh is not None:
            program = fluid.CompiledProgram(main).with_data_parallel()
            program._mesh = mesh
        vals = exe.run(program, feed=feed, fetch_list=fetches)
    seen = {k: v for k, v in profiler.counters().items()
            if k.startswith(("attn_dispatch_", "pallas_on_mesh_")) and v}
    return [np.asarray(x) for x in vals], seen


def test_the_op_on_a_batch_mesh_takes_the_kernel_per_shard():
    want, seen = _run_attn_program(8, None)
    calls = seen["attn_dispatch_short"]
    assert seen == {"attn_dispatch_short": calls}
    got, seen = _run_attn_program(8, _mesh())
    assert seen == {"attn_dispatch_short": calls,
                    "pallas_on_mesh_calls": calls}
    for a, b_ in zip(want, got):
        np.testing.assert_allclose(a, b_, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", ["model_2", "batch_undivided", "ulysses",
                                  "ring"])
def test_every_other_mesh_keeps_its_path(case, monkeypatch):
    """Tensor parallelism beside the batch axis, a batch of 6 on four
    shards, and both sequence-parallel modes: the counters of before,
    and no per-shard call."""
    b, mesh, want = 8, _mesh(2, 2), "attn_dispatch_xla"
    if case == "batch_undivided":
        b, mesh = 6, _mesh()
    elif case != "model_2":
        monkeypatch.setenv("PADDLE_TPU_SP_MODE", case)
        want = f"attn_dispatch_{case}"
    vals, seen = _run_attn_program(b, mesh)
    assert set(seen) == {want}
    assert all(np.isfinite(v).all() for v in vals)


# ------------------------- compiled for a described v5e 2x2, without a chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever says "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_kernels_per_shard_compile_for_four_v5e_chips(topo, monkeypatch):
    """BERT-base's attention at the four-chip cell's shapes, forward and
    backward with bias and dropout, and its LayerNorm backward: Mosaic
    takes each shard's call inside the manual region, and the partial
    dscale/dbias rows meet in an all-reduce outside it. Nothing runs."""
    import importlib

    module = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET")
    monkeypatch.setattr(module, "_use_pallas", lambda: True)  # as on the chip
    mesh = build_mesh(batch=4, devices=list(topo.devices))
    rows, rep = NamedSharding(mesh, P("batch")), NamedSharding(mesh, P())

    def sds(shape, dtype, sharding=rows):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    b, s, nh, dh = 1024, 128, 12, 64
    key = jax.eval_shape(lambda: jax.random.key(0))

    def attention(q, k, v, bias, key):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(mha_short(
            q, k, v, nh, bias=bias, dropout=0.1, rng_key=key,
            mesh=mesh).astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)

    x = sds((b, s, nh * dh), jnp.bfloat16)
    text = jax.jit(attention).lower(
        x, x, x, sds((b, s), jnp.float32),
        sds(key.shape, key.dtype, rep)).compile().as_text()
    assert "mha_short_fwd" in text and "mha_short_bwd" in text

    n, k = b * s, nh * dh
    text = jax.jit(lambda *a: ln_bwd(*a, mesh=mesh)).lower(
        sds((n, k), jnp.bfloat16), sds((n, k), jnp.bfloat16),
        sds((n,), jnp.float32), sds((n,), jnp.float32),
        sds((k,), jnp.float32, rep)).compile().as_text()
    assert "ln_bwd" in text and "all-reduce" in text


def test_kda_kernels_compile_for_a_v5e_chip_at_the_published_widths(topo):
    """The KDA chunk kernels (ops/pallas/kda_chunk.py) at the shape of the
    cell that runs them: one 4,096-token row, 32 heads of 128, bf16
    products and values, forward and backward. Here with this file's
    topology because one process of a test run can describe it (the
    kernels' mathematics is tests/test_kda_kernel.py's). Nothing runs."""
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas import kda_chunk

    chip = SingleDeviceSharding(topo.devices[0])
    b, s, h, d = 1, 4096, 32, 128
    statics = (h, kda_chunk.CHUNKS_PER_STEP, jnp.bfloat16, False, s)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    x, v = sds((b, s, h * d)), sds((b, s, h * d), jnp.bfloat16)

    def both(q, k, v, g, beta):
        o, pull = jax.vjp(
            lambda *a: kda_chunk._core(*a, statics), q, k, v, g, beta)
        return o, pull(o)

    compiled = jax.jit(both).lower(x, x, v, x, sds((b, s, h))).compile()
    text = compiled.as_text()
    assert "kda_fwd" in text and "kda_bwd" in text
    # the operands, their gradients and the 134 MB of chunk states
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("theta,scaling", [
    (0.0, None), (10000.0, None),
    (500000.0, (16.0, 8192.0, 32.0, 1.0, 1.2772588722239782))],
    ids=["no_positions", "rope", "yarn"])
def test_qk_prep_kernels_compile_for_a_v5e_chip_at_the_published_widths(
        topo, theta, scaling):
    """The kernel pair between the projections and the flash kernels
    (ops/pallas/qk_prep.py) at the shape of the cells that run it: one
    8,192-token row, 32 query heads over 4 key/value heads of 128, bf16 in
    and out; Trinity's full layer's call and its window layer's, and
    Mellum's full layer's, whose tables are YaRN's (the same kernels: the
    tables are inputs). Nothing runs."""
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas import qk_prep

    chip = SingleDeviceSharding(topo.devices[0])
    b, s, h, g, d = 1, 8192, 32, 4, 128
    bf16 = jnp.dtype(jnp.bfloat16)
    statics = (h, g, 1e-5, theta, qk_prep.ROWS, bf16, bf16, scaling, False)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def both(q, k, v, wq, wk):
        o, pull = jax.vjp(lambda *a: qk_prep._core(*a, statics),
                          q, k, v, wq, wk)
        return o, pull(o)

    compiled = jax.jit(both).lower(
        sds((b, s, h * d)), sds((b, s, g * d)), sds((b, s, g * d)),
        sds((d,), jnp.float32), sds((d,), jnp.float32)).compile()
    text = compiled.as_text()
    assert "qk_prep_fwd" in text and "qk_prep_bwd" in text
    # q, k, v in, out and back, and nothing float32 of their size between
    assert "f32[1,8192" not in text and "f32[1,32,8192" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.fixture(scope="module")
def trinity_step(topo):
    """The cell's whole train step, built as its runner builds it and
    compiled for a described chip, once for the tests that read it: the
    optimised HLO, and what the lowering added to each counter. Nothing
    runs."""
    import importlib

    from benchmark.harness import spec
    from benchmark.tests.test_compile_v5e import lower_train_step

    module = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    with pytest.MonkeyPatch.context() as patch:
        patch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
        patch.setattr(module, "_use_pallas", lambda: True)  # as on the chip
        before = profiler.counters()
        text = lower_train_step(spec.cell("trinity_mini_ep16_s8192"),
                                topo.devices).compile().as_text()
        after = profiler.counters()
    return text, {n: v - before.get(n, 0) for n, v in after.items()}


def test_trinity_step_compiled_for_v5e_holds_no_float32_array_of_q(
        trinity_step):
    """The kernel pair is in the step five times each way, and
    the float32 `[1, 8192, 32, 128]` and `[1, 8192, 4, 128]` views of
    QK-norm and rotation that XLA laid out its own way and relaid (215 and
    231 mentions in the parent's step, 28 copies) are gone, in any layout
    and under either shape."""
    import re

    text, bumped = trinity_step
    # the forward op and the gradient op's replay, five layers
    assert bumped["attn_qk_prep_fused"] == 10
    calls = re.findall(r"^\s*%?(qk_prep_\w+?|flash_\w+?)[.\d]* = .* custom-call\(",
                       text, re.M)
    assert calls.count("qk_prep_fwd") == 5 and calls.count("qk_prep_bwd") == 5
    assert calls.count("flash_fwd") == 5  # still one a layer, still shared
    for shape in ("1,8192,32,128", "1,8192,4096", "1,8192,4,128",
                  "1,8192,512"):
        assert f"f32[{shape}]" not in text, shape


def test_trinity_step_compiled_for_v5e_makes_each_first_block_once(
        trinity_step):
    """Four expert layers: the 36 `ragged-dot` calls that run every step
    lie outside every `while` (a layer's first block: gate, up and down
    once for the forward op and the gradient op's replay, and the six of
    its backward); the overflow loops hold 3 and 9 a layer, for the trips
    a load past a quarter of the assignments costs."""
    import re

    from test_moe_experts import in_and_out_of_whiles

    text, bumped = trinity_step
    assert bumped["moe_first_block_shared"] == 8  # forward and replay
    assert bumped["moe_dispatch_grouped"] == 8
    assert in_and_out_of_whiles(text, re.compile(
        r"%ragged-dot-none[.\d]* = ").search) == (36, 48)
