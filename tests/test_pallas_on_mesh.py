"""The Pallas kernels on a data-parallel mesh (ops/pallas/on_mesh.py): on a
mesh that shards `batch` alone each shard calls the kernel on its rows, and
the result is the one-device kernel's on the global batch, dropout masks
included; every other mesh keeps XLA's lowering. In the Pallas interpreter
on four of the suite's eight virtual CPU devices."""

import contextlib
import importlib
import re

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
from kernel_cases import attn_program  # the op with its gradients

KEY = jax.random.key(0)


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


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


@pytest.mark.parametrize("n,k", [(4 * 512, 1024), (4 * 300, 1152)])
def test_rms_bwd_per_shard_equals_one_device(n, k):
    """RMSNorm's side of the same call: dx row for row, dscale as the sum
    of the shards' partial rows, each shard padding to its own blocks."""
    from paddle_tpu.ops.pallas.layer_norm import rms_bwd

    x, dy = (jax.random.normal(jax.random.fold_in(KEY, i), (n, k))
             for i in range(2))
    scale = jax.random.normal(jax.random.fold_in(KEY, 2), (k,))
    want = rms_bwd(x, dy, scale, 1e-6)
    mesh = _mesh()
    got = jax.jit(lambda *a: rms_bwd(*a, 1e-6, mesh=mesh))(x, dy, scale)
    np.testing.assert_array_equal(np.asarray(want[0]), np.asarray(got[0]))
    np.testing.assert_allclose(np.asarray(want[1]), np.asarray(got[1]),
                               rtol=1e-5, atol=1e-4)


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
    main, startup, fetches = attn_program(b, s, s, nh, dh, "bshd")
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


@pytest.mark.parametrize("case", ["model_2", "batch_undivided", "ring"])
def test_every_other_mesh_keeps_its_path(case, attn_path):
    """Tensor parallelism beside the batch axis, a batch of 6 on four
    shards, and ring sequence parallelism (below its threshold, so by
    name): the counters of before, and no per-shard call."""
    b, mesh, want = 8, _mesh(2, 2), "attn_dispatch_xla"
    if case == "batch_undivided":
        b, mesh = 6, _mesh()
    elif case == "ring":
        attn_path("ring")
        want = "attn_dispatch_ring"
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


def _custom_call_types(text, name):
    """Of each custom call named `name` in an optimised HLO module: (its
    operands', its results') types as the module prints them, in order,
    `bf16[1,4096,2048]` (the operands' from the call's
    `operand_layout_constraints`, where the line has them by type)."""
    def types(part):
        return [f"{kind}[{shape}]" for kind, shape in
                re.findall(r"\b([a-z]+[0-9]+)\[([0-9,]*)\]", part)]

    found = []
    for line in text.splitlines():
        head, call, rest = line.partition(" custom-call(")
        if not call or name not in head:
            continue
        operands = re.search(
            r"operand_layout_constraints=\{(.*?)\}, [a-z_]+=", rest)
        found.append((types(operands[1]), types(head.split("=", 1)[1])))
    return found


def _delta_rule_pair(statics, operands):
    """The delta-rule pair under `jax.vjp` on `operands`, compiled for
    their chip: (the optimised module's text, its memory analysis)."""
    from paddle_tpu.ops.pallas import kda_chunk

    def both(*operands):
        o, pull = jax.vjp(lambda *a: kda_chunk._core(*a, statics), *operands)
        return o, pull(o)

    compiled = jax.jit(both).lower(*operands).compile()
    return compiled.as_text(), compiled.memory_analysis()


@pytest.mark.parametrize("hk,per_head", [(32, False), (16, True)],
                         ids=["kda", "gdn"])
def test_kda_kernels_compile_for_a_v5e_chip_at_the_published_widths(
        topo, hk, per_head):
    """The delta-rule chunk kernels (ops/pallas/kda_chunk.py) at the
    shapes of the cells that run them: one 4,096-token row, 32 heads of
    128, bf16 products, q, k, v and the logits bf16 as the projections
    write them under AMP (since PR 65 the kernels make the norms, beta
    and a head's decay of them in VMEM), forward and backward; Kimi's
    call (a decay a channel, gated by XLA: the float32 log decay is the
    operand; a key head a value head) and Qwen3-Next's (a decay a head,
    its logits under A_log and the bias `[1, 32]` float32; 16 key heads,
    q and k read at `[1, 4096, 2048]`). The compiled calls' operands and
    results are read back: what crosses HBM, and in which dtype. Here with
    this file's topology because one process of a test run can describe
    it (the kernels' mathematics is tests/test_kda_kernel.py's and
    tests/test_gdn_kernel.py's). Nothing runs."""
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas import kda_chunk

    chip = SingleDeviceSharding(topo.devices[0])
    b, s, h, d = 1, 4096, 32, 128
    statics = kda_chunk._Statics(h, kda_chunk.CHUNKS_PER_STEP, jnp.bfloat16,
                                 False, s, h // hk, per_head)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    x, v, logits = sds((b, s, hk * d)), sds((b, s, h * d)), sds((b, s, h))
    numbers = sds((1, h), jnp.float32)
    g, gate = (logits, (numbers, numbers)) if per_head else (
        sds((b, s, h * d), jnp.float32), ())
    text, memory = _delta_rule_pair(statics, (x, x, v, g, logits, gate))
    names = ("gdn_fwd", "gdn_bwd") if per_head else ("kda_fwd", "kda_bwd")
    assert all(name in text for name in names)
    assert ("kda_fwd" in text) != per_head
    keys, values, heads = (f"bf16[{b},{s},{hk * d}]", f"bf16[{b},{s},{h * d}]",
                           f"bf16[{b},{s},{h}]")
    wide, each = f"f32[{b},{s},{h * d}]", f"f32[1,{h}]"
    states, rows = (f"f32[{b * h},{s // 64},{d},{d}]",
                    f"f32[{b * h},{s // 64},1,64]")
    if per_head:  # dq, dk float32 a value head: XLA adds a group's
        decay, grads = [heads, heads, each, each], [wide, wide, values,
                                                    rows, rows, rows]
    else:  # dq, dk bf16 as q, k arrived; the log decay's float32
        decay, grads = [wide, heads], [keys, keys, values, wide, rows]
    ((ins, outs),) = _custom_call_types(text, names[0])
    assert ins == [keys, keys, values] + decay and outs == [values, states]
    ((ins, outs),) = _custom_call_types(text, names[1])
    assert ins == [keys, keys, values] + decay + [states, values]
    assert outs == grads
    # the operands, their gradients and the 134 MB of chunk states
    assert memory.temp_size_in_bytes < 1 << 30


def test_gdn_kernels_compile_for_a_v5e_chip_at_heads_that_are_no_tile(topo):
    """The pair at Olmo-Hybrid's shape: one 4,096-token row, 30 heads of 96
    key lanes and 192 value lanes, four heads a grid step (blocks of 384
    and 768 lanes, the eighth step's hanging over the arrays' edge), each
    head's lanes cut out, padded to whole tiles and normed over its 96
    lanes in VMEM, beta 2 sigmoid: Mosaic takes the slices at lanes 96,
    192 and 288 and the stores back to them, and every operand and
    gradient by token is bf16 as the projections wrote it (PR 65).
    Nothing runs."""
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas import kda_chunk

    chip = SingleDeviceSharding(topo.devices[0])
    b, s, h, dk, dv = 1, 4096, 30, 96, 192
    held = kda_chunk.layout(h, dk, dv)[0]
    statics = kda_chunk._Statics(h, kda_chunk.lockstep_chunks(s, held),
                                 jnp.bfloat16, False, s, 1, True, held, 1e-6,
                                 2.0)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    x, v, g = sds((b, s, h * dk)), sds((b, s, h * dv)), sds((b, s, h))
    numbers = sds((1, h), jnp.float32)
    text, memory = _delta_rule_pair(statics, (x, x, v, g, g,
                                              (numbers, numbers)))
    assert "gdn_fwd" in text and "gdn_bwd" in text and "kda_fwd" not in text
    keys, values, heads = (f"bf16[{b},{s},{h * dk}]",
                           f"bf16[{b},{s},{h * dv}]", f"bf16[{b},{s},{h}]")
    each, rows = f"f32[1,{h}]", f"f32[{b},{h},{s // 64},1,64]"
    states = f"f32[{b},{h},{s // 64},{dv},{dk}]"
    ((ins, outs),) = _custom_call_types(text, "gdn_fwd")
    assert ins == [keys, keys, values, heads, heads, each, each]
    assert outs == [values, states]
    ((ins, outs),) = _custom_call_types(text, "gdn_bwd")
    assert ins == [keys, keys, values, heads, heads, each, each, states,
                   values]
    assert outs == [keys, keys, values, rows, rows, rows]
    # the operands, their gradients and the chunks' states (a state's 96
    # lanes lie in whole tiles in HBM: 189 MB where 141 are its numbers)
    assert memory.temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("theta,scaling", [
    (0.0, None), (10000.0, None),
    (500000.0, (16.0, 8192.0, 32.0, 1.0, 1.2772588722239782))],
    ids=["no_positions", "rope", "yarn"])
def test_qk_prep_kernels_compile_for_a_v5e_chip_at_the_published_widths(
        topo, monkeypatch, theta, scaling):
    """The kernel pair between the projections and the flash kernels
    (ops/pallas/qk_prep.py) at the shape of the cells that run it: one
    8,192-token row, 32 query heads over 4 key/value heads of 128, bf16 in
    and out; Trinity's full layer's call and its window layer's, and
    Mellum's full layer's, whose tables are YaRN's (the same kernels: the
    tables are inputs). Nothing runs."""
    text, memory = _qk_prep_compiled(
        topo, monkeypatch, 1, 8192, 32, 4, 128, epsilon=1e-5, theta=theta,
        scaling=scaling)
    assert "qk_prep_fwd" in text and "qk_prep_bwd" in text
    # q, k, v in, out and back, and nothing float32 of their size between
    assert "f32[1,8192" not in text and "f32[1,32,8192" not in text
    assert memory.temp_size_in_bytes < 1 << 20


def _qk_prep_compiled(topo, monkeypatch, b, s, h, g, d, normed=True, **kw):
    """`qk_prep` and its backward on bf16 operands, compiled for the
    topology's first chip as the chip would (no interpreter)."""
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas.qk_prep import qk_prep

    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET")
    monkeypatch.setattr(importlib.import_module(
        "paddle_tpu.ops.pallas.flash_attention"), "_use_pallas", lambda: True)
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def both(*a):
        # flat as the projections leave them, cut into heads on the way in
        o, pull = jax.vjp(lambda q, k, v, *w: qk_prep(
            *(t.reshape(b, s, -1, d) for t in (q, k, v)), *w, **kw), *a)
        return o, pull(o)

    compiled = jax.jit(both).lower(
        sds((b, s, h * d)), sds((b, s, g * d)), sds((b, s, g * d)),
        *[sds((d,), jnp.float32)] * (2 if normed else 0)).compile()
    return compiled.as_text(), compiled.memory_analysis()


def test_qk_prep_kernels_compile_for_a_v5e_chip_without_a_norm(
        topo, monkeypatch):
    """Ouro's call: one 4,096-token row, 16 query heads over 16 key/value
    heads of 128, positions and no QK-norm, bf16 in and out. The backward
    reads the three cotangents and the tables alone: its custom call has
    five operands, and no weight's partial sums come out. Nothing runs."""
    text, memory = _qk_prep_compiled(topo, monkeypatch, 1, 4096, 16, 16, 128,
                                     normed=False, theta=10000.0)
    assert "qk_prep_fwd" in text and "qk_prep_bwd" in text
    assert "f32[1,4096,2048]" not in text and "f32[1,16,4096" not in text
    (bwd,) = re.findall(r"qk_prep_bwd[.\d]* = \((.*?)\) custom-call\((.*?)\),",
                        text)
    assert bwd[0].count("bf16[1,4096,2048]") == 3 and "f32" not in bwd[0]
    assert len(bwd[1].split(", ")) == 5  # nothing of the forward's
    assert memory.temp_size_in_bytes < 5 << 20  # the two tables


def test_qk_prep_kernels_compile_for_a_v5e_chip_with_a_part_of_the_head_turned(
        topo, monkeypatch):
    """Qwen3-Next's call: one 4,096-token row, 16 query heads over 2
    key/value heads of 256 lanes of which the first 64 turn, two rolls a
    block and three tables. Nothing runs."""
    text, memory = _qk_prep_compiled(
        topo, monkeypatch, 1, 4096, 16, 2, 256, epsilon=1e-6, theta=1e7,
        rotary_dim=64)
    assert "qk_prep_fwd" in text and "qk_prep_bwd" in text
    assert "f32[1,4096,4096]" not in text and "f32[1,16,4096" not in text
    # the three [4096, 256] float32 tables, 4 MB each, and the weights'
    # partial sums
    assert memory.temp_size_in_bytes < 14 << 20


@pytest.mark.parametrize("shape,window,lanes,backward", [
    # JoyAI's and Kimi's latent layer: keys of 192 and values of 128
    ((1, 32, 32, 4096, 192, 128), 0, (256, 128), "fused"),
    # Trinity's window layer: one width, 32 query heads over 4
    ((1, 32, 4, 8192, 128, 128), 2048, (128, 128), "fused"),
    # Mellum's: a band as wide as one of the forward's blocks
    ((1, 32, 4, 8192, 128, 128), 1024, (128, 128), "fused"),
    # their full layers: the longest run of key blocks, all sixteen
    ((1, 32, 4, 8192, 128, 128), 0, (128, 128), "fused"),
    # LFM2's: heads of 64 in 128 lanes, 32 over 8
    ((1, 32, 8, 8192, 64, 64), 0, (128, 128), "fused"),
    # Phi-4's differential pairs: keys of 64 under values of 128
    ((1, 40, 20, 4096, 64, 128), 512, (128, 128), "fused"),
    # the pair, as a call whose key/value head does not fit VMEM gets it
    ((1, 32, 32, 4096, 192, 128), 0, (256, 128), "pair"),
    ((1, 32, 4, 8192, 128, 128), 2048, (128, 128), "pair"),
    # ... and a call that does not: 32,768 keys at 128 and 128 lanes
    ((1, 8, 1, 32768, 128, 128), 2048, (128, 128), "refused"),
], ids=["latent_192_128", "gqa_window_128", "gqa_window_1024", "gqa_full",
        "gqa_32_over_8_at_64", "differential_64_128", "latent_192_128_pair",
        "gqa_window_128_pair", "s32768_refused"])
def test_flash_kernels_compile_for_a_v5e_chip_at_the_published_widths(
        topo, shape, window, lanes, backward, monkeypatch):
    """The blocked attention kernels at the shapes of the cells that run
    them, bf16, through `jax.vjp`: Mosaic takes q, k, dq and dk at the
    keys' lanes and v, the output, dO and dv at the values' (the shapes
    the custom calls are held to in the compiled module), the forward at
    the 1,024 x 1,024 blocks the call picks for itself (their VMEM fits
    the chip's), and the backward as `flash_bwd_dkv_dq` with a key/value
    head's dk and dv resident (16 MiB at 8,192 keys and 256 lanes, under
    the 64 MiB the call asks for) or, where the rule refuses, as the pair.
    Nothing runs."""
    import re

    from jax.sharding import SingleDeviceSharding

    from paddle_tpu import profiler
    from paddle_tpu.ops.pallas import flash_attention

    chip = SingleDeviceSharding(topo.devices[0])
    b, h, hkv, s, d, dv = shape
    d_p, dv_p = lanes
    if backward == "pair":
        monkeypatch.setattr(importlib.import_module(
            "paddle_tpu.ops.pallas.flash_attention"),
            "_BWD_FUSED_VMEM_BYTES", 0)

    def sds(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=chip)

    def both(q, k, v):
        o, pull = jax.vjp(lambda *a: flash_attention(
            *a, causal=True, window=window), q, k, v)
        return o, pull(o)

    fused = profiler.counters().get("flash_bwd_fused_calls", 0)
    wide = profiler.counters().get("flash_fwd_wide_key_calls", 0)
    with _as_on_the_chip():
        text = jax.jit(both).lower(
            sds(b, h, s, d), sds(b, hkv, s, d), sds(b, hkv, s, dv)
        ).compile().as_text()
    assert profiler.counters()["flash_fwd_wide_key_calls"] == wide + 1
    assert (profiler.counters().get("flash_bwd_fused_calls", 0) - fused
            == (backward == "fused"))
    qs, ks = f"bf16[{b * h},{s},{d_p}]", f"bf16[{b * hkv},{s},{d_p}]"
    vs, outs = f"bf16[{b * hkv},{s},{dv_p}]", f"bf16[{b * h},{s},{dv_p}]"
    rows = f"f32[{b * h},1,{s}]"
    read = ["s32[1]", qs, ks, vs, outs, rows, rows]
    operands = {"flash_fwd": ["s32[1]", qs, ks, vs]}
    results = {"flash_fwd": [outs, rows]}
    if backward == "fused":
        operands["flash_bwd_dkv_dq"] = read
        results["flash_bwd_dkv_dq"] = [qs, ks, vs]
        assert "flash_bwd_dq" not in text
    else:
        operands.update(flash_bwd_dq=read, flash_bwd_dkv=read)
        results.update(flash_bwd_dq=[qs], flash_bwd_dkv=[ks, vs])
        assert "flash_bwd_dkv_dq" not in text
    shapes = re.compile(r"\w+\[[\d,]*\]").findall
    for name, want in operands.items():
        ((written, read),) = re.findall(
            rf"^\s*%?{name}[.\d]* = (.*?) custom-call\(.*?"
            r"operand_layout_constraints=\{(.*?\})\}, ", text, re.M)
        assert shapes(read) == want, name
        assert shapes(written) == results[name], name


def test_sparse_attention_kernels_compile_for_a_v5e_chip_at_keyes_widths(
        topo):
    """What `keye_vl2_ep16_s8192` adds to a step, at its shapes: one
    8,192-token row, the indexer's 16 heads of 64 on one key head
    (`sparse_index_fwd`, `sparse_index_bwd`), the selection of 2,048 keys
    a row (`sparse_select`, 64 rows of float32 scores resident), the
    flash kernels at 32 heads over 4 of 128 with the admission as an int8
    operand and their log-sum-exp rows returned, and the loss's target
    (`index_kl_target`, 32 heads' blocks resident). Nothing runs."""
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas import flash_attention, sparse_index

    chip = SingleDeviceSharding(topo.devices[0])
    b, s, h, g, d, hi, di, k = 1, 8192, 32, 4, 128, 16, 64, 2048

    def sds(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    def index(q, kk, w):
        out, pull = jax.vjp(lambda *a: sparse_index.index_scores(*a, 1 / 32),
                            q, kk, w)
        admit, tau = sparse_index.select(out, k)
        return admit, tau, pull(out)

    def attention(q, kk, v, scores, admit, rows):
        (o, lse), pull = jax.vjp(lambda *a: flash_attention(
            *a, causal=True, admit=admit, admit_keys=k, with_lse=True),
            q, kk, v)
        kl, loss_pull = jax.vjp(lambda i: sparse_index.index_kl(
            q, kk, lse, i, admit, d ** -0.5, k), scores)
        return kl, loss_pull(rows), pull((o, jnp.zeros_like(lse)))

    with _as_on_the_chip():
        text = jax.jit(index).lower(
            sds(b, hi, s, di), sds(b, s, di),
            sds(b, s, hi, dtype=jnp.float32)).compile().as_text()
        assert all(name in text for name in (
            "sparse_index_fwd", "sparse_index_bwd", "sparse_select"))
        assert "f32[1,16,8192,8192]" not in text  # no head's matrix
        compiled = jax.jit(attention).lower(
            sds(b, h, s, d), sds(b, g, s, d), sds(b, g, s, d),
            sds(b, s, s, dtype=jnp.float32), sds(b, s, s, dtype=jnp.int8),
            sds(b, s, dtype=jnp.float32)).compile()
    text = compiled.as_text()
    assert all(name in text for name in (
        "flash_fwd", "flash_bwd_dkv_dq", "index_kl_target"))
    assert "s8[1,8192,8192]" in text and "f32[1,32,8192,8192]" not in text
    # the loss walks an [s, s] float32 array in the target's kernel and
    # in one fusion, the gradient's: p, the scores and the admission in
    walks = re.findall(r"^%?fused_computation\S* \((.*?f32\[1,8192,8192\].*?)"
                       r"\) -> (\S+)", text, re.M)
    assert len(walks) == 1 and walks[0][1] == "f32[1,8192,8192]", walks
    assert walks[0][0].count("f32[1,8192,8192]") == 2
    assert walks[0][0].count("s8[1,8192,8192]") == 1
    # p is written over by the gradient; what is left is the rows' three
    # [s, 1] outputs in tiles of 128 lanes (12 MB) and the admission's
    # 64 MB where XLA fetches it into VMEM ahead of that fusion
    assert compiled.memory_analysis().temp_size_in_bytes < 80 << 20


@pytest.mark.parametrize("dtype,d_p,dv_p,blocks", [
    (jnp.bfloat16, 256, 256, (1024, 1024)),
    (jnp.bfloat16, 512, 512, (512, 1024)),
    (jnp.float32, 256, 128, (512, 1024)),
    (jnp.float32, 384, 256, (512, 1024)),
], ids=["bf16_256_256", "bf16_512_512", "float32_256_128",
        "float32_384_256"])
def test_flash_fwd_fits_vmem_at_the_largest_blocks_the_chooser_gives(
        topo, dtype, d_p, dv_p, blocks):
    """`_fwd_blocks` bounds the forward's blocks by an estimate of the
    step's VMEM: at the widths where the estimate lets the most through,
    Mosaic takes the kernel with everything a call can add (a key bias,
    dropout's hash of the block, no mask to skip a block by)."""
    import importlib

    from jax.sharding import SingleDeviceSharding

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    chip = SingleDeviceSharding(topo.devices[0])
    s, heads = 2048, 4
    assert fa._fwd_blocks(s, s, 512, 512, d_p, dv_p,
                          jnp.dtype(dtype).itemsize) == blocks

    def sds(*dims, dtype=dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    text = jax.jit(lambda q, k, v, bias, seed: fa._fwd_pallas(
        q, k, v, bias, seed, heads, sm_scale=0.1, causal=False,
        causal_offset=0, dropout=0.1, block_q=blocks[0], block_k=blocks[1])
    ).lower(sds(heads, s, d_p), sds(heads, s, d_p), sds(heads, s, dv_p),
            sds(1, 1, s, dtype=jnp.float32), sds(1, dtype=jnp.int32)
            ).compile().as_text()
    assert "flash_fwd" in text


@contextlib.contextmanager
def _as_on_the_chip():
    """The program asks whether Pallas can run, and here the answer is the
    interpreter's or the CPU's: steer it, in the test, to the chip's."""
    import importlib

    # the package exports a function of the same name over the module
    module = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    with pytest.MonkeyPatch.context() as patch:
        patch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
        patch.setattr(module, "_use_pallas", lambda: True)
        yield patch


def test_grouped_matmul_kernels_compile_for_a_v5e_chip_at_kimis_widths(topo):
    """The expert layer's kernel pair (ops/pallas/grouped_matmul.py) at
    the shapes of `kimi_linear_ep32_s4096`: a block of 8,192 sorted rows,
    8 groups of 2,304 x 1,024 and back, bf16 in, through `jax.vjp`, at
    the tile sizes the calls pick (Trinity's and Mellum's compile inside
    their whole steps below; the kernels' mathematics is
    tests/test_grouped_matmul.py's). Nothing runs."""
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas import grouped_matmul as gm

    chip = SingleDeviceSharding(topo.devices[0])
    rows, groups, hidden, width = 8192, 8, 2304, 1024

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def both(x, w, sizes):
        y, pull = jax.vjp(lambda x, w: gm.grouped_matmul(x, w, sizes), x, w)
        return y, pull(y)

    with _as_on_the_chip():
        for k, n in ((hidden, width), (width, hidden)):
            compiled = jax.jit(both).lower(
                sds((rows, k)), sds((groups, k, n), jnp.float32),
                sds((groups,), jnp.int32)).compile()
            text = compiled.as_text()
            assert "moe_gmm" in text and "moe_tgmm" in text
            # dx is the kernel's too, on the weights as they lie
            assert "ragged-dot-none" not in text
            # the weights' cast and the steps' lists: no [rows, .] array
            # beside the operands and the three results, and no
            # transposed copy of the weights
            assert compiled.memory_analysis().temp_size_in_bytes < (
                groups * k * n * 2 + (1 << 20))


def _step_for_v5e(topo, cell_name):
    """A cell's whole train step, built as its runner builds it and
    compiled for a described chip: the optimised HLO, what the lowering
    added to each counter, and the compiler's account of the memory.
    Nothing runs."""
    from benchmark.harness import spec
    from benchmark.tests.test_compile_v5e import lower_train_step

    with _as_on_the_chip():
        before = profiler.counters()
        compiled = lower_train_step(spec.cell(cell_name),
                                    topo.devices).compile()
        after = profiler.counters()
    return (compiled.as_text(),
            {n: v - before.get(n, 0) for n, v in after.items()},
            compiled.memory_analysis())


@pytest.fixture(scope="module")
def trinity_step(topo, run_dir):
    """Once a run of the tests for the two cases that read it, whichever
    workers they fall to (`decoder_suite.kept`): the module's text and
    the counters of a compile of a minute and more."""
    from decoder_suite import kept

    return kept(run_dir, "trinity_step_for_v5e",
                lambda: _step_for_v5e(topo, "trinity_mini_ep16_s8192")[:2])


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


def _grouped_products(text):
    """((outside, inside) every `while`) of the expert layers' grouped
    products in an optimised HLO module: the Pallas kernels' custom calls,
    and XLA's `ragged-dot` (the plain path's)."""
    import re

    from kernel_cases import in_and_out_of_whiles

    return (in_and_out_of_whiles(text, re.compile(
        r"%moe_t?gmm[.\d]* = .* custom-call\(").search),
        in_and_out_of_whiles(text, re.compile(
            r"%ragged-dot-none[.\d]* = ").search))


def _sums_onto_tokens(text):
    """(outside, inside) every `while`: the calls that sum a block's rows
    onto their tokens (`parallel/moe.py::_onto_tokens`)."""
    import re

    from kernel_cases import in_and_out_of_whiles

    return in_and_out_of_whiles(text, re.compile(
        r"%onto_tokens_tgmm[.\d]* = .* custom-call\(").search)


def test_trinity_step_compiled_for_v5e_makes_each_first_block_once(
        trinity_step):
    """Four expert layers: the 36 grouped products that run every step lie
    outside every `while` (a layer's first block: gate, up and down once
    for the forward op and the gradient op's replay, and the six of its
    backward); the overflow loops hold 3 and 9 a layer, for the trips a
    load past a quarter of the assignments costs. Every lowering took the
    kernels, and every product is one's: the forward products and the
    three `dx` of a backward are `moe_gmm`'s custom calls (`dx` on the
    weights read transposed: PR 73), the weight gradients `moe_tgmm`'s,
    and no `ragged-dot` is left in the step.
    A layer's first block sums its rows onto the tokens twice, once each
    way (the combine, and the gather's transpose, whose sort XLA shares
    with the combine's), by the call `onto_tokens_tgmm` and not by a
    `scatter`; XLA merges the replay's, so two a layer and not three; an
    overflow trip makes one each way."""
    text, bumped = trinity_step
    # forward and replay
    assert bumped["moe_dispatch_grouped"] == bumped["moe_dispatch_gmm"] == 8
    assert bumped["moe_onto_tokens_grouped"] == 8
    assert _grouped_products(text) == ((36, 48), (0, 0))
    assert _sums_onto_tokens(text) == (8, 8)
    assert not [line for line in text.split("\n")
                if " scatter(" in line and "f32[8192,2048]" in line]


def test_mellum_step_compiled_for_v5e_makes_each_first_block_once(topo):
    """`mellum2_ep4_s8192`, where the expert layer is most of the step:
    the same 36 and 48 products over 16 groups of 2,304 x 896, all the
    kernels', and temporaries no larger than the step held with
    `ragged-dot` (8.448 GB by this compile of PR 37's tree; the chip's
    peak stood at 15.667 of 16.9 GB): the kernels add metadata and no
    `[rows, .]` array, `dx` leaves its kernel in the rows' dtype (a
    float32 `[28672, 2304]` would be 264 MB a product) and no transposed
    copy of a weight is made."""
    text, bumped, memory = _step_for_v5e(topo, "mellum2_ep4_s8192")
    assert bumped["moe_dispatch_grouped"] == bumped["moe_dispatch_gmm"] == 8
    assert _grouped_products(text) == ((36, 48), (0, 0))
    # the sums onto the tokens: a float32 `[28672, 2304]` in token order is
    # a new transient of each, inside the same bound
    assert _sums_onto_tokens(text) == (8, 8)
    assert memory.temp_size_in_bytes <= 8.448e9


def test_scan_kernels_compile_for_a_v5e_chip_at_the_published_widths(topo):
    """The selective scan's kernel pair (ops/pallas/selective_scan.py) at
    the shape of the cell that runs it: one 4,096-token row, 5,120
    channels, a state of 16, bf16 rows with float32 steps, forward and
    backward (the backward's four `[64, 16, 8, 128]` float32 arrays in
    VMEM past Mosaic's 16 MiB default: the call raises its own limit).
    The kernels' mathematics is tests/test_selective_scan_kernel.py's.
    Nothing runs."""
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas import selective_scan as scan

    chip = SingleDeviceSharding(topo.devices[0])
    b, s, d, n = 1, 4096, 5120, 16

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def both(*operands):
        y, pull = jax.vjp(lambda *a: scan.selective_scan(*a)[0], *operands)
        return y, pull(y)

    with _as_on_the_chip():
        compiled = jax.jit(both).lower(
            sds((b, s, d)), sds((b, s, d), jnp.float32),
            sds((d, n), jnp.float32), sds((b, s, n)), sds((b, s, n)),
            sds((d,), jnp.float32)).compile()
    text = compiled.as_text()
    assert "ssm_scan_fwd" in text and "ssm_scan_bwd" in text
    # the rows turned to [s, 40, 128] and back, the 21 MB of states kept
    # and the partial sums: nothing of the trajectory's 1.34 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 29


@pytest.fixture(scope="module")
def phi4_step(topo):
    """Once for the tests that read it."""
    return _step_for_v5e(topo, "phi4_mini_flash_vp8_longdoc")


def test_phi4_step_compiled_for_v5e_runs_both_scans_in_the_kernels(phi4_step):
    """`phi4_mini_flash_vp8_longdoc`'s whole train step: the two Mamba
    layers' forward and gradient ops lower to the kernel pair and no
    `while` is left in the step (the chunked form's loops were the only
    ones), `Starts` is kept every 64 tokens, and the temporaries are
    under the 3.727 GB this compile gave the chunked form (PR 44's
    tree)."""
    text, bumped, memory = phi4_step
    assert bumped["ssm_dispatch_pallas"] == 2
    assert "ssm_dispatch_chunked" not in bumped or not bumped[
        "ssm_dispatch_chunked"]
    assert profiler.counters()["ssm_chunk_len"] == 64
    assert "ssm_scan_fwd" in text and "ssm_scan_bwd" in text
    assert " while(" not in text
    assert memory.temp_size_in_bytes < 3.4e9


def test_phi4_step_compiled_for_v5e_sums_the_tables_gradient_as_products(
        phi4_step):
    """The same step: the tied table's gradient from the embedding is the
    grouped products (`ops/pallas/embedding_grad.py`; the forward op's
    lowering and the gradient op's replay of it bump the counter), 49 runs
    of 512 rows under the kernel's own name, no `scatter` is left under
    `bwd/lookup_table_grad`, and the temporaries are no larger than the
    3.218 GB this compile gave the scatter (PR 45's tree)."""
    text, bumped, memory = phi4_step
    assert bumped["embed_grad_dispatch_grouped"] == 2
    assert not bumped.get("embed_grad_dispatch_scatter")
    assert profiler.counters()["embed_grad_run_rows"] == 512
    under = [line for line in text.splitlines()
             if "bwd/lookup_table_grad" in line]
    assert under and not any(" scatter(" in line for line in under)
    assert any("embed_tgmm" in line and "f32[49,512,2560]" in line
               for line in under)
    # the expert layer's name is on no call (the text's table of stack
    # frames does hold the function `moe_tgmm`, which the embedding calls)
    assert " custom-call(" in text and "%moe_tgmm" not in text
    assert memory.temp_size_in_bytes <= 3.218e9


def test_phi4_step_compiled_for_v5e_runs_each_conv_backward_in_one_call(
        phi4_step):
    """The same step: each of the two Mamba layers' `short_conv1d_grad` is
    one `short_conv_bwd` call (`ops/pallas/short_conv.py`) over the row's
    4,096 tokens and 5,120 channels as they arrive, and no float32 array
    of that shape is left under the scope (XLA's backward kept dpre, and
    before PR 46 four padded products of it)."""
    text, bumped, _ = phi4_step
    assert bumped["short_conv_dispatch_pallas"] == 2
    assert not bumped.get("short_conv_dispatch_xla")
    under = [line for line in text.splitlines()
             if "bwd/short_conv1d_grad" in line]
    calls = [line for line in under if "short_conv_bwd" in line
             and " custom-call(" in line]
    assert len(calls) == 2
    assert all("bf16[1,4096,5120]" in line and "f32[1,8,5120]" in line
               for line in calls)
    assert not any(" = f32[1,4096,5120]" in line for line in under)


@pytest.mark.parametrize("kernel", [True, False], ids=["kernels", "plain"])
def test_one_layers_step_compiled_for_v5e_makes_nine_products_and_twelve(
        topo, kernel):
    """`tests/test_moe_experts.py`'s one expert layer behind an `fc`, at
    widths of one lane slice, trained and compiled for the chip with the
    kernels and with `grouped_matmul_viable` saying no: nine grouped
    products outside every `while` and twelve inside either way: custom
    calls all on one path, `ragged-dot` all on the other, and the counter
    that says which."""
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas import grouped_matmul

    L = fluid.layers
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = L.data("x", [256, 128], append_batch_size=False)
        y, _ = L.moe_experts(L.fc(x, 128, bias_attr=False), experts_total=8,
                             experts_held=2, d_ff=128, k=2, scaling=2.446)
        loss = L.reduce_mean(L.square(y))
        fluid.optimizer.SGD(1.0).minimize(loss)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    chip = SingleDeviceSharding(topo.devices[0])
    with _as_on_the_chip() as patch:
        if not kernel:
            patch.setattr(grouped_matmul, "grouped_matmul_viable",
                          lambda *a: False)
        before = profiler.counters()
        compiled, feeds, _ = exe._prepare_run(
            main, {"x": np.zeros((256, 128), np.float32)}, [loss], scope)
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            (exe._assemble_state(compiled, scope), feeds, jax.random.key(0)))
        text = compiled.jit_fn.lower(*shapes).compile().as_text()
        after = profiler.counters()
    bumped = {n: after.get(n, 0) - before.get(n, 0)
              for n in ("moe_dispatch_grouped", "moe_dispatch_gmm",
                        "moe_onto_tokens_grouped")}
    assert bumped == {"moe_dispatch_grouped": 2,
                      "moe_dispatch_gmm": 2 if kernel else 0,
                      "moe_onto_tokens_grouped": 2 if kernel else 0}
    assert _grouped_products(text) == (
        ((9, 12), (0, 0)) if kernel else ((0, 0), (9, 12)))
    assert _sums_onto_tokens(text) == ((2, 2) if kernel else (0, 0))


@pytest.fixture(scope="module")
def lfm2_step(topo):
    """Once for the tests that read it."""
    return _step_for_v5e(topo, "lfm2_24b_ep8_longdoc")


def test_lfm2_step_compiled_for_v5e_runs_each_conv_backward_in_one_call(
        lfm2_step):
    """`lfm2_24b_ep8_longdoc`'s whole train step: each of the four gated
    convolution layers' `short_conv1d_grad` is one `short_conv_bwd` call
    without the SiLU over the row's 8,192 tokens (the kernel's longest
    row) and 2,048 channels at three taps, and no float32 array of that
    shape is left under the scope."""
    text, bumped, _ = lfm2_step
    assert bumped["short_conv_linear_calls"] == 4
    assert bumped["short_conv_dispatch_pallas"] == 4
    assert not bumped.get("short_conv_dispatch_xla")
    under = [line for line in text.splitlines()
             if "bwd/short_conv1d_grad" in line]
    calls = [line for line in under if "short_conv_bwd" in line
             and " custom-call(" in line]
    assert len(calls) == 4
    assert all("bf16[1,8192,2048]" in line and "f32[1,8,2048]" in line
               for line in calls)
    assert not any(" = f32[1,8192,2048]" in line for line in under)


def test_lfm2_step_compiled_for_v5e_takes_the_kernels_it_can_and_fits(
        lfm2_step):
    """The same step: the one attention layer's 64-lane heads go through
    the flash kernels (forward lowering and the gradient op's replay) and
    not through `qk_prep`, which takes whole 128-lane heads; the four
    expert layers' grouped products at 2,048 x 1,536 are the Pallas pair;
    the tied table's gradient is the grouped products; and the step is
    under 11 GB of a chip's 16.9 by the compiler's count (5.63 of them
    the 469.3M parameters and their two moments)."""
    text, bumped, memory = lfm2_step
    assert bumped["attn_dispatch_flash"] == 2
    assert not bumped.get("attn_qk_prep_fused")
    assert not bumped.get("attn_dispatch_xla")
    assert profiler.counters()["attn_kv_group"] == 4
    assert bumped["moe_dispatch_gmm"] == bumped["moe_dispatch_grouped"] == 8
    assert profiler.counters()["moe_block_rows"] == 8192
    assert bumped["embed_grad_dispatch_grouped"] == 2
    for kernel in ("flash_fwd", "flash_bwd_dkv_dq", "moe_gmm", "moe_tgmm",
                   "embed_tgmm"):
        assert kernel in text, kernel
    assert "flash_bwd_dq" not in text  # the backward is the one kernel
    assert "qk_prep" not in text
    assert abs(memory.argument_size_in_bytes / 1e9 - 5.63) < 0.01
    need = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            + memory.temp_size_in_bytes - memory.alias_size_in_bytes)
    assert need < 11e9


@pytest.fixture(scope="module")
def qwen3_next_step(topo):
    """Once for the test that reads it; the delta rule's dispatch asks its
    own module whether Pallas runs, and is steered there too."""
    from paddle_tpu.ops.pallas import kda_chunk

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(kda_chunk, "_use_pallas", lambda: True)
        patch.setattr(kda_chunk, "_product_dtype", lambda: jnp.bfloat16)
        return _step_for_v5e(topo, "qwen3_next_ep16_s4096")


def test_qwen3_next_step_compiled_for_v5e_takes_its_kernels_and_fits(
        qwen3_next_step):
    """`qwen3_next_ep16_s4096`'s whole train step: the three Gated
    DeltaNet layers run `gdn_fwd` and `gdn_bwd` on q and k as the
    convolution wrote them, bf16 `[1, 4096, 2048]` for 16 key heads, and
    on the decay's and beta's bf16 `[1, 4096, 32]` logits (PR 65: no
    float32 copy of q, k, beta or the decay is among the kernels'
    operands), with no float32 decay a channel anywhere in the step;
    each layer's convolution backward is one kernel call over the 8,192
    channels; the attention layer's 256-lane heads go through
    `qk_prep` with 64 lanes turned and the flash kernels' one-visit
    backward; the four expert layers' products at 2,048 x 512 are the
    Pallas pair under a softmax router; and the step is under 14 GB of a
    chip's 16.9 by the compiler's count (7.51 of them the 625.7M
    parameters and their two moments)."""
    text, bumped, memory = qwen3_next_step
    assert bumped["kda_dispatch_pallas"] == bumped["kda_decay_per_head"] == 6
    assert not bumped.get("kda_dispatch_chunked")
    assert profiler.counters()["kda_key_group"] == 2
    assert bumped["short_conv_dispatch_pallas"] == 3
    assert not bumped.get("short_conv_dispatch_xla")
    assert bumped["attn_dispatch_flash"] == bumped["attn_qk_prep_fused"] == 2
    assert bumped["flash_bwd_fused_calls"] == 2
    assert profiler.counters()["attn_rotary_lanes"] == 64
    assert profiler.counters()["attn_kv_group"] == 8
    assert bumped["moe_dispatch_gmm"] == bumped["moe_dispatch_grouped"] == 8
    assert bumped["moe_route_softmax"] == 8
    assert bumped["moe_shared_expert_gated"] == 4
    assert (profiler.counters()["moe_experts_held"],
            profiler.counters()["moe_experts_total"],
            profiler.counters()["moe_block_rows"]) == (32, 512, 10240)
    for kernel in ("gdn_fwd", "gdn_bwd", "short_conv_bwd", "qk_prep_fwd",
                   "qk_prep_bwd", "flash_fwd", "flash_bwd_dkv_dq", "moe_gmm",
                   "moe_tgmm", "embed_tgmm"):
        assert kernel in text, kernel
    assert "kda_fwd" not in text and "kda_bwd" not in text
    assert "flash_bwd_dq" not in text  # the backward is the one kernel
    calls = [line for line in text.splitlines()
             if " custom-call(" in line and "gdn_" in line]
    assert len(calls) == 6
    for name, behind in (("gdn_fwd", []), ("gdn_bwd", [
            "f32[32,64,128,128]", "bf16[1,4096,4096]"])):
        found = _custom_call_types(text, name)
        assert len(found) == 3
        for ins, outs in found:
            assert ins == ["bf16[1,4096,2048]"] * 2 + [
                "bf16[1,4096,4096]"] + ["bf16[1,4096,32]"] * 2 + [
                "f32[1,32]"] * 2 + behind, (name, ins)
            if behind:  # dq, dk float32 a value head, dv, three of rows
                assert outs == ["f32[1,4096,4096]"] * 2 + [
                    "bf16[1,4096,4096]"] + ["f32[32,64,1,64]"] * 3, outs
    # under the op's scopes no decay a channel, as a head's array or side
    # by side, and no repeated q or k: the only float32 arrays as wide as
    # the value heads are dq and dk as `gdn_bwd` writes them (the gated
    # norm after the op holds its own, under `rms_norm`)
    under = [line for line in text.splitlines() if "kda_attention" in line]
    assert under and not any("f32[1,4096,32,128]" in l for l in under)
    assert all("gdn_bwd" in l for l in under if "f32[1,4096,4096]" in l)
    convs = [line for line in text.splitlines()
             if " custom-call(" in line and "short_conv_bwd" in line]
    assert len(convs) == 3 and all("bf16[1,4096,8192]" in l for l in convs)
    assert abs(memory.argument_size_in_bytes / 1e9 - 7.51) < 0.01
    need = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            + memory.temp_size_in_bytes - memory.alias_size_in_bytes)
    assert need < 14e9
