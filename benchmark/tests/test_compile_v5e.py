"""Compile the real train steps for a described TPU v5e, without a chip.

This settles, before any chip time is spent, that the `bert_base_s512`
step, the transformer's step with its 37,000-row softmax and the
four-chip data-parallel step fit a chip's 16 GB, and that Mosaic accepts
`ln_bwd` at these shapes. Nothing runs: a
compile that passes is not a chip run and gives no time.

One file, with the topology described inside a fixture (only one process
may load the TPU's library; a second test file could land on another
pytest worker and skip in silence).
"""

from __future__ import annotations

import numpy as np
import pytest

from benchmark.harness import spec

# the table has the published 16 GB; the device holds 16 GiB, of which the
# runtime keeps a part: the chip run reads the real peak (`peak_hbm_gb`)
HBM = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever says "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def as_on_tpu(monkeypatch):
    """The program asks `jax.default_backend()` whether Pallas can run,
    and here that is the CPU: steer it, in the test, to what it answers
    on the chip."""
    import importlib

    # the package exports a function of the same name over the module
    module = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(module, "_use_pallas", lambda: True)


def lower_train_step(cell, devices):
    """The cell's train step, built as the runner builds it, lowered for
    `devices` with shapes in place of arrays."""
    import jax
    from jax.sharding import NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as fluid
    from paddle_tpu.scope import Scope

    from benchmark.runners.train_loop import _optimizer

    config, traffic = cell["config"], cell["traffic"]
    adapter = spec.plugin("models", config["adapter"])
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        built = adapter.build(config, traffic)
        _optimizer(fluid, config).minimize(main.global_block().var(built["loss"]))
    exe, scope = fluid.Executor(fluid.CPUPlace()), Scope()

    # the state's shapes, from the startup program traced and never run
    init, _, _ = exe._prepare_run(startup, {}, [], scope)
    _, shapes = jax.eval_shape(
        init.jit_fn, exe._assemble_state(init, scope), {}, jax.random.key(0))
    for name, sds in shapes.items():
        scope.set(name, sds)

    batch = adapter.make_batch(np.random.RandomState(0), config, traffic)
    block = main.global_block()
    feed_sig = tuple(
        (n, batch[n].shape, "int32" if batch[n].dtype.kind == "i" else "float32")
        for n in sorted(built["feeds"]))
    if cell["chips"] == 1:
        compiled = exe._compile(main, block, feed_sig, [built["loss"]], scope,
                                is_test=False)
        one = SingleDeviceSharding(devices[0])
        place = lambda name, kind: one  # noqa: E731
    else:
        from paddle_tpu.parallel.mesh import build_mesh

        mesh = build_mesh(batch=cell["chips"], model=1, pipe=1,
                          devices=list(devices)[:cell["chips"]])
        compiled = exe._compile(
            main, block, feed_sig, [built["loss"]], scope, is_test=False,
            mesh=mesh, sharding_specs=main._sharding_specs,
            build_strategy=fluid.compiler.BuildStrategy())
        shardings = {"state": compiled.state_shardings,
                     "feed": compiled.feed_shardings}
        place = lambda name, kind: shardings.get(kind, {}).get(  # noqa: E731
            name, NamedSharding(mesh, P()))

    state = {n: jax.ShapeDtypeStruct(scope.get(n).shape, scope.get(n).dtype,
                                     sharding=place(n, "state"))
             for n in compiled.state_names}
    feeds = {n: jax.ShapeDtypeStruct(shape, dtype, sharding=place(n, "feed"))
             for n, shape, dtype in feed_sig}
    key = jax.eval_shape(lambda: jax.random.key(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=place("", "rng"))
    return compiled.jit_fn.lower(state, feeds, key)


def fits(cell_name, compiled):
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"{cell_name}: arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
          f"outputs {mem.output_size_in_bytes / 1e9:.2f} GB, aliased "
          f"{mem.alias_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB: {need / 1e9:.2f} GB a chip")
    assert need < HBM


@pytest.mark.parametrize("cell_name", ["bert_base_s512", "bert_base_s128_dp4",
                                       "transformer_base_s64"])
def test_train_step_compiles_for_v5e_and_fits(cell_name, topo, as_on_tpu):
    compiled = lower_train_step(spec.cell(cell_name), topo.devices).compile()
    fits(cell_name, compiled)
    text = compiled.as_text()
    if cell_name.endswith("dp4"):
        assert "all-reduce" in text  # the gradient exchange GSPMD put in
        assert "ln_bwd" not in text  # LayerNorm backward is XLA's on a mesh
    else:
        assert text.count("tpu_custom_call") >= 26  # Mosaic took ln_bwd


def test_resnet50_step_compiles_for_v5e_and_fits(topo, as_on_tpu):
    """The adapter that ships without a cell, at b=128 and 224x224."""
    cell = spec.resolve({"config": "resnet50_imagenet",
                         "traffic": "imagenet_b128", "chips": 1})
    fits("resnet50_b128", lower_train_step(cell, topo.devices).compile())
