"""Worker process for the multi-process distributed test (the reference's
test_dist_base.py:442 runtime: real OS processes on localhost, loss
comparison against single-process). Launched with the PADDLE_* env
contract; exercises fleet.init -> jax.distributed -> CompiledProgram over
the multi-process mesh."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# virtual 2-device CPU host: must land in XLA_FLAGS BEFORE the backend
# initializes
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=2"
    ).strip()


def main():
    import jax

    jax.config.update("jax_platforms", "cpu")
    # multi-process collectives on the CPU backend need the gloo
    # transport selected before backend init (the default 'none' raises
    # "Multiprocess computations aren't implemented on the CPU backend")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.incubate.fleet.collective import fleet

    fleet.init()  # PADDLE_* env -> jax.distributed.initialize
    rank = fleet.worker_index()
    nproc = fleet.worker_num()
    assert jax.process_count() == nproc, (jax.process_count(), nproc)
    assert len(jax.devices()) == 2 * nproc

    main_p = fluid.Program()
    startup = fluid.Program()
    main_p.random_seed = 123
    with fluid.program_guard(main_p, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data("x", [16])
            y = fluid.layers.data("y", [1])
            h = fluid.layers.fc(
                x, 32, act="relu",
                param_attr=fluid.initializer.Constant(0.05),
            )
            pred = fluid.layers.fc(
                h, 1, param_attr=fluid.initializer.Constant(0.1),
            )
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y)
            )
            opt = fleet.distributed_optimizer(fluid.optimizer.SGD(0.1))
            opt.minimize(loss)

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    compiled = fluid.CompiledProgram(main_p).with_data_parallel(
        loss_name=loss.name
    )

    steps = int(os.environ["DIST_TEST_STEPS"])
    global_b = int(os.environ["DIST_TEST_BATCH"])
    local_b = global_b // nproc
    rng = np.random.RandomState(3)
    w_true = rng.randn(16, 1).astype("float32")
    losses = []
    for _ in range(steps):
        xv = rng.randn(global_b, 16).astype("float32")
        yv = xv @ w_true
        lo = rank * local_b
        (lv,) = exe.run(
            compiled,
            feed={"x": xv[lo: lo + local_b], "y": yv[lo: lo + local_b]},
            fetch_list=[loss],
        )
        losses.append(float(np.asarray(lv).reshape(-1)[0]))
    if rank == 0:
        with open(os.environ["DIST_TEST_OUT"], "w") as f:
            json.dump(losses, f)


if __name__ == "__main__":
    main()
