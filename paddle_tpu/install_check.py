"""Install sanity check (reference: python/paddle/fluid/install_check.py
run_check — trains a tiny fc model single-device and, when multiple devices
exist, data-parallel, then prints a success banner). Like the reference,
which picks CUDAPlace or CPUPlace by what the build has, it checks the
backend JAX found and names it in the banner."""

from __future__ import annotations

import numpy as np

__all__ = ["run_check"]


def run_check():
    import jax

    from . import (
        CPUPlace,
        CompiledProgram,
        Executor,
        Program,
        Scope,
        TPUPlace,
        initializer,
        layers,
        optimizer,
        program_guard,
        scope_guard,
    )
    from .framework import unique_name

    def _build():
        x = layers.data("install_check_x", [2])
        y = layers.data("install_check_y", [1])
        pred = layers.fc(
            x, 1, param_attr=initializer.Constant(0.5),
        )
        loss = layers.mean(layers.square_error_cost(pred, y))
        optimizer.SGD(0.01).minimize(loss)
        return loss

    dev = jax.devices()[0]
    place = TPUPlace() if dev.platform == "tpu" else CPUPlace()
    n_dev = len(jax.devices())
    # a multiple of the device count >= 16 so the dp mesh divides evenly
    bs = n_dev * max(2, -(-16 // n_dev))
    xv = np.random.rand(bs, 2).astype("float32")
    yv = (xv.sum(1, keepdims=True) * 0.3).astype("float32")

    # single-device
    main, startup = Program(), Program()
    with program_guard(main, startup):
        with unique_name.guard():
            loss = _build()
    exe = Executor(place)
    scope = Scope()
    with scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed={"install_check_x": xv, "install_check_y": yv},
                fetch_list=[loss], scope=scope)

    n = n_dev
    if n > 1:
        main2, startup2 = Program(), Program()
        with program_guard(main2, startup2):
            with unique_name.guard():
                loss2 = _build()
        exe2 = Executor(place)
        scope2 = Scope()
        with scope_guard(scope2):
            exe2.run(startup2)
            cp = CompiledProgram(main2).with_data_parallel(
                loss_name=loss2.name)
            exe2.run(cp, feed={"install_check_x": xv,
                               "install_check_y": yv},
                     fetch_list=[loss2], scope=scope2)
        print(f"Your paddle_tpu works well on {n} {dev.platform} devices "
              f"({dev.device_kind}, mesh dp={n}).")
    else:
        print(f"Your paddle_tpu works well on SINGLE {dev.platform} device "
              f"({dev.device_kind}).")
    print("paddle_tpu is installed successfully!")
