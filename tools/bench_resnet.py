"""ResNet-50 feed-path DIAGNOSTIC on the real chip: device-staged vs
exe.run-path (DataLoader double-buffer) feeds. The driver metric is
bench.py's bench_resnet (canonical); this tool isolates the feed-path
delta. Diagnostics to stderr."""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu.models.resnet import (  # noqa: E402
    RESNET50_TRAIN_FLOPS_PER_IMG as TRAIN_FLOPS_PER_IMG,
)
from paddle_tpu.place import peak_bf16_flops  # noqa: E402


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main():
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.models.resnet import resnet50

    b = int(os.environ.get("RN_BATCH", "128"))
    steps = int(os.environ.get("RN_STEPS", "10"))
    amp = os.environ.get("RN_AMP", "1") == "1"

    img = fluid.layers.data("img", [b, 3, 224, 224],
                            append_batch_size=False)
    label = fluid.layers.data("label", [b, 1], dtype="int64",
                              append_batch_size=False)
    _, loss, _, _ = resnet50(img, label)
    opt = fluid.optimizer.Momentum(0.1, 0.9)
    if amp:
        from paddle_tpu.contrib import mixed_precision as mp

        opt = mp.decorate(opt)
    opt.minimize(loss)

    exe = fluid.Executor(fluid.TPUPlace())
    t0 = time.time()
    exe.run(fluid.default_startup_program())
    log(f"startup {time.time() - t0:.1f}s")

    rng = np.random.RandomState(0)
    imgs = rng.rand(b, 3, 224, 224).astype("float32")
    lbls = rng.randint(0, 1000, (b, 1)).astype("int64")

    # device-staged
    feed_dev = {
        "img": jax.device_put(jnp.asarray(imgs)),
        "label": jax.device_put(jnp.asarray(lbls)),
    }
    t0 = time.time()
    out = exe.run(feed=feed_dev, fetch_list=[loss])
    log(f"first step (compile) {time.time() - t0:.1f}s loss={out[0][0]}")
    for _ in range(3):
        exe.run(feed=feed_dev, fetch_list=[loss], return_numpy=False)
    t0 = time.time()
    for _ in range(steps):
        out = exe.run(feed=feed_dev, fetch_list=[loss], return_numpy=False)
    np.asarray(out[0])
    dt = time.time() - t0
    dev_ips = b * steps / dt
    mfu = (dev_ips * TRAIN_FLOPS_PER_IMG
           / peak_bf16_flops(jax.devices()[0].device_kind))
    log(f"device-staged: {dev_ips:,.0f} img/s ({dt / steps * 1e3:.1f} ms"
        f"/step, MFU~{mfu * 100:.1f}%)")

    # exe.run path with DataLoader prefetch (the user training loop)
    from paddle_tpu.reader.dataloader import DataLoader

    loader = DataLoader.from_generator(feed_list=[img, label], capacity=8)

    def gen():
        for _ in range(steps + 4):
            yield [imgs, lbls]

    loader.set_batch_generator(gen)
    it = iter(loader)
    warm = next(it)
    exe.run(feed=warm, fetch_list=[loss], return_numpy=False)
    t0 = time.time()
    n = 0
    for feed in it:
        out = exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
        n += 1
    np.asarray(out[0])
    dt = time.time() - t0
    run_ips = b * n / dt
    log(f"exe.run+DataLoader: {run_ips:,.0f} img/s "
        f"({dt / n * 1e3:.1f} ms/step over {n} steps)")
    log(f"exe.run path at {run_ips / dev_ips * 100:.0f}% of device-staged")


if __name__ == "__main__":
    main()
