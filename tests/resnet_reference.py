"""ResNet in training mode, plain `jax.numpy`: the reference that
`tests/test_resnet_reference.py` holds the program's train step to.

Written from the papers, not from `paddle_tpu`'s lowerings nor from the
benchmark's adapter (which has the evaluation forward only):

- He et al. 2015, arXiv:1512.03385, table 1 and figure 5: a 7x7/2 stem of
  64 filters, a 3x3/2 max pool, four stages of basic (3x3, 3x3) or
  bottleneck (1x1, 3x3, 1x1 at four times the width) blocks at widths 64,
  128, 256 and 512, a batch normalisation after every convolution, an
  identity shortcut or, where shape changes, a projection (1x1 convolution
  and batch normalisation), global average pool, one linear layer.
- Ioffe and Szegedy 2015, arXiv:1502.03167, algorithm 1: a batch's mean
  and biased variance over (N, H, W), epsilon inside the root.
- Sutskever et al. 2013 for Momentum without Nesterov:
  `v = mu v + g; p = p - rate v`.

Departures from the papers, each the program's own and followed here:
the stride of a bottleneck sits on its 3x3 convolution (Goyal et al.
2017, arXiv:1706.02677: "v1.5"), not on its first 1x1; epsilon is 1e-5;
the moving statistics move a tenth of the way to the batch's (momentum
0.9) and the moving variance takes the biased batch variance (Ioffe and
Szegedy: the unbiased one); no weight decay; the loss is the mean
negative log-likelihood of the labels.

Parameters go by the program's names: `<conv>.w_0` (OIHW), `<conv>_bn.w_0`
(scale), `.w_1` (shift), `.mean`, `.var`, the classifier's `fc_<n>.w_0`
`[features, classes]` and `.w_1`.

`precision`:

- `"float32"`: everything in float32 (the caller sets
  `jax.default_matmul_precision("highest")`).
- `"bf16_amp"`: the policy `mixed_precision.decorate` states, in this
  file's own words. Convolutions and the classifier's product read bf16
  and write bf16; activations between layers are bf16; a normalisation
  reads its bf16 input, computes statistics and the affine map in float32
  and writes bf16; logits, loss, master weights, velocity and moving
  statistics are float32.
- `"bf16"`: the nearest precision below. The normalisation's statistics
  and arithmetic are bf16 too (means and variances summed in bf16). The
  master weights and the update stay float32: a bf16 update at a small
  rate is lost whole, which would show nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

STAGES = {18: ((2, 2, 2, 2), False), 34: ((3, 4, 6, 3), False),
          50: ((3, 4, 6, 3), True), 101: ((3, 4, 23, 3), True)}
EPSILON = 1e-5
STATS_MOMENTUM = 0.9
PRECISIONS = ("float32", "bf16_amp", "bf16")


def split_state(state: dict):
    """The program's persistables by name -> (trainable parameters,
    moving statistics)."""
    stats = {n: v for n, v in state.items()
             if n.endswith(("_bn.mean", "_bn.var"))}
    params = {n: v for n, v in state.items()
              if n.endswith((".w_0", ".w_1"))}
    return params, stats


def _per_channel(v):
    return v.reshape(1, -1, 1, 1)


def _conv(x, w, stride, precision):
    pad = (w.shape[2] - 1) // 2
    if precision != "float32":
        x, w = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def _batch_norm(x, scale, shift, moving_mean, moving_var, precision):
    """Normalise `x` with its own batch's statistics. Returns the output
    (in `x`'s dtype) and the moving statistics after this batch."""
    held = x.dtype
    work = jnp.bfloat16 if precision == "bf16" else jnp.float32
    x = x.astype(work)
    mean = jnp.mean(x, axis=(0, 2, 3))
    var = jnp.mean(jnp.square(x - _per_channel(mean)), axis=(0, 2, 3))
    y = ((x - _per_channel(mean)) / jnp.sqrt(_per_channel(var) + EPSILON)
         * _per_channel(scale.astype(work)) + _per_channel(shift.astype(work)))
    moved = tuple(
        (STATS_MOMENTUM * old + (1 - STATS_MOMENTUM) * new.astype(jnp.float32))
        for old, new in ((moving_mean, mean), (moving_var, var)))
    return y.astype(held), moved


def loss_and_stats(params, stats, images, labels, depth,
                   precision="float32"):
    """Mean negative log-likelihood of `labels` `[n, 1]` on `images`
    `[n, 3, h, w]`, and every moving statistic after this batch."""
    assert precision in PRECISIONS, precision
    blocks, bottleneck = STAGES[depth]
    new_stats = {}

    def conv_bn(x, name, stride, relu):
        bn = name + "_bn"
        y, (new_stats[bn + ".mean"], new_stats[bn + ".var"]) = _batch_norm(
            _conv(x, params[name + ".w_0"], stride, precision),
            params[bn + ".w_0"], params[bn + ".w_1"],
            stats[bn + ".mean"], stats[bn + ".var"], precision)
        return jax.nn.relu(y) if relu else y

    x = conv_bn(images, "conv1", 2, True)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2), [(0, 0), (0, 0), (1, 1), (1, 1)])
    for stage, n in enumerate(blocks):
        for blk in range(n):
            stride = 2 if blk == 0 and stage > 0 else 1
            name = f"res{stage + 2}{chr(ord('a') + blk)}"
            if bottleneck:
                y = conv_bn(x, name + "_a", 1, True)
                y = conv_bn(y, name + "_b", stride, True)
                y = conv_bn(y, name + "_c", 1, False)
            else:
                y = conv_bn(x, name + "_a", stride, True)
                y = conv_bn(y, name + "_b", 1, False)
            if x.shape[1] != y.shape[1] or stride != 1:
                x = conv_bn(x, name + "_sc", stride, False)  # projection
            x = jax.nn.relu(x + y)
    pooled = jnp.mean(x.astype(jnp.float32), axis=(2, 3)).astype(x.dtype)
    (w_name,) = [n for n in params
                 if n.startswith("fc_") and n.endswith(".w_0")]
    w = params[w_name]
    if precision != "float32":
        w = w.astype(jnp.bfloat16)
    logits = (pooled @ w).astype(jnp.float32) + params[w_name[:-1] + "1"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels.astype(jnp.int32), axis=1)
    return jnp.mean(nll), new_stats


def train_step(params, stats, velocity, images, labels, depth, rate,
               mu=0.9, precision="float32"):
    """One Momentum step. Returns the loss before the step and the new
    parameters, moving statistics and velocity."""
    (loss, new_stats), grads = jax.value_and_grad(
        loss_and_stats, has_aux=True)(params, stats, images, labels, depth,
                                      precision)
    velocity = {n: mu * velocity[n] + grads[n] for n in params}
    params = {n: params[n] - rate * velocity[n] for n in params}
    return loss, params, new_stats, velocity
