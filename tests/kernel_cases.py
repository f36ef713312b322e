"""What more than one kernel's test file uses, in a plain module beside
them (pytest does not collect it): how the recurrence cases evaluate a
side and its gradients (compiled, products at the highest precision), the
delta-rule pair at two widths of a grid step, Mamba-1's recurrence a token
a step with its operands, the attention op's Program with gradients, a
rotation of part of a head written out, and a count of an optimised HLO
module's instructions inside and outside its `while` loops."""

from __future__ import annotations

import re

import numpy as np

from decoder_suite import compiled


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def value_and_grads(fn, args, weight=None):
    """`fn(*args)` and the gradients of `sum(fn(*args) ** 2)` (of
    `sum(fn(*args) * weight)` where a weight is given) by every argument,
    from one compiled function."""
    import jax
    import jax.numpy as jnp

    def loss(*a):
        out = fn(*a)
        wide = out.astype(jnp.float32)
        return jnp.sum(wide ** 2 if weight is None else wide * weight), out

    (_, out), grads = compiled(jax.value_and_grad(
        loss, argnums=range(len(args)), has_aux=True), *args)
    return out, grads


def loss_grads(fn, args, weight=None):
    return value_and_grads(fn, args, weight)[1]


def pair_at_widths(args, per_step, monkeypatch):
    """The kernel pair's outputs and five gradients at `per_step` chunks a
    grid step against one a step, both primitive by primitive (compiled,
    the interpreter's loops cost each width ten seconds more): equal bit
    for bit."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import kda_chunk as kernel

    read = {}
    for steps in (1, per_step):
        monkeypatch.setattr(kernel, "CHUNKS_PER_STEP", steps)
        read[steps] = (kernel.kda_chunk(*args), *jax.grad(
            lambda *a: jnp.sum(kernel.kda_chunk(*a) ** 2),
            argnums=range(5))(*args))
    for name, a, w in zip("o q k v g beta".split(), read[per_step], read[1]):
        assert a.shape == w.shape and np.isfinite(np.asarray(a)).all(), name
        assert np.array_equal(np.asarray(a), np.asarray(w)), name


# ------------------------------------------------ Mamba-1, a token a step


def ssm_recurrence(x, delta, a, bm, cm, dskip):
    """`h = exp(delta a) h + (delta x) B^T; y = h C + D x`, one
    `lax.scan` step a token from a zero state. x, delta: [b, s, d];
    a: [d, n]; bm, cm: [b, s, n]; dskip: [d]."""
    import jax
    import jax.numpy as jnp

    def token(h, xs):  # h [b, d, n]
        x, delta, bm, cm = xs
        h = (jnp.exp(delta[..., None] * a) * h
             + (delta * x)[..., None] * bm[:, None, :])
        return h, jnp.einsum("bdn,bn->bd", h, cm) + dskip * x

    _, y = jax.lax.scan(
        token, jnp.zeros((x.shape[0], *a.shape), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, delta, bm, cm)))
    return jnp.moveaxis(y, 0, 1)


def ssm_operands(b, s, d, n, seed=0, step=(-5.0, 0.5)):
    """Steps log-uniform in `exp(step)`: at 1.6 a token and A = -15 a
    state is gone in one token, at 0.007 it lasts the row."""
    import jax.numpy as jnp

    r = np.random.RandomState(seed)
    return tuple(jnp.asarray(t, jnp.float32) for t in (
        r.randn(b, s, d), np.exp(r.uniform(*step, (b, s, d))),
        -np.exp(r.uniform(0.0, 2.7, (d, n))), r.randn(b, s, n),
        r.randn(b, s, n), r.randn(d)))


SSM_NAMES = ("x", "delta", "a", "b", "c", "d")


# ------------------------------------------------ attention, the op alone


def attn_program(b, sq, sk, nh, dh, layout, causal=False, dropout=0.0):
    """q [b, sq, nh*dh] and k, v [b, sk, nh*dh] as the projections write
    them, head-split by reshape (and transposed for "bhsd") as the models
    do, through the op, with gradients. Returns (main, startup, fetches)."""
    import paddle_tpu as fluid
    from paddle_tpu.framework import Program

    main, startup = Program(), Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds = [fluid.layers.data(n, [b, s, nh * dh], append_batch_size=False)
                 for n, s in (("q", sq), ("k", sk), ("v", sk))]
        for t in feeds:
            t.stop_gradient = False
        heads = [fluid.layers.reshape(t, [b, -1, nh, dh]) for t in feeds]
        if layout == "bhsd":
            heads = [fluid.layers.transpose(t, [0, 2, 1, 3]) for t in heads]
        bias = fluid.layers.data("bias", [b, sk], append_batch_size=False)
        out = fluid.layers.fused_multihead_attention(
            *heads, key_bias=bias, causal=causal, attn_dropout=dropout,
            layout=layout)
        if layout == "bhsd":
            out = fluid.layers.transpose(out, [0, 2, 1, 3])
        out = fluid.layers.reshape(out, [b, -1, nh * dh])
        loss = fluid.layers.reduce_sum(fluid.layers.elementwise_mul(out, out))
        grads = fluid.backward.calc_gradient(loss, feeds)
    return main, startup, [out, *grads]



def written_out(x, theta, lanes):
    """The rotation of the first `lanes` lanes of [b, s, n, d], the others
    passed, with a concatenation and no roll."""
    import jax.numpy as jnp

    s = x.shape[1]
    turning, passing = x[..., :lanes], x[..., lanes:]
    inv = 1.0 / theta ** (jnp.arange(0, lanes, 2, dtype=jnp.float32) / lanes)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], -1)[None, :, None, :]
    swapped = jnp.concatenate(
        [-turning[..., lanes // 2:], turning[..., :lanes // 2]], -1)
    return jnp.concatenate(
        [turning * jnp.cos(angle) + swapped * jnp.sin(angle), passing], -1)



# ------------------------------------------------ an optimised HLO module


def in_and_out_of_whiles(hlo, is_product):
    """(outside, inside): the instructions of an optimised HLO module that
    `is_product(line)` admits, in no `while`'s body, and in some body or a
    computation called from one."""
    lines, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            name = head[1]
            lines[name] = []
        elif name:
            lines[name].append(line)
    inside, todo = set(), [
        c for body in lines.values() for line in body if " while(" in line
        for c in re.findall(r"(?:condition|body)=%?([\w.\-]+)", line)]
    while todo:
        c = todo.pop()
        if c not in inside:
            inside.add(c)
            todo += re.findall(
                r"(?:condition|body|to_apply|calls)=%?([\w.\-]+)",
                "\n".join(lines[c]))
            for group in re.findall(r"branch_computations=\{([^}]*)\}",
                                    "\n".join(lines[c])):
                todo += [c.strip(" %") for c in group.split(",")]
    count = {c: sum(bool(is_product(line)) for line in body)
             for c, body in lines.items()}
    within = sum(n for c, n in count.items() if c in inside)
    return sum(count.values()) - within, within
