"""What more than one kernel's test file uses, in a plain module beside
them (pytest does not collect it): how the recurrence cases evaluate a
side and its gradients (compiled, products at the highest precision), the
delta-rule kernels' path from the projections' arrays beside an oracle
behind the op's prologue, and the pair at two widths of a grid step,
Mamba-1's recurrence a token a step with its operands, the attention
op's Program with gradients, a rotation of part of a head written out, and a count of an optimised HLO
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


# ------------------------------------------------ the delta rule's kernels

# what the op's core takes, and `kernel_path`: the gradients' names
OPERANDS = ("q", "k", "v", "g", "beta", "a_log", "dt_bias")


def logits_of(g, beta, a_log, dt_bias, beta_scale=1.0):
    """The decay's and beta's logits that the op's prologue turns into
    the log decay `g` (<= 0, `[b, s, h, dk]` or `[b, s, h]`) and `beta`
    (in (0, `beta_scale`)) under `a_log` `[h]` and `dt_bias` (`[h, dk]`
    or `[h]`): softplus and the sigmoid inverted in float64, so that a
    regime chosen for `g` and `beta` is the regime the kernels compute in
    (a `g` of 0 is a logit of -inf, whose softplus is 0)."""
    g, beta = np.asarray(g, np.float64), np.asarray(beta, np.float64)
    rate = np.exp(np.asarray(a_log, np.float64))
    x = -g / (rate[:, None] if g.ndim == 4 else rate)
    with np.errstate(divide="ignore"):
        raw = x + np.log(-np.expm1(-x)) - np.asarray(dt_bias, np.float64)
    p = beta / beta_scale
    return raw, np.log(p) - np.log1p(-p)


def after_prologue(fn, eps=1e-6, beta_scale=1.0):
    """`fn` (a recurrence, or `kda_chunked`) behind the op's float32
    prologue (`linear_attn_ops._prologue`, as the plain path runs it), as
    a function of what `kernel_path` takes. The output in v's dtype, as
    the op casts it."""
    from paddle_tpu.ops.linear_attn_ops import _prologue

    def run(q, k, v, g, beta, a_log, dt_bias):
        import jax.numpy as jnp

        b, s, hk, _ = q.shape
        q, k, wide, g = (t.reshape(b, s, -1) for t in (q, k, v, g))
        return fn(*_prologue(
            q, k, wide.astype(jnp.float32), g, beta, a_log,
            dt_bias.reshape(-1), v.shape[2], eps, hk, beta_scale)).astype(
                v.dtype)

    return run


def kernel_path(q, k, v, g, beta, a_log, dt_bias, eps=1e-6, beta_scale=1.0):
    """The op's core (`kda_mixer_core`) where it takes the kernels (the
    interpreter has to be on), from the projections' arrays, heads apart:
    q, k `[b, s, h_k, dk]` (rows of any length), v `[b, s, h, dv]`, the
    decay's logits `[b, s, h, dk]` or `[b, s, h]`, beta's `[b, s, h]`,
    A_log `[h]`, the decay's bias `[h, dk]` or `[h]`. Returns o
    `[b, s, h, dv]`."""
    from paddle_tpu import profiler
    from paddle_tpu.ops.linear_attn_ops import kda_mixer_core

    b, s, hk, _ = q.shape
    before = profiler.counters().get("kda_dispatch_pallas", 0)
    out = kda_mixer_core(
        *(t.reshape(b, s, -1) for t in (q, k, v, g)), beta, a_log,
        dt_bias.reshape(-1), v.shape[2], eps, hk, beta_scale)
    assert profiler.counters()["kda_dispatch_pallas"] == before + 1
    return out.reshape(v.shape)


def oracles(recurrence, args, weight=None, beta_scale=1.0):
    """(out, gradients) of `recurrence` and of `kda_chunked`, the plain
    path, each behind the op's prologue on `args` (what `kernel_path`
    takes), compiled: what a kernel path's `value_and_grads` is held
    against. With grouped key heads both take key head n // group under
    value head n (`kda_chunked` by its own repeat)."""
    from paddle_tpu.ops.linear_attn_ops import kda_chunked

    return tuple(
        value_and_grads(after_prologue(fn, beta_scale=beta_scale), args,
                        weight) for fn in (recurrence, kda_chunked))


def _sums_terms(d_logits, args):
    """What A_log's and the bias's gradients sum, from the gradient of the
    decay's logits `d_logits` (of the recurrence behind the prologue) and
    `args`, in float64, in the logits' shape. With x = logit + bias and
    the log decay g = -exp(A_log) softplus(x): the logit's gradient is
    dg * -exp(A_log) * sigmoid(x), and it is the bias's term; A_log's
    term is dg * g, so the logit's times softplus(x) / sigmoid(x) (1 at
    x = -inf, where both terms are 0)."""
    x = np.asarray(args[3], np.float64) + np.asarray(args[6], np.float64)
    d = np.asarray(d_logits, np.float64)
    with np.errstate(over="ignore", invalid="ignore"):
        ratio = np.where(x > -700, np.logaddexp(x, 0.0) * (1 + np.exp(-x)),
                         1.0)
    return d * ratio, d


def gradients_held(got, want, plain, args, bf16=False):
    """The seven gradients of a kernel path (`got`) against the
    recurrence's (`want`) and the plain path's (`plain`), all three behind
    the same prologue.

    The five by token at the limits these files held them at before the
    kernels took the prologue: 1e-4 of the gradient's size, and for the
    decay's twice the plain path's own distance from the recurrence where
    that is more. Where a token all but erases the state the decay's
    gradient is 1e-10 to 1e-8 and is what float32 leaves of
    q*dq + k*(dk_row - dk_col), summed back over the chunk: the plain path
    itself reads 2.4e-4 against the recurrence there.

    A_log's and the bias's gradients are sums over the tokens (and, a
    head's A_log under a decay a channel, over the lanes) of terms made
    of the decay's logits' gradient (`_sums_terms`). Their distance is
    the norm of the difference over the norm of the sums of the sizes of
    the terms each entry sums (the recurrence's): wherever the terms do
    not cancel that is `rel`, and where they do (in the erasing regimes
    an entry of 1e-9 sums 25,600 terms of 1e-8 and less, and the plain
    path reads 600 times the entry against the recurrence) it is the only
    size a float32 sum can be held to. Held to 1e-4, or where that is
    more to three times the plain path's own distance from the
    recurrence: a head's sum of 200 terms that are each what float32
    left reads 2.7e-4 from the kernels where the plain path reads 1.4e-4
    (a decay a head of 0.01 to 0.05 a token, key groups of 2), the
    decay's own gradient there 1.9e-4 where 1.2e-4; elsewhere both read
    1e-7 to 3e-6.

    `bf16`: a gradient that leaves in bf16 (q's, k's, v's and the
    logits', not the two float32 parameters') is the same float32 number
    rounded once on each side, 2^-9 an entry: 2^-8 of its size on top.
    Returns the distances read, by name."""
    read = {}
    for name, a, w, p, like in zip(OPERANDS, got, want, plain, args):
        assert a.shape == like.shape == w.shape, name
        assert a.dtype == like.dtype, name
        a, w, p = (np.asarray(t, np.float64) for t in (a, w, p))
        assert np.isfinite(a).all(), name
        if name in ("a_log", "dt_bias"):
            terms = _sums_terms(want[3], args)[name == "dt_bias"]
            # over the tokens, and a head's A_log over its lanes too
            over = (0, 1, 3) if terms.ndim == 4 and w.ndim == 1 else (0, 1)
            size = np.abs(terms).sum(over) + 1e-30
            assert size.shape == w.shape, name
            read[name] = tuple(
                float(np.linalg.norm(x - y) / np.linalg.norm(size))
                for x, y in ((a, w), (a, p), (p, w)))
            # against the recurrence alone: between the kernels and the
            # plain path such a sum is one rounding against another
            limit, held = max(1e-4, 3 * read[name][2]), read[name][:1]
        else:
            held = read[name] = rel(a, w), rel(a, p), rel(p, w)
            limit = max(1e-4, 2 * held[2]) if name == "g" else 1e-4
            if bf16 and like.dtype != np.float32:
                limit += 2.0 ** -8
        assert max(held) < limit, (name, read)
    return read


def one_cotangent(args):
    """With bf16 inputs each side rounds its own outputs, so the sides
    are differentiated under one cotangent, a weight in the output's
    shape, and not under their own outputs; None with float32 inputs."""
    if args[2].dtype == np.float32:
        return None
    return np.random.RandomState(1).randn(*args[2].shape).astype(np.float32)


def _grids(fn, *args):
    """Kernel name -> the grids of its calls in `fn(*args)`, traced now."""
    from pallas_costs import block_shapes

    return {name: [grid for grid, _ in calls]
            for name, calls in block_shapes(fn, *args).items()}


_AT_ONE_CHUNK = {}  # `pair_at_widths`: by operands, their oracles and pair


def pair_at_widths(args, per_step, monkeypatch, recurrence, beta_scale=1.0):
    """The kernel pair at `per_step` chunks a grid step against one a
    step, from `args`, what `kernel_path` takes, float32.

    **Equal bit for bit across the widths** (`np.array_equal`): the
    output and every array the backward kernel itself writes, as its
    `pallas_call` returns them (`kda_chunk._sweep`, under the forward's
    own states and the cotangent 2 o): dq, dk, dv, and with a decay a
    head the three arrays of rows a chunk (the gradients of the decay's
    logits and of beta's, and what A_log's gradient sums), with a decay a
    channel the gradient of the float32 log decay (`kda_gate`'s, made
    once, in front of both widths, as the op hands it over) and beta's
    logits' rows. They are taken before XLA sums a group's dq and dk,
    sums or casts a row, lays one by token or takes a gate's gradient.
    Rows padded behind the row's last token (a wider step pads more of
    them) read 0 in every one of these arrays, and that is asserted.

    **Held at each width against the oracles**, not against each other:
    the seven gradients as the op returns them (`kernel_path` under
    `jax.vjp`), by `gradients_held` against `recurrence` and `kda_chunked`
    behind `_prologue`. What XLA forms after the kernels (the rows by
    token, A_log's and the bias's sums over the tokens, `kda_gate`'s
    backward, a group's sum) is one lowering at one padded length and
    another at the next, and XLA's CPU backend may associate a sum or
    contract a multiply-add differently from one to the other.

    **Each width is traced afresh, and the widths are shown to differ.**
    `CHUNKS_PER_STEP` is a module global that `lockstep_chunks` reads at
    trace time, so it is in no trace-cache key: a function defined once
    outside the loop and jitted twice would be served the first width's
    jaxpr (what refused PR 64's last version of this helper compared:
    one computation with itself). Here every function that is traced is
    defined inside the loop, the kernels' calls take the width in their
    static `_Statics`, and the grids of the two widths' traced calls are
    read from their jaxprs and must differ.

    **Compiled at XLA's optimisation level 0, and why.** The interpreter
    hands a grid step's body to XLA's CPU backend, one computation a
    width, and LLVM's optimiser sees loops over 64, 128 or 256 stacked
    rows. At the backend's default level the two arrays that come of
    dG = q*dq + k*(dk_row - dk_col) (the decay's gradient, and A_log's
    rows, which are it times the log decay) then differ from width to
    width in what float32 leaves of that sum: read at 100 tokens, widths
    1 and 4, 1.8e-9 at entries of up to 2.4e-3 with a decay a channel,
    1.6e-8 and 2.9e-8 with a decay a head (a sum over the lanes), and an
    entry of 1e-9 that is all cancellation changes sign; the output, dq,
    dk, dv and beta's rows are equal to the bit there too. With
    `xla_backend_optimization_level` 0 (no pass of LLVM's reorders or
    contracts anything; `xla_cpu_max_isa=AVX` and
    `xla_llvm_disable_expensive_passes` change nothing) every array is
    equal to the bit at every width, so what differs is what the CPU's
    optimiser makes of one body at another loop shape, and not what the
    body states: the test compiles both widths at level 0, and holds
    every array to the bit. (Before PR 65 the kernels read q and k
    normed from HBM and the default level happened to treat all widths
    alike.)"""
    import jax

    from paddle_tpu.ops.linear_attn_ops import kda_gate
    from paddle_tpu.ops.pallas import kda_chunk as kernel

    q, k, v, g, beta, a_log, dt_bias = args
    b, s, h = beta.shape
    per_head = g.ndim == 3
    if per_head:
        gate = (a_log, dt_bias)
    else:  # as `kda_mixer_core` hands a channel's over: gated by XLA
        g, gate = kda_gate(g.reshape(b, s, -1), a_log, dt_bias.reshape(-1),
                           h), ()
    # What does not depend on `per_step` (the oracles, and the pair at one
    # chunk a step with its own comparison against them) is kept for the
    # process's next case on the same operands: the cases on one length
    # at widths 2 and 4 each made both again, half of a case's time.
    key = (recurrence, beta_scale) + tuple(
        (a.shape, str(a.dtype), np.asarray(a, np.float32).tobytes())
        for a in args)
    if key not in _AT_ONE_CHUNK:
        _AT_ONE_CHUNK[key] = {
            "oracles": oracles(recurrence, args, None, beta_scale)}
    kept = _AT_ONE_CHUNK[key]
    (_, g_want), (_, g_plain) = kept["oracles"]
    chunks = -(-s // kernel.CHUNK)
    read, grids = {}, {}
    for steps in (1, per_step):
        if steps == 1 and "read" in kept:
            read[1], grids[1] = kept["read"], kept["grids"]
            continue
        monkeypatch.setattr(kernel, "CHUNKS_PER_STEP", steps)

        def pair(q, k, v, g, beta, gate):  # defined at this width
            operands, statics = kernel.step_operands(
                q, k, v, g, beta, gate, 1e-6, beta_scale)
            assert statics.steps == min(
                max(1, steps // max(statics.narrow, 1)), chunks)
            o, states = kernel._call_fwd(*operands, statics=statics)
            flat = (*operands[:5], *operands[5])
            return (o, *kernel._sweep(flat, states, 2 * o, statics))

        def through_the_op(*a):  # and so is this
            return kernel_path(*a, 1e-6, beta_scale)

        grids[steps] = _grids(pair, q, k, v, g, beta, gate)
        assert grids[steps] == _grids(
            lambda *a: jax.vjp(through_the_op, *a)[1](a[2]), *args)
        read[steps] = jax.jit(pair).lower(q, k, v, g, beta, gate).compile(
            compiler_options={"xla_backend_optimization_level": 0})(
                q, k, v, g, beta, gate)
        def op_gradients(*a):  # compiled whole, not op by op
            o, pull = jax.vjp(through_the_op, *a)
            return pull(2 * o)

        gradients_held(jax.jit(op_gradients)(*args), g_want, g_plain, args)
        if steps == 1:
            kept["read"], kept["grids"] = read[1], grids[1]
    assert len(grids[1]) == 2 and grids[1] != grids[per_step], grids
    names = ("o", "dq", "dk", "dv") + (
        ("dg rows", "dbeta rows", "dA_log rows") if per_head
        else ("dg", "dbeta rows"))
    assert len(read[1]) == len(read[per_step]) == len(names)
    for name, a, w in zip(names, read[per_step], read[1]):
        a, w = np.asarray(a, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(a).all() and np.isfinite(w).all(), name
        if "rows" in name:  # [.., S/C, 1, C] -> [.., S]: the row's tokens
            a, w = (t.reshape(*t.shape[:-3], -1) for t in (a, w))
            a, w = np.moveaxis(a, -1, 1), np.moveaxis(w, -1, 1)
        for padded in (a[:, s:], w[:, s:]):
            assert not padded.any(), name
        assert np.array_equal(a[:, :s], w[:, :s]), (
            name, np.abs(a[:, :s] - w[:, :s]).max())


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
