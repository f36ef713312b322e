"""The held experts' grouped product (`parallel/moe.py::_held_experts`):
the first block of the sorted assignments is straight-line JAX, which the
forward op and the replay inside its gradient op emit alike and XLA merges;
the blocks a skewed load fills past it run in a `while` that rebuilds its
forward. Held to a count of the compiled step's products and, at every
number of blocks, to `jax.grad` of the plain reference."""

from __future__ import annotations

import re

import numpy as np
import pytest

import kimi_linear_reference as ref  # beside this file
from test_kimi_linear_reference import _expert_params, highest, rel


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


def test_one_layers_train_step_makes_the_first_blocks_products_once():
    """One expert layer behind an `fc`, trained: in the step XLA compiled,
    nine grouped products lie outside every `while` (the first block's gate,
    up and down once, for the forward op and the gradient op's replay, and
    the six of its backward), and twelve inside the two overflow loops
    (three, and the nine of a backward that rebuilds its forward). On the
    CPU JAX lowers a `ragged_dot` to one masked `dot`; the router's three
    products are the ones at the highest precision. The v5e's `ragged-dot`
    calls are counted in `tests/test_pallas_on_mesh.py`."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import profiler

    L = fluid.layers
    x = L.data("x", [48, 16], append_batch_size=False)
    y, _ = L.moe_experts(L.fc(x, 16, bias_attr=False), experts_total=8,
                         experts_held=2, d_ff=8, k=2, scaling=2.446)
    loss = L.reduce_mean(L.square(y))
    fluid.optimizer.SGD(1.0).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    before = profiler.counters()
    compiled, feeds, _ = exe._prepare_run(
        fluid.default_main_program(),
        {"x": np.zeros((48, 16), np.float32)}, [loss], scope)
    hlo = compiled.jit_fn.lower(
        exe._assemble_state(compiled, scope), feeds,
        jax.random.key(0)).compile().as_text()
    after = profiler.counters()
    # once a lowering: the forward op's, and the gradient op's replay
    for counter in ("moe_first_block_shared", "moe_dispatch_grouped"):
        assert after[counter] - before.get(counter, 0) == 2, counter

    def products(router):
        return in_and_out_of_whiles(hlo, lambda line: (
            " dot(" in line and "moe_experts" in line
            and ("highest" in line) == router))

    assert products(router=False) == (9, 12)
    assert products(router=True) == (3, 0)


TOTAL, K, TOKENS = 16, 2, 64


@pytest.mark.parametrize("held,correction,blocks", [
    (2, -10.0, 0),   # nothing routed here: one block of dead rows
    (4, 0.0, 1),     # a quarter held, a block of 7/16: the first block
    (16, 0.0, 1),    # all of them held: one block holds every assignment
    (8, 10.0, 2),    # every assignment lands on the half held: a block of
                     # 7/8, and one trip of the overflow loop
    (4, 10.0, 3),    # ... on the quarter held, a block of 7/16: two trips
    (2, 10.0, 4),    # ... on the eighth held, the floor of 1/4: three
])
def test_value_and_gradients_equal_the_reference_at_every_block_count(
        held, correction, blocks):
    """`moe_experts`' output and the gradients of a scalar of it in x, the
    three weights and the router's gate, against `jax.grad` of the
    reference's routed experts, in float32."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import moe

    r = np.random.RandomState(7)
    hidden, width = 16, 8
    p = _expert_params(r, hidden, width, TOTAL)
    p = {n: v[:held] if n.startswith("m.moe.w_") else v for n, v in p.items()}
    p["m.moe.bias"][:held] += correction
    u = r.randn(TOKENS, hidden).astype(np.float32)
    cotangent = r.randn(TOKENS, hidden).astype(np.float32)
    model = {"num_experts_per_token": K, "num_experts": held, "held_from": 0,
             "moe_renormalize": True, "routed_scaling_factor": 2.446,
             "num_shared_experts": 0}
    trained = ("m.moe.gate", "m.moe.w_gate", "m.moe.w_up", "m.moe.w_down")

    def ours(u, *w):
        q = dict(p, **dict(zip(trained, w)))
        y, load = moe.moe_experts(
            u, q["m.moe.gate"], q["m.moe.bias"], q["m.moe.w_gate"],
            q["m.moe.w_up"], q["m.moe.w_down"], k=K, scaling=2.446,
            experts_held=held, held_from=0)
        return jnp.sum(y * cotangent), (y, load)

    def theirs(u, *w):
        y = ref.expert_ffn(dict(p, **dict(zip(trained, w))), u, "m", model)
        return jnp.sum(y * cotangent), y

    args = (u, *(p[n] for n in trained))
    grad = range(len(args))
    (_, (y, load)), got = jax.jit(jax.value_and_grad(
        ours, grad, has_aux=True))(*args)
    (_, want_y), want = highest(jax.jit(jax.value_and_grad(
        theirs, grad, has_aux=True)), *args)
    rows = moe._block_rows(TOKENS * K, held / TOTAL)
    assert -(-int(np.sum(load)) // rows) == blocks, load
    if blocks == 0:  # dead rows give exactly nothing
        assert not np.abs(y).any() and not np.abs(want_y).any()
        assert all(not np.abs(g).any() for g in (*got, *want))
        return
    assert rel(y, want_y) < 1e-5
    for name, g, w in zip(("x", *trained), got, want):
        assert np.abs(w).max() > 0, name
        assert rel(g, w) < 1e-5, name
