"""The held experts' grouped product (`parallel/moe.py::_held_experts`):
the first block of the sorted assignments is straight-line JAX, which the
forward op and the replay inside its gradient op emit alike and XLA merges;
the blocks a skewed load fills past it run in a `while` that rebuilds its
forward. Held to a count of the compiled step's products and, at every
number of blocks, to `jax.grad` of the plain reference."""

from __future__ import annotations

import numpy as np
import pytest

from decoder_suite import expert_params, highest, rel
from kernel_cases import compiled, in_and_out_of_whiles, loss_grads

from benchmark.models import kimi_linear as ref


def test_one_layers_train_step_makes_the_first_blocks_products_once():
    """One expert layer behind an `fc`, trained: in the step XLA compiled,
    nine grouped products lie outside every `while` (the first block's gate,
    up and down once, for the forward op and the gradient op's replay, and
    the six of its backward), and twelve inside the two overflow loops
    (three, and the nine of a backward that rebuilds its forward). On the
    CPU JAX lowers a `ragged_dot` to one masked `dot`; the router's three
    products are the ones at the highest precision. The v5e's grouped
    products, the `moe_gmm` and `moe_tgmm` kernels' custom calls where the
    lowering takes them and `ragged-dot` on this plain path, are counted
    in `tests/test_pallas_on_mesh.py` (the same nine and twelve for this
    layer, 36 and 48 for the cells' four)."""
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
    # neither took the Pallas kernels: no Mosaic on the CPU, widths of 16
    assert after.get("moe_dispatch_gmm", 0) == before.get(
        "moe_dispatch_gmm", 0)

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
    p = expert_params(r, hidden, width, TOTAL)
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


# --------------------------- the epsilon beside the renormalisation's sum


def test_the_renormalisations_epsilon_against_a_hand_count():
    """Two tokens over four experts, two a token, each expert's own
    sigmoid: scores 0.8, 0.6, 0.2, 0.1 give the two largest 0.8 and 0.6,
    renormalised 4/7 and 3/7; over `sum + 0.1` they are 0.8/1.5 and
    0.6/1.5, over `sum + 1e-6` a millionth of 1.4 less than 4/7 and 3/7;
    without renormalisation the epsilon changes nothing. The op carries
    the attribute only where it is not 0."""
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.parallel.moe import moe_route

    def logit(p):
        return np.log(np.asarray(p) / (1 - np.asarray(p)))

    x = jnp.eye(2, dtype=jnp.float32)
    gate = jnp.asarray([logit([0.8, 0.6, 0.2, 0.1]),
                        logit([0.1, 0.2, 0.6, 0.8])], jnp.float32)
    none = jnp.zeros(4)
    idx, w = moe_route(x, gate, none, 2, 1.0, True)
    assert idx.tolist() == [[0, 1], [3, 2]]
    np.testing.assert_allclose(w, [[4 / 7, 3 / 7]] * 2, rtol=1e-6)
    _, w = moe_route(x, gate, none, 2, 1.0, True, "sigmoid", 0.1)
    np.testing.assert_allclose(w, [[0.8 / 1.5, 0.6 / 1.5]] * 2, rtol=1e-6)
    _, w = moe_route(x, gate, none, 2, 3.0, True, norm_eps=1e-6)
    np.testing.assert_allclose(
        w, [[2.4 / 1.400001, 1.8 / 1.400001]] * 2, rtol=1e-6)
    assert float(w[0, 0]) < 3 * 4 / 7
    _, w = moe_route(x, gate, none, 2, 2.0, False, norm_eps=0.1)
    np.testing.assert_allclose(w, [[1.6, 1.2]] * 2, rtol=1e-6)

    u = fluid.layers.data("u", [1, 2, 2], append_batch_size=False)
    outs = [fluid.layers.moe_experts(
        u, experts_total=4, experts_held=4, d_ff=8, k=2,
        param_attr=fluid.ParamAttr(name=f"m{i}"), **kw)[0]
        for i, kw in enumerate(({}, {"norm_eps": 0.1}))]
    plain, with_eps = [op for op in fluid.default_main_program()
                       .global_block().ops if op.type == "moe_experts"]
    assert "norm_eps" not in plain.attrs and with_eps.attr("norm_eps") == 0.1
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    for n in ("gate", "w_gate", "w_up", "w_down"):
        scope.set("m1." + n, np.asarray(scope.get("m0." + n)))
    scope.set("m0.gate", np.asarray(gate))
    scope.set("m1.gate", np.asarray(gate))
    a, b = exe.run(feed={"u": np.eye(2, dtype=np.float32)[None]},
                   fetch_list=outs)
    # every weight shrinks by 1.4 / 1.5, and so the layer's output
    assert np.abs(a).max() > 1e-4
    np.testing.assert_allclose(b, a * (1.4 / 1.5), rtol=1e-5, atol=1e-8)


def _route_digests(route=None):
    """sha256 of the jaxpr's text of the router and of `moe_experts`,
    value and gradients, on fixed operands."""
    import jax
    import jax.numpy as jnp

    from pallas_costs import jaxpr_digest as digest
    from paddle_tpu.parallel import moe

    r = np.random.RandomState(9)
    x = jnp.asarray(r.randn(2, 12, 16), jnp.float32)
    gate = jnp.asarray(r.randn(16, 8), jnp.float32)
    bias = jnp.asarray(r.randn(8), jnp.float32)
    w_gate, w_up = (jnp.asarray(r.randn(2, 16, 8), jnp.float32)
                    for _ in range(2))
    w_down = jnp.asarray(r.randn(2, 8, 16), jnp.float32)
    kw = route or {}

    def layer(x, gate, w_gate, w_up, w_down):
        return moe.moe_experts(x, gate, bias, w_gate, w_up, w_down, k=2,
                               scaling=2.0, experts_held=2, held_from=2,
                               **kw)[0]

    operands = (x, gate, w_gate, w_up, w_down)
    return (digest(lambda x, g: moe.moe_route(x.reshape(-1, 16), g, bias, 2,
                                              2.0, **kw), x, gate),
            digest(layer, *operands),
            digest(jax.grad(lambda *a: jnp.sum(layer(*a)),
                            argnums=(0, 1, 2, 3, 4)), *operands))


# as the parent of PR 47 traces them under jax 0.9.0 (taken by running
# `_route_digests` against a copy of that commit)
PARENTS_JAXPRS = ("c9ab9fc20ef9c8b6", "c5dbb24e9790627b", "d5e703e7acf42309")


def test_the_default_epsilons_jaxpr_is_the_parents():
    """With `norm_eps` 0, by default or named, the router and the expert
    layer trace, value and gradients, what they traced before the
    attribute existed: the four expert decoders' steps compile to what
    they compiled to. An epsilon adds its one `add`."""
    assert _route_digests() == PARENTS_JAXPRS
    assert _route_digests({"norm_eps": 0.0}) == PARENTS_JAXPRS
    assert all(a != b for a, b in zip(_route_digests({"norm_eps": 1e-6}),
                                      PARENTS_JAXPRS))


def _latent_layer(r, tokens=40, hidden=16, latent=8, width=12, total=8,
                  held=3, held_from=2, k=3):
    import jax.numpy as jnp

    return dict(
        x=jnp.asarray(r.randn(tokens, hidden), jnp.float32),
        l=jnp.asarray(r.randn(tokens, latent), jnp.float32),
        gate=jnp.asarray(r.randn(hidden, total) * 0.5, jnp.float32),
        bias=jnp.asarray(r.randn(total) * 0.1, jnp.float32),
        w_up=jnp.asarray(r.randn(held, latent, width) * 0.3, jnp.float32),
        w_down=jnp.asarray(r.randn(held, width, latent) * 0.3, jnp.float32),
        k=k, held=held, held_from=held_from)


@pytest.mark.parametrize("rows", [None, 16])
def test_ungated_experts_on_a_second_input_equal_a_loop_over_the_experts(
        rows, monkeypatch):
    """`moe_experts` with no `w_gate` and `experts_x`: the router reads x,
    the experts the latent, an expert is `W_down relu(W_up l)^2`; value
    and the gradients by x, the latent, the router and both matrices
    against a loop over the held experts with a mask, in one block and
    (`rows` 16) through the overflow loops."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import moe

    if rows:
        monkeypatch.setattr(moe, "_block_rows", lambda total, share: rows)
    c = _latent_layer(np.random.RandomState(11))

    def layer(x, l, gate, w_up, w_down):
        return moe.moe_experts(x, gate, c["bias"], None, w_up, w_down,
                               k=c["k"], scaling=5.0, experts_held=c["held"],
                               held_from=c["held_from"], experts_x=l)

    def loop(x, l, gate, w_up, w_down):
        scores = jax.nn.sigmoid(x @ gate)
        _, chosen = jax.lax.top_k(scores + c["bias"], c["k"])
        w = jnp.take_along_axis(scores, chosen, -1)
        w = 5.0 * w / jnp.sum(w, -1, keepdims=True)
        y = 0.0
        for e in range(c["held"]):
            here = jnp.sum(jnp.where(chosen == c["held_from"] + e, w, 0.0), -1)
            y = y + here[:, None] * (
                jnp.square(jax.nn.relu(l @ w_up[e])) @ w_down[e])
        return y

    args = (c["x"], c["l"], c["gate"], c["w_up"], c["w_down"])
    (y, load), want = compiled(layer, *args), compiled(loop, *args)
    assert y.shape == c["l"].shape and int(load.sum()) > (rows or 0)
    assert rel(y, want) < 1e-5
    cot = jnp.asarray(np.random.RandomState(1).randn(*y.shape), jnp.float32)
    grads = [loss_grads(fn, args, cot)
             for fn in (lambda *a: layer(*a)[0], loop)]
    for name, g, g_want in zip(("x", "latent", "gate", "w_up", "w_down"),
                               *grads):
        assert rel(g, g_want) < 1e-5, name


def test_the_op_refuses_matrices_that_do_not_take_their_inputs():
    """The message names the shapes: the router's matrix against the
    router's input, `WUp` against the experts' input (a parameter of
    another shape put into the scope behind the Program's back)."""
    import paddle_tpu as fluid

    L = fluid.layers
    for param, shape, match in (
            ("e.w_up", (2, 12, 8),
             r"WUp \(2, 12, 8\) does not take the experts' input "
             r"\(4, 6, 16\) \(the router's X is \(4, 6, 32\)\)"),
            ("e.gate", (16, 8),
             r"Gate \(16, 8\) does not take the router's input X "
             r"\(4, 6, 32\)")):
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()), \
                fluid.unique_name.guard(), fluid.scope_guard(fluid.Scope()):
            x = L.data("x", [4, 6, 32], append_batch_size=False)
            latent = L.data("l", [4, 6, 16], append_batch_size=False)
            y, _ = L.moe_experts(
                x, experts_total=8, experts_held=2, d_ff=8, k=2,
                experts_input=latent, expert_form="relu2",
                param_attr=fluid.ParamAttr(name="e"))
            assert tuple(y.shape) == (4, 6, 16)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            fluid.global_scope().set(param, np.zeros(shape, np.float32))
            with pytest.raises(ValueError, match=match):
                exe.run(main, feed={"x": np.zeros((4, 6, 32), np.float32),
                                    "l": np.zeros((4, 6, 16), np.float32)},
                        fetch_list=[y])
    with pytest.raises(ValueError, match="expert_form"):
        L.moe_experts(L.data("x", [4, 16], append_batch_size=False),
                      experts_total=8, experts_held=2, d_ff=8, k=2,
                      expert_form="gelu")
