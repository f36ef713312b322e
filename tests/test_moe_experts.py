"""The held experts' grouped product (`parallel/moe.py::_held_experts`):
the first block of the sorted assignments is straight-line JAX, which the
forward op and the replay inside its gradient op emit alike and XLA merges;
the blocks a skewed load fills past it run in a `while` that rebuilds its
forward. Held to a count of the compiled step's products and, at every
number of blocks, to `jax.grad` of the plain reference."""

from __future__ import annotations

import numpy as np
import pytest

from decoder_suite import expert_params, highest, rel, settled_counters
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
    assert after["moe_dispatch_grouped"] - before.get(
        "moe_dispatch_grouped", 0) == 2
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


# as PR 67's tree traces them under jax 0.9.0 (taken by running
# `_route_digests` on that commit: the router's selection by compare, the
# one sort with its weights and the counted load changed the jaxpr by
# design; up to PR 66 these were the digests of PR 47's parent)
PARENTS_JAXPRS = ("ab66d574550ff698", "8b219f67b28c6656", "56c6abc24d4bbaac")


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


# -------------------- the bookkeeping without a gather or a scatter (PR 67)


def _bookkeeping(selected, held_first, score_func, k, held, held_from, total):
    """The router's choices and what the sort stage makes of them, as a
    function of the logits: `selected(scores, idx)` reads the k scores,
    `held_first(idx, weights, held, held_from)` orders them. One held
    expert, the second of them, is never drawn."""
    import jax
    import jax.numpy as jnp

    bias = np.zeros(total, np.float32)
    if held_from + 1 < total:
        bias[held_from + 1] = -10.0

    def run(logits, cot_w, cot_s):
        scores = (jax.nn.sigmoid(logits) if score_func == "sigmoid"
                  else jax.nn.softmax(logits, axis=-1))
        _, idx = jax.lax.top_k(jax.lax.stop_gradient(scores + bias), k)
        idx = idx.astype(jnp.int32)
        w = selected(scores, idx)
        token, weight, load = held_first(idx, 2.5 * w, held, held_from)
        return (jnp.sum(w * cot_s) + jnp.sum(weight * cot_w),
                (w, token, weight, load))

    return jax.jit(jax.value_and_grad(run, has_aux=True))


@pytest.mark.parametrize("k", [2, 8])
@pytest.mark.parametrize("held_from,held", [
    (5, 4),    # inside the experts
    (0, 4),    # at their start
    (14, 4),   # two of the four keys past the last expert: never drawn
    (16, 2),   # all of them past the end: nothing is held
])
@pytest.mark.parametrize("score_func", ["sigmoid", "softmax"])
def test_the_bookkeeping_equals_a_gather_an_argsort_and_a_bincount_bitwise(
        score_func, held_from, held, k):
    """The k selected scores, `token`, the permuted `weight` and `load`,
    and the gradient by the logits through both the scores and the
    weights, against the forms `moe_route` and `moe_experts` had up to
    PR 66: `take_along_axis`, `argsort`, `[order]`, `bincount`. Equal
    bit for bit: a sum of one non-zero term, a permutation and a count."""
    import jax.numpy as jnp

    from paddle_tpu.parallel import moe

    total, tokens = 16, 96

    def held_first(idx, weights, held, held_from):
        local = idx.reshape(-1) - held_from
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held)
        order = jnp.argsort(key, stable=True)
        load = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
        return ((order // idx.shape[1]).astype(jnp.int32),
                jnp.where(here, weights.reshape(-1), 0.0)[order], load)

    r = np.random.RandomState(5)
    args = (jnp.asarray(r.randn(tokens, total), jnp.float32),
            jnp.asarray(r.randn(tokens * k), jnp.float32),
            jnp.asarray(r.randn(tokens, k), jnp.float32))
    shape = (score_func, k, held, held_from, total)
    (_, got), got_grad = _bookkeeping(
        moe._selected, moe._held_first, *shape)(*args)
    (_, want), want_grad = _bookkeeping(
        lambda s, i: jnp.take_along_axis(s, i, axis=-1), held_first,
        *shape)(*args)
    for name, g, w in zip(("selected", "token", "weight", "load", "grad"),
                          (*got, got_grad), (*want, want_grad)):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert np.array_equal(g, w), name
    load = np.asarray(got[3])
    assert load.dtype == np.int32 and np.abs(want_grad).max() > 0
    if held_from < total:
        assert load[0] > 0 and load[1] == 0  # the expert left out
        assert np.abs(np.asarray(got[2])).max() > 0
    else:
        assert not load.any() and not np.asarray(got[2]).any()


def _stage_equations(jaxpr, outer=""):
    """(innermost stage or "", primitive's name) of every equation of a
    jaxpr and of the jaxprs in its equations' parameters, a name stack
    read under those of the equations around it."""
    import re

    from pallas_costs import _jaxprs

    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        stages = re.findall(r"moe\.\w+", stack)
        yield (stages[-1] if stages else ""), eqn.primitive.name
        for sub in _jaxprs(list(eqn.params.values())):
            yield from _stage_equations(sub, stack)


def test_no_equation_of_the_route_or_the_sort_addresses_one_assignment():
    """The mechanism's counter: it always engages. In the jaxpr of
    `moe_experts`, value and gradients (the overflow loops' bodies
    included), no `gather` and no `scatter` of any kind lies under
    `moe.route` or `moe.sort`, whose sort is one and whose backward is
    one more; the rows of x are still gathered and scattered
    (`moe.gather`, `moe.combine`), which also shows that the walk sees
    them, as it sees the forms of PR 66 under a stage."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import moe

    c = _latent_layer(np.random.RandomState(3))

    def layer(x, l, gate, w_up, w_down):
        return moe.moe_experts(x, gate, c["bias"], None, w_up, w_down,
                               k=c["k"], scaling=5.0, experts_held=c["held"],
                               held_from=c["held_from"], experts_x=l)

    args = (c["x"], c["l"], c["gate"], c["w_up"], c["w_down"])

    def equations(fn, *args):
        return list(_stage_equations(jax.make_jaxpr(fn)(*args).jaxpr))

    def addressed(equations):
        found = {}
        for stage, primitive in equations:
            if primitive == "gather" or primitive.startswith("scatter"):
                found.setdefault(stage, set()).add(primitive)
        return found

    both = {"gather", "scatter-add"}
    for fn, sorts in ((layer, 1),
                      (jax.grad(lambda *a: jnp.sum(layer(*a)[0]),
                                argnums=range(5)), 2)):
        traced = equations(fn, *args)
        found = addressed(traced)
        assert set(found) == {"moe.gather", "moe.combine"}, found
        assert "gather" in found["moe.gather"]
        assert "scatter-add" in found["moe.combine"]
        # the one sort, and the backward's under the stage it names itself
        assert traced.count(("moe.sort", "sort")) == sorts
    # the walk finds PR 66's forms where they stand under a stage

    def before(scores, idx):
        with moe.stage("moe.route"):
            w = jnp.take_along_axis(scores, idx, axis=-1)
        with moe.stage("moe.sort"):
            key = idx.reshape(-1)
            load = jnp.bincount(key, length=4)
            return jnp.sum(w.reshape(-1)[jnp.argsort(key)]) + load[0]

    scores, idx = jnp.ones((6, 4)), jnp.zeros((6, 2), jnp.int32)
    assert addressed(equations(before, scores, idx)) == {
        "moe.route": {"gather"}, "moe.sort": both}
    assert addressed(equations(jax.grad(before), scores, idx))[
        "moe.sort"] == both


# ------------------------------- the layer read from inside: stages, counts

STAGES = ("moe.route", "moe.sort", "moe.gather", "moe.products", "moe.combine")
COUNTS = ("moe_rows_routed", "moe_rows_live", "moe_blocks_run")
ROWS, WIDTH, EXPERTS = 48, 16, 8


def _one_layer(held, experts=True):
    """`fc`, one expert layer of `held` of eight experts (none: a second
    `fc`), a loss, SGD: (Executor, loss, load or None)."""
    import paddle_tpu as fluid

    L = fluid.layers
    x = L.data("x", [ROWS, WIDTH], append_batch_size=False)
    h = L.fc(x, WIDTH, bias_attr=False, param_attr=fluid.ParamAttr(name="in"))
    if experts:
        y, load = L.moe_experts(h, experts_total=EXPERTS, experts_held=held,
                                d_ff=8, k=K, scaling=2.446,
                                param_attr=fluid.ParamAttr(name="m"))
    else:
        y, load = L.fc(h, WIDTH, bias_attr=False), None
    loss = L.reduce_mean(L.square(y))
    fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    return exe, loss, load


def _counted(before):
    after = settled_counters()
    return {n: after.get(n, 0) - before.get(n, 0)
            for n in (*COUNTS, "device_counts_dropped")}


@pytest.mark.parametrize("held,blocks", [(8, 1), (2, 3)])
def test_every_operation_the_layer_traces_lies_in_one_of_five_stages(
        held, blocks):
    """The lowered train step of one layer, whose sorted assignments fill
    at most `blocks` blocks: under `fwd/moe_experts` and under
    `bwd/moe_experts_grad` alike every operation's name carries a stage
    (no AMP here: the pre-cast of the op's inputs is `lower_op`'s, under
    the bare op scope), each of the five is there under either, and what
    the benchmark reads a trace with (`xplane_meta.scope`, the metric's
    pattern, `trace_stage_share.stage_of`) finds the innermost stage in a
    transposed name and inside the overflow loops' bodies."""
    import json
    import re

    import jax

    import paddle_tpu as fluid
    from paddle_tpu.parallel import moe

    from benchmark.harness import spec, xplane_meta
    from benchmark.harness.sources import trace_stage_share

    assert moe.STAGES == STAGES
    assert -(-ROWS * K // moe._block_rows(ROWS * K, held / EXPERTS)) == blocks
    exe, loss, _ = _one_layer(held)
    scope = fluid.global_scope()
    compiled, feeds, _ = exe._prepare_run(
        fluid.default_main_program(),
        {"x": np.zeros((ROWS, WIDTH), np.float32)}, [loss], scope)
    text = compiled.jit_fn.lower(
        exe._assemble_state(compiled, scope), feeds,
        jax.random.key(0)).as_text(debug_info=True)
    metric = spec.load("layer_metrics", "moe_dispatch_device_pct")
    assert metric["kind"] == "trace_stage_share", json.dumps(metric)
    assert set(metric["args"]["stages"]) < set(STAGES)
    assert metric["args"]["counts"] == list(COUNTS)
    pattern = metric["args"]["scope"]

    names = {n for n in re.findall(r'loc\("(jit\(step\)/[^"]*)"', text)
             if "moe_experts" in n}
    found = {}  # phase/op -> {stage}
    for name in names:
        scope_ = xplane_meta.scope(name + ":")
        assert re.search(pattern, scope_), name
        stage = trace_stage_share.stage_of(scope_)
        assert stage in STAGES, f"outside every stage: {name}"
        found.setdefault(xplane_meta.phase_op(scope_), set()).add(stage)
    assert found == {"fwd/moe_experts": set(STAGES),
                     "bwd/moe_experts_grad": set(STAGES)}

    def innermost(fragment):
        return {trace_stage_share.stage_of(xplane_meta.scope(n))
                for n in names if fragment in n}

    assert innermost("transpose(jvp(moe.gather))/") == {"moe.gather"}
    assert innermost("/while/body/moe.gather/") == {"moe.gather"}
    # the loops' own counting and sums are the combine's, fwd and bwd
    assert innermost("/while/cond/") == {"moe.combine"}
    assert {"moe.gather", "moe.products", "moe.combine"} <= innermost(
        "bwd/moe_experts_grad/transpose(bwd/moe_experts_grad)")
    # no stage for an op without one, nor for a dotted primitive's type
    assert trace_stage_share.stage_of("fwd/matmul/dot_general") == ""
    assert trace_stage_share.stage_of("bwd/mul_grad/transpose(jvp())/mul") == ""
    with pytest.raises(ValueError, match="no stage"):
        moe.stage("moe.top_k")


@pytest.mark.parametrize("held,correction,blocks,places", [
    (2, 0.0, 1, None),  # a quarter held, a block of 7/16 of the 96 rows
    (2, 10.0, 3, None),  # every assignment lands on the quarter held: two trips
    (2, 0.0, 1, 2),     # the rows over a mesh of two: one count, the whole step's
])
def test_the_step_counts_its_rows_and_blocks_as_numpy_counts_them(
        held, correction, blocks, places):
    """Three steps on three inputs: `profiler.counters()` gains exactly
    what a numpy router over the same weights counts, the gradient op's
    replay counts nothing, nothing is dropped, and `run_repeated`'s
    window sums its steps."""
    import paddle_tpu as fluid
    from paddle_tpu.parallel import moe

    exe, loss, load = _one_layer(held)
    program = fluid.default_main_program()
    if places:
        program = fluid.CompiledProgram(program).with_data_parallel(
            loss_name=loss.name, places=places)
    scope = fluid.global_scope()
    bias = np.zeros(EXPERTS, np.float32)
    bias[:held] += correction
    scope.set("m.bias", bias)
    rows = moe._block_rows(ROWS * K, held / EXPERTS)
    before = settled_counters()
    want = dict.fromkeys(COUNTS, 0)
    for seed in range(3):
        x = np.random.RandomState(seed).randn(ROWS, WIDTH).astype(np.float32)
        scores = 1 / (1 + np.exp(-(x @ np.asarray(scope.get("in"))
                                   @ np.asarray(scope.get("m.gate")))))
        chosen = np.argsort(-(scores + bias), axis=1, kind="stable")[:, :K]
        live = int((chosen < held).sum())
        (got,) = exe.run(program, feed={"x": x}, fetch_list=[load])
        assert int(got.sum()) == live
        want["moe_rows_routed"] += ROWS * K
        want["moe_rows_live"] += live
        want["moe_blocks_run"] += max(1, -(-live // rows))
    assert want["moe_blocks_run"] == 3 * blocks
    assert _counted(before) == {**want, "device_counts_dropped": 0}

    before = settled_counters()
    stacked_loss, stacked_load = exe.run_repeated(
        program, feed={"x": x}, fetch_list=[loss, load], steps=4)
    assert stacked_loss.shape[0] == 4 and stacked_load.shape == (4, held)
    assert _counted(before) == {
        "moe_rows_routed": 4 * ROWS * K,
        "moe_rows_live": int(stacked_load.sum()),
        "moe_blocks_run": int(np.maximum(
            1, -(-stacked_load.sum(axis=1) // rows)).sum()),
        "device_counts_dropped": 0}


def test_a_program_without_experts_returns_what_it_returned_and_counts_nothing():
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import profiler

    exe, loss, _ = _one_layer(0, experts=False)
    scope = fluid.global_scope()
    before = settled_counters()
    compiled, feeds, _ = exe._prepare_run(
        fluid.default_main_program(),
        {"x": np.ones((ROWS, WIDTH), np.float32)}, [loss], scope)
    assert compiled.count_names == ()
    state = exe._assemble_state(compiled, scope)
    out = jax.eval_shape(compiled.jit_fn, state, feeds, jax.random.key(0))
    # (the fetches, the state): one loss and nothing beside it
    assert jax.tree.structure(out) == jax.tree.structure(
        ([0], dict.fromkeys(compiled.state_names, 0)))
    exe.run(feed={"x": np.ones((ROWS, WIDTH), np.float32)}, fetch_list=[loss])
    assert not any(_counted(before).values())
    assert not profiler._device_counts


@pytest.mark.parametrize("form", ["nan_checked", "micro_batched", "in_a_loop"])
def test_a_step_that_cannot_carry_its_counts_says_so(form, monkeypatch):
    """The NaN-checked step, the micro-batched step and an op inside a
    `while` body drop their counts loudly: `device_counts_dropped` moves
    with the compile, the three counts do not, the step runs."""
    import paddle_tpu as fluid

    L = fluid.layers
    before = settled_counters()
    x = np.ones((ROWS, WIDTH), np.float32)
    if form == "in_a_loop":
        u = L.data("x", [ROWS, WIDTH], append_batch_size=False)
        i = L.fill_constant([1], "int64", 0)
        acc = L.fill_constant([ROWS, WIDTH], "float32", 0.0)
        cond = L.less_than(i, L.fill_constant([1], "int64", 2))
        loop = L.While(cond)
        with loop.block():
            y, _ = L.moe_experts(u, experts_total=EXPERTS, experts_held=2,
                                 d_ff=8, k=K)
            L.assign(acc + y, acc)
            L.increment(i, in_place=True)
            L.less_than(i, L.fill_constant([1], "int64", 2), cond=cond)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        exe.run(feed={"x": x}, fetch_list=[acc])
    else:
        if form == "nan_checked":
            monkeypatch.setenv("PADDLE_TPU_CHECK_NAN_INF", "1")
        exe, loss, _ = _one_layer(2)
        if form == "micro_batched":
            main = fluid.default_main_program()
            main._pipeline_microbatches = 2
        exe.run(feed={"x": x}, fetch_list=[loss])
    counted = _counted(before)
    assert counted.pop("device_counts_dropped") >= len(COUNTS)
    assert not any(counted.values())


def test_counters_never_waits_for_a_step_in_flight():
    """`profiler.counters()` folds the steps that have finished and leaves
    the others in the queue; the next call has them. Past
    `DEVICE_COUNTS_IN_FLIGHT` held steps the oldest is folded at the next
    dispatch, finished or not: the queue is bounded and loses nothing."""
    from paddle_tpu import profiler

    class Step:
        """What the Executor hands over: one step's counts, ready or not."""

        def __init__(self, *counts, ready=False):
            self.counts, self.ready = np.array(counts, np.int32), ready

        def is_ready(self):
            return self.ready

        def addressable_data(self, index):
            return self.counts

    names = ("test_rows", "test_blocks")
    before = profiler.counters()

    def gained():
        now = profiler.counters()
        return tuple(now.get(n, 0) - before.get(n, 0) for n in names)

    first, second = Step(5, 1, ready=True), Step(2_000_000_000, 3)
    profiler.hold_device_counts(names, first)
    profiler.hold_device_counts(names, second)
    assert gained() == (5, 1)  # ... and came back with `second` running
    assert gained() == (5, 1)
    second.ready = True
    # exact, past what an int32 sum on the device would hold
    assert gained() == (2_000_000_005, 4)
    assert not profiler._device_counts
    # a window of `run_repeated`: [steps, counts], summed on the host
    profiler.hold_device_counts(
        names, Step([2_000_000_000, 1], [2_000_000_000, 2], ready=True))
    assert gained() == (6_000_000_005, 7)
    # a host that far ahead: the oldest is waited for, none is lost
    running = [Step(1, 0) for _ in range(profiler.DEVICE_COUNTS_IN_FLIGHT + 2)]
    for step in running:
        profiler.hold_device_counts(names, step)
    assert len(profiler._device_counts) == profiler.DEVICE_COUNTS_IN_FLIGHT
    assert gained() == (6_000_000_007, 7)
    for step in running:
        step.ready = True
    assert gained() == (6_000_000_005 + len(running), 7)
    # an array that cannot be read (its step failed) is counted as dropped
    broken = Step(1, 1, ready=True)
    broken.addressable_data = None
    dropped = profiler.counters().get("device_counts_dropped", 0)
    profiler.hold_device_counts(names, broken)
    assert profiler.counters()["device_counts_dropped"] == dropped + 1

    # dispatching threads and readers at once: no count lost or doubled
    import sys
    import threading

    before, each, threads = profiler.counters(), 300, 12

    def dispatch():
        for _ in range(each):
            profiler.hold_device_counts(names, Step(3, 1, ready=True))
            profiler.counters()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        workers = [threading.Thread(target=dispatch) for _ in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(w.is_alive() for w in workers)
    assert gained() == (3 * each * threads, each * threads)


def test_a_lowering_that_counts_what_its_op_did_not_declare_is_refused(
        monkeypatch):
    from paddle_tpu.ops import registry

    opdef = registry.get_op("moe_experts")
    monkeypatch.setattr(opdef, "device_counts", COUNTS[:2])
    exe, loss, _ = _one_layer(2)
    with pytest.raises(RuntimeError, match="moe_blocks_run.*does not declare"):
        exe.run(feed={"x": np.ones((ROWS, WIDTH), np.float32)},
                fetch_list=[loss])


# ------------- a block's rows summed onto their tokens as grouped products

def _sorted_block(r, tokens, groups, live, rows):
    """`rows` token ids as a block holds them: `live` assignments in
    `groups` runs, ascending within each (an expert's), then dead rows
    that name tokens like any other."""
    per = np.diff(np.round(np.linspace(0, live, groups + 1)).astype(int))
    parts = [np.sort(r.choice(tokens, p, replace=False)) for p in per]
    return np.concatenate(parts + [r.randint(0, tokens, rows - live)])


# name: (tokens, rows, live rows, how the block's token ids are drawn)
BLOCKS = {
    # 1,024 tokens in two runs of 512; 300 of 768 rows live
    "dead_rows": (1024, 768, 300,
                  lambda r: _sorted_block(r, 1024, 4, 300, 768)),
    # three runs, no live row names a token of the second
    "an_empty_run": (1536, 256, 200, lambda r: np.concatenate(
        [np.sort(r.choice(np.r_[0:512, 1024:1536], 200, replace=False)),
         r.randint(512, 1024, 56)])),
    "every_row_live": (1024, 512, 512,
                       lambda r: _sorted_block(r, 1024, 4, 512, 512)),
    "no_row_live": (1024, 256, 0, lambda r: r.randint(0, 1024, 256)),
    # eight experts hold the same 40 tokens: eight rows a token
    "a_token_k_times": (640, 384, 320, lambda r: np.concatenate(
        [np.tile(np.sort(r.choice(640, 40, replace=False)), 8),
         r.randint(0, 640, 64)])),
    # 96 tokens: one run of 128 rows, nothing sorted
    "one_run": (96, 160, 120, lambda r: _sorted_block(r, 96, 2, 120, 160)),
}


def _scatter(values, token, live, n):
    """The oracle, and the `kernel=False` path's: XLA's scatter-add."""
    import jax.numpy as jnp

    return jnp.zeros((n, values.shape[1]), jnp.float32).at[token].add(
        jnp.where(live, values, 0).astype(jnp.float32))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("block", list(BLOCKS))
def test_a_blocks_sum_onto_its_tokens_equals_the_scatter_add(
        block, dtype, monkeypatch):
    """`_onto_tokens` against `zeros.at[token].add` with a NaN in every
    dead row: bfloat16 values summed in float32, the scatter's sums to the
    order of the additions; float32 values, which go through the products
    as three bfloat16 slices, no further from the float64 sums than the
    scatter's are. Its gradient is `_from_tokens`, the masked gather."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import moe

    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    n, rows, count, draw = BLOCKS[block]
    r = np.random.RandomState(11)
    token = jnp.asarray(draw(r), jnp.int32)
    assert token.shape == (rows,)
    live = (jnp.arange(rows) < count)[:, None]
    clean = jnp.asarray(r.randn(rows, 128) * np.exp(r.randn(rows, 1)), dtype)
    values = jnp.where(live, clean, jnp.nan)
    # compiled whole, each side: op by op every primitive of the
    # interpreter's walk is a module of its own
    onto = jax.jit(lambda v: moe._onto_tokens(v, token, live, n, jnp.float32))
    got = onto(values)
    want = jax.jit(lambda v: _scatter(v, token, live, n))(clean)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert np.isfinite(np.asarray(got)).all()
    exact = np.zeros((n, 128))
    np.add.at(exact, np.asarray(token)[:count],
              np.asarray(clean.astype(jnp.float32), np.float64)[:count])
    ours, theirs = (np.abs(np.asarray(a, np.float64) - exact).max()
                    for a in (got, want))
    assert ours <= max(theirs, 1e-7 * np.abs(exact).max()), (ours, theirs)
    assert float(jnp.abs(got - want).max()) <= 1e-6 * max(
        float(jnp.abs(want).max()), 1.0)
    hit = np.zeros(n, bool)
    hit[np.asarray(token)[:count]] = True
    assert not np.asarray(got)[~hit].any()

    ct = jnp.asarray(r.randn(n, 128), jnp.float32)
    (grad,) = jax.jit(lambda v, ct: jax.vjp(onto, v)[1](ct))(clean, ct)
    assert grad.dtype == clean.dtype
    np.testing.assert_array_equal(
        np.asarray(grad.astype(jnp.float32)),
        np.asarray(jnp.where(live, ct[token], 0).astype(dtype)
                   .astype(jnp.float32)))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("block", ["dead_rows", "a_token_k_times"])
def test_a_blocks_rows_of_x_and_their_transpose(block, dtype, monkeypatch):
    """`_from_tokens` is the masked gather and its cast, and its backward
    is handed the cotangent in the compute dtype and returns x's: the
    float32 scatter-add of the bfloat16 (or float32) cotangent, whatever
    the dead rows' cotangent holds."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import moe

    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    n, rows, count, draw = BLOCKS[block]
    r = np.random.RandomState(12)
    token = jnp.asarray(draw(r), jnp.int32)
    live = (jnp.arange(rows) < count)[:, None]
    x = jnp.asarray(r.randn(n, 128), jnp.float32)
    got, pull = jax.vjp(lambda x: moe._from_tokens(x, token, live, dtype), x)
    np.testing.assert_array_equal(
        np.asarray(got.astype(jnp.float32)),
        np.asarray(jnp.where(live, x[token], 0).astype(dtype)
                   .astype(jnp.float32)))
    ct = jnp.asarray(r.randn(rows, 128), dtype)
    (dx,) = pull(jnp.where(live, ct, jnp.nan))
    want = _scatter(ct, token, live, n)
    assert dx.dtype == x.dtype and np.isfinite(np.asarray(dx)).all()
    assert float(jnp.abs(dx - want).max()) <= 1e-6 * float(
        jnp.abs(want).max())


def test_the_kernels_lowering_sums_by_products_and_counts_what_it_counted(
        monkeypatch):
    """One layer at lane-multiple widths under the interpreter: each
    lowering (the forward op's, the gradient op's replay) that takes the
    kernels bumps `moe_onto_tokens_grouped` beside `moe_dispatch_gmm`; the
    step's jaxpr holds the sums' call, `onto_tokens_tgmm`, and no
    scatter-add at all (`dx` takes the groups' true sizes like the two
    other products, so not even the G integers of a stretched last group);
    the three device counts are the plain path's. Without the kernels (no
    interpreter) neither counter moves and the scatter stays."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import profiler

    L = fluid.layers
    feed = {"x": np.random.RandomState(5).randn(64, 128).astype(np.float32)}
    found = {}
    for kernel in (True, False):
        if kernel:
            monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
        else:
            monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET")
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            x = L.data("x", [64, 128], append_batch_size=False)
            y, load = L.moe_experts(
                L.fc(x, 128, bias_attr=False), experts_total=8,
                experts_held=4, d_ff=128, k=2, scaling=2.446)
            loss = L.reduce_mean(L.square(y))
            fluid.optimizer.SGD(0.1).minimize(loss)
        main.random_seed = startup.random_seed = 3
        exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
        exe.run(startup, scope=scope)
        before = settled_counters()
        compiled, feeds, _ = exe._prepare_run(main, feed, [loss], scope)
        jaxpr = jax.make_jaxpr(compiled.jit_fn)(
            exe._assemble_state(compiled, scope), feeds, jax.random.key(0))
        bumped = {n: profiler.counters().get(n, 0) - before.get(n, 0)
                  for n in ("moe_dispatch_gmm", "moe_onto_tokens_grouped")}
        # once a lowering: the forward op's, the gradient op's replay
        assert set(bumped.values()) == {2 if kernel else 0}, bumped
        assert ("onto_tokens_tgmm" in str(jaxpr)) is kernel
        # (the plain path's `part.at[-1].add`, a scatter-add of G integers,
        # is under `moe.gather` too; the kernels take the true sizes)
        scatters = {stage for stage, primitive in _stage_equations(
            jaxpr.jaxpr) if primitive == "scatter-add"}
        assert scatters == (set() if kernel else
                            {"moe.gather", "moe.combine"}), scatters
        before = settled_counters()
        got = exe.run(main, feed=feed, fetch_list=[loss, load], scope=scope)
        found[kernel] = (got, _counted(before))
    (loss_k, load_k), counts_k = found[True]
    (loss_p, load_p), counts_p = found[False]
    np.testing.assert_array_equal(load_k, load_p)
    np.testing.assert_allclose(loss_k, loss_p, rtol=1e-5)
    assert counts_k == counts_p and counts_k["moe_rows_live"] == load_k.sum()
    assert counts_k["device_counts_dropped"] == 0
