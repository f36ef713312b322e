"""What the Pallas calls of a traced function declare: the tests of the
kernels' `cost_estimate` (ops/pallas/cost.py has the convention) read the
`pallas_call` equations of a jaxpr, nested ones included, and hold them
to counts written out by hand. `operand_shapes` reads the same equations'
operands and results (`operand_types` with their dtypes),
`block_shapes` their grids and blocks;
`jaxpr_digest` hashes a traced jaxpr's text, for the tests that hold a
function's default path to what a parent commit traced."""

import hashlib
import re

import jax
from jax.extend import core as jex_core


def _jaxprs(value):
    if isinstance(value, jex_core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jex_core.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for item in value:
            yield from _jaxprs(item)


def _walk(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.setdefault(eqn.params["name"], []).append(eqn)
            continue  # the kernel's own body holds no call
        for value in eqn.params.values():
            for inner in _jaxprs(value):
                _walk(inner, found)


def _calls(fn, args, read) -> dict:
    found = {}
    _walk(jax.make_jaxpr(fn)(*args).jaxpr, found)
    return {name: [read(eqn) for eqn in eqns] for name, eqns in found.items()}


def declared(fn, *args) -> dict:
    """Kernel name -> the `pl.CostEstimate` of each of its calls in
    `fn(*args)`, in the order they are traced. Nothing runs."""
    return _calls(fn, args, lambda eqn: eqn.params["cost_estimate"])


def operand_shapes(fn, *args) -> dict:
    """Kernel name -> (operands' shapes, results' shapes) of each of its
    calls in `fn(*args)`: the arrays the custom call reads and writes."""
    return _calls(fn, args, lambda eqn: (
        [v.aval.shape for v in eqn.invars],
        [v.aval.shape for v in eqn.outvars]))


def operand_types(fn, *args) -> dict:
    """As `operand_shapes`, each array as (shape, dtype's name): what
    crosses HBM at a call, and in which dtype."""
    return _calls(fn, args, lambda eqn: tuple(
        [(v.aval.shape, v.aval.dtype.name) for v in vs]
        for vs in (eqn.invars, eqn.outvars)))


def block_shapes(fn, *args) -> dict:
    """Kernel name -> (grid, the block of each operand and result in
    turn) of each of its calls in `fn(*args)`."""
    def read(eqn):
        mapping = eqn.params["grid_mapping"]
        return mapping.grid, [
            tuple(getattr(dim, "block_size", dim) for dim in m.block_shape)
            for m in mapping.block_mappings]

    return _calls(fn, args, read)


def numbers(estimate) -> tuple:
    return (estimate.flops, estimate.transcendentals,
            estimate.bytes_accessed)


def jaxpr_digest(fn, *args) -> str:
    """sha256 (16 hex digits) of the text of `fn(*args)`'s jaxpr, object
    addresses blanked: equal where two trees trace the same equations
    under one JAX."""
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def program_digest(build, amp=False) -> str:
    """`jaxpr_digest` of a Program and its backward: `build()` declares
    layers under fresh Programs and returns the loss; the global block is
    lowered as the Executor's step lowers it, every var an op reads and
    none writes (parameters and data) an argument by its declared shape
    and dtype. Nothing runs and no scope is filled."""
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.ops.registry import LoweringContext, lower_block

    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), \
            fluid.unique_name.guard():
        loss = build()
        pairs = fluid.backward.append_backward(loss)
    if amp:
        main._amp_dtype = "bfloat16"
    block = main.global_block()
    written, read = set(), []
    for op in block.ops:
        for n in op.input_arg_names():
            if n and n not in written and n not in read:
                read.append(n)
        written.update(n for n in op.output_arg_names() if n)
    shapes = [jax.ShapeDtypeStruct(tuple(block.var(n).shape),
                                   jnp.dtype(np.dtype(block.var(n).dtype)))
              for n in read]
    fetch = [loss.name] + [g.name for _, g in pairs]

    def step(*values):
        ctx = LoweringContext(main, rng_key=jax.random.PRNGKey(0))
        ctx.values.update(zip(read, values))
        lower_block(ctx, block)
        return [ctx.get(n) for n in fetch]

    return jaxpr_digest(step, *shapes)
