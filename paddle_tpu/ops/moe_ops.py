"""Mixture-of-Experts op lowerings, the math in parallel/moe.py: `moe_ffn`
dispatches to the GShard dense-dispatch formulation, which drops past an
expert's capacity; `moe_experts` to the dropless grouped product over the
experts held here. Under a mesh whose 'ep'
axis shards the expert (leading) dim of the expert parameters, GSPMD
lowers the dispatch/combine einsums to the all-to-all over ICI — the
lowering itself stays pure jnp (SURVEY.md §2.8 expert parallel; no
reference counterpart — Fluid ~1.5 has no MoE)."""

from __future__ import annotations

from .registry import register_op


@register_op("moe_ffn")
def _moe_ffn(ctx, op):
    from ..parallel.moe import moe_ffn

    x = ctx.in_(op, "X")
    gate = ctx.in_(op, "Gate")
    w1 = ctx.in_(op, "W1")
    b1 = ctx.in_(op, "B1")
    w2 = ctx.in_(op, "W2")
    b2 = ctx.in_(op, "B2")
    # AMP: the expert FFN einsums ride the amp dtype INSIDE moe_ffn (both
    # dot operands cast there — casting weights here would just be undone
    # by jnp promotion against fp32 activations); routing softmax and the
    # load-balance aux loss stay fp32 per the repo-wide policy
    cd = ctx.amp_dtype_for(op)
    y, aux = moe_ffn(
        {"gate": gate, "w1": w1, "b1": b1, "w2": w2, "b2": b2},
        x,
        capacity_factor=op.attr("capacity_factor", 1.25),
        k=op.attr("k", 2),
        compute_dtype=cd,
    )
    ctx.out(op, "Out", y)
    ctx.out(op, "AuxLoss", aux.reshape(1))


@register_op("moe_experts", no_grad_inputs=("Bias",),
             device_counts=("moe_rows_routed", "moe_rows_live",
                            "moe_blocks_run"))
def _moe_experts(ctx, op):
    """X: [..., D]; Gate: [D, experts_total]; Bias: [experts_total], the
    router's correction (selection only, no gradient); attr `score_func`
    "sigmoid" (the default) or "softmax"; attr `norm_eps` (optional, 0)
    added to the renormalisation's sum; WGate, WUp:
    [experts_held, D, F]; WDown: [experts_held, F, D]. Out like X: what
    the held experts add. Load: [experts_held] int32, the assignments
    each held expert took.

    XExperts (optional): [..., D_e], what the experts read where that is
    not the router's X (a latent of the token: gauge `moe_latent_width`);
    WGate, WUp and WDown are then D_e wide and Out is like XExperts.
    Attr `expert_form` (optional): absent, the experts are SiLU-gated,
    `W_down (silu(W_gate x) * W_up x)`; "relu2": there is no WGate and an
    expert is `W_down relu(W_up x)^2`. `moe_assignments` counts the
    tokens times k of each lowering.

    Three counts the step itself makes (`ctx.count`: data, read from
    `profiler.counters()` once the step has run), once an op and not in
    its gradient op's replay: `moe_rows_routed`, tokens times k;
    `moe_rows_live`, the assignments the held experts took (`sum(Load)`);
    `moe_blocks_run`, the first block and every trip of the overflow
    loop."""
    import jax.numpy as jnp

    from .. import profiler
    from ..parallel.moe import _block_count, _block_rows, moe_experts, stage
    from .pallas.grouped_matmul import grouped_matmul_viable
    from .pallas.on_mesh import batch_shards

    held, total = op.attr("experts_held"), op.attr("experts_total")
    gate = ctx.in_(op, "Gate")
    if gate.shape[1] != total:
        raise ValueError(
            f"moe_experts: Gate has {gate.shape[1]} columns, experts_total "
            f"is {total}")
    x = ctx.in_(op, "X")
    experts_x = ctx.in_(op, "XExperts") if op.input("XExperts") else None
    form = op.attr("expert_form", "silu_gated")
    if form not in ("silu_gated", "relu2"):
        raise ValueError(f"moe_experts: expert_form {form!r}: expected "
                         "'silu_gated' or 'relu2'")
    w_gate = ctx.in_(op, "WGate") if form == "silu_gated" else None
    w_up = ctx.in_(op, "WUp")
    read = x if experts_x is None else experts_x
    if gate.shape[0] != x.shape[-1]:
        raise ValueError(
            f"moe_experts: Gate {gate.shape} does not take the router's "
            f"input X {x.shape}")
    if w_up.shape[1] != read.shape[-1] or read.shape[:-1] != x.shape[:-1]:
        raise ValueError(
            f"moe_experts: WUp {w_up.shape} does not take the experts' "
            f"input {read.shape} (the router's X is {x.shape})")
    profiler.bump_counter("moe_dispatch_grouped")
    profiler.set_counter("moe_experts_held", held)
    profiler.set_counter("moe_experts_total", total)
    score_func = op.attr("score_func", "sigmoid")
    if score_func == "softmax":
        profiler.bump_counter("moe_route_softmax")
    k = op.attr("k")
    routed = x.size // x.shape[-1] * k
    profiler.bump_counter("moe_assignments", routed)
    if experts_x is not None:
        profiler.set_counter("moe_latent_width", int(experts_x.shape[-1]))
    # rows of the first, straight-line block of this layer's N*k sorted
    # assignments: it follows the share held
    block_rows = _block_rows(routed, held / total)
    profiler.set_counter("moe_block_rows", block_rows)
    # the router is float32 inside moe_route; the grouped products ride
    # the amp dtype, cast inside (both operands), as in moe_ffn
    compute_dtype = ctx.amp_dtype_for(op)
    # the Pallas kernels where they can run: lane-multiple widths, Mosaic
    # or the interpreter, one device (GSPMD cannot partition a custom call,
    # and the sorted rows are no batch axis to run per shard)
    kernel = batch_shards(ctx.mesh) == 1 and grouped_matmul_viable(
        w_up.shape[1], w_up.shape[2], compute_dtype or x.dtype)
    if kernel:
        profiler.bump_counter("moe_dispatch_gmm")
    y, load = moe_experts(
        x, gate, ctx.in_(op, "Bias"), w_gate, w_up, ctx.in_(op, "WDown"),
        k=k, scaling=op.attr("scaling", 1.0),
        experts_held=held, held_from=op.attr("held_from", 0),
        renormalize=op.attr("renormalize", True),
        compute_dtype=compute_dtype, score_func=score_func, kernel=kernel,
        norm_eps=op.attr("norm_eps", 0.0), experts_x=experts_x)
    ctx.out(op, "Out", y)
    ctx.out(op, "Load", load)
    with stage("moe.sort"):  # where the load is made
        ctx.count("moe_rows_routed", routed)
        ctx.count("moe_rows_live", lambda: jnp.sum(load))
        ctx.count("moe_blocks_run", lambda: jnp.maximum(
            _block_count(load, block_rows), 1))
