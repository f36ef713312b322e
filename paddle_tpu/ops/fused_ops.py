"""Fused-op lowerings (reference: paddle/fluid/operators/fused/ — e.g.
fused_elemwise_activation, fusion_lstm; Fluid fuses on CUDA via hand-written
kernels and IR passes). On TPU, XLA already fuses elementwise chains into
matmuls; the ops here are the ones that need a real kernel: blocked flash
attention (Pallas) so the [s, s] score matrix never materializes in HBM.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import profiler
from .nn_ops import rms_norm, rope_scaling_attr, rotate_half
from .pallas import cost, on_mesh
from .pallas.flash_attention import _xla_attention, flash_attention
from .pallas.mha_short import mha_short, mha_short_viable
from .pallas.qk_prep import qk_prep, qk_prep_viable
from .registry import register_op

# attention_path's three thresholds; nothing else reads them
FLASH_MIN_SCORE_BYTES = 2 << 30
FLASH_MIN_SEQ = 2048
RING_MIN_SEQ = 4096


def _use_pallas() -> bool:
    # asked of the module at each call, as nn_ops asks it: the v5e compile
    # test steers the answer there
    from .pallas.flash_attention import _use_pallas as can_run

    return can_run()


def attention_path(q, k, v, *, layout, causal, window, group, mesh) -> str:
    """Which implementation a `fused_multihead_attention` call gets, from
    what the lowering can observe: the shapes of q, k and v in `layout`,
    the masks, `group` query heads to a key/value head, the mesh, and
    whether this backend runs Pallas. The first rule that holds:

      "ring"   ops/pallas/ring_attention.py: the mesh's `model` axis is
               above 1, q is `RING_MIN_SEQ` long or longer, and the axis
               divides q's and k's lengths. The sequence is sharded over
               the axis and only a chunk pair of scores exists at a time.
               Below the threshold the axis stays tensor parallelism.
      "flash"  ops/pallas/flash_attention.py, on one device that runs
               Pallas: the [b, h, sq, sk] float32 scores are above
               `FLASH_MIN_SCORE_BYTES`, or both lengths are at least
               `FLASH_MIN_SEQ`. It visits only the blocks `causal` and
               `window` admit, reads grouped heads in place and takes
               values narrower or wider than the keys. The same size per shard of a
               mesh takes "xla": GSPMD cannot partition a custom call.
      "short"  ops/pallas/mha_short.py, where Pallas runs and whole score
               rows fit VMEM (`mha_short_viable`: heads of 64 or 128 that
               tile 128 lanes, up to `MAX_SHORT_SEQ`): layout "bshd",
               whose [b, s, heads*dh] operands it reads in place, values as
               wide as the keys, no group, no window. On one device, and
               per shard of a mesh that shards `batch` alone and divides
               the batch (`on_mesh.batch_shards`).
      "xla"    `_xla_attention` everywhere else: "bhsd", the CPU, every
               other mesh; it shards by propagation like the rest of the
               graph.

    `causal` changes no choice: every path takes it (a window needs it,
    which the lowering checks).

    Why the values. `FLASH_MIN_SCORE_BYTES`, 2 GiB: an eighth of a v5e's
    HBM for one layer's scores; XLA's path at the expert cells' 2.1 GB and
    8.6 GB was never run (PERF.md section 7). `FLASH_MIN_SEQ`, 2,048: where
    32k tokens a batch of 12 heads cross that size; XLA won at 512 and
    nothing between was measured (ROADMAP Reach B6). `RING_MIN_SEQ`,
    4,096: a CPU memory study, not yet run on the chip (ROADMAP Reach B7).
    What each path takes in the nine cells: PERF.md section 5, and the
    ledger's `attn_short_ms_per_step` and `flash_*_ms_per_step` lines.
    """
    bshd = layout == "bshd"
    s_ax, h_ax = (1, 2) if bshd else (2, 1)
    b, heads, sq, dh = q[0], q[h_ax], q[s_ax], q[3]
    sk = k[s_ax]
    model_n = mesh.shape.get("model", 1) if mesh is not None else 1
    if (model_n > 1 and sq >= RING_MIN_SEQ
            and sq % model_n == 0 and sk % model_n == 0):
        return "ring"
    shards = on_mesh.batch_shards(mesh, b, k[0])
    if not shards or not _use_pallas():
        return "xla"
    if ((b // shards) * heads * sq * sk * 4 > FLASH_MIN_SCORE_BYTES
            or min(sq, sk) >= FLASH_MIN_SEQ):
        return "flash" if shards == 1 else "xla"
    plain = v[3] == dh and group == 1 and not window
    if bshd and plain and mha_short_viable(sq, sk, heads, dh):
        return "short"
    return "xla"


def block_diffusion_mask(length, block):
    """Block diffusion's training mask (BD3-LM, arXiv:2503.09573, its
    vectorised training) over the 2 x `length` rows `[noisy ; clean]` of
    one sequence cut in blocks of `block`, as a [2L, 2L] boolean array:
    `cost.block_diffusion_rules`' three rectangles, each `cost.admits`
    under a granule, which is how the flash kernels take them, and
    nothing where a clean row would see a noisy one; the whole array is
    for the XLA path at small sizes and for the tests."""
    i, j = np.arange(length)[:, None], np.arange(length)[None, :]
    part = {name: cost.admits(i, j, *rule, block)
            for name, rule in cost.block_diffusion_rules(block).items()}
    return np.block([[part["own"], part["past"]],
                     [np.zeros_like(part["own"]), part["clean"]]])


def _block_diffusion_flash(q, k, v, block, sm_scale):
    """q, k, v head-major, `[2b, heads, L, d]`, a sequence's noisy copy
    and then its clean copy along the first axis. Three calls of the
    flash kernels under a granule, none of which visits a block of scores
    the mask empties: the clean copy on itself; the noisy copy on the
    clean blocks before each query's own (`attn.noisy_past`) and on its
    own noisy block (`attn.noisy_own`), one softmax over both key sets,
    joined by the two calls' log-sum-exp rows in float32. The first
    noisy block has no past: its rows of that call weigh exp(-1e30)."""
    (qn, qc), (kn, kc), (vn, vc) = (
        (t[0::2], t[1::2]) for t in (q, k, v))
    def call(q, k, v, rule, **rows):
        offset, window = cost.block_diffusion_rules(block)[rule]
        return flash_attention(q, k, v, causal=True, sm_scale=sm_scale,
                               granule=block, causal_offset=offset,
                               window=window, **rows)

    with jax.named_scope("attn.clean"):
        clean = call(qc, kc, vc, "clean")
    with jax.named_scope("attn.noisy_past"):
        past, lse_past = call(qn, kc, vc, "past", with_lse=True,
                              lse_grad=True)
    with jax.named_scope("attn.noisy_own"):
        own, lse_own = call(qn, kn, vn, "own", with_lse=True, lse_grad=True)
    with jax.named_scope("attn.join"):
        lse = jnp.logaddexp(lse_past, lse_own)
        noisy = (past * jnp.exp(lse_past - lse)[..., None]
                 + own * jnp.exp(lse_own - lse)[..., None]).astype(q.dtype)
    return jnp.stack([noisy, clean], 1).reshape(q.shape[:3] + v.shape[3:])


@register_op("fused_multihead_attention",
             no_grad_inputs=("KeyBias", "Admit"))
def _fused_mha(ctx, op):
    """Q/K/V: [b, nh, s, dh] (layout attr "bhsd", default) or
    [b, s, nh, dh] ("bshd" — the shape the model's QKV reshape produces,
    no head transposes anywhere in the graph); optional KeyBias: [b, sk]
    additive (0 keep, large-negative drop). Out matches the input layout.
    V's last dim may be narrower than Q's and K's (latent attention) or
    wider (a differential head's pair of value heads), and is then Out's. K and V may have fewer heads than Q, a divisor of its
    count: query head n reads key/value head n // group. Attr `window`
    (0: none; needs `causal`) admits only the last `window` keys a query
    may see: key j for query i iff 0 <= i - j < window.

    Replaces the unfused matmul->softmax->dropout->matmul chain
    (reference model pattern, e.g. the Fluid transformer/BERT models) with
    one Pallas kernel; in-kernel dropout is regenerated in the backward.

    Optional QNorm, KNorm ([dh] each, together): q and k are first normed
    head by head as the op `rms_norm` norms the last axis, with attr
    `qk_norm_epsilon`. Attr `rope_theta` > 0 (layout "bshd", with the
    norms or without them) then turns q and k by the op
    `rotary_embedding`'s positions, under attr `rope_scaling` (YaRN's five
    numbers, `nn_ops.yarn_frequencies`) by its scaled tables, whether the
    layer has a window or none. Attr `rotary_dim` (absent: the whole
    head) turns the first `rotary_dim` lanes of a head as a head of that
    width and passes the rest as they were (`partial_rotary_factor`); gauge
    `attn_rotary_lanes` then holds it. On the flash path with layout "bshd"
    and heads of whole 128-lane slices, that and the head-major write the
    kernel wants are one kernel pair (ops/pallas/qk_prep.py; counter
    `attn_qk_prep_fused`, and `attn_qk_prep_rope_only` where it runs
    without a norm); on every other path the two ops' own functions run
    first, in `jnp`.

    Optional outputs QPrepared [b, heads, s, dh] and KPrepared [b, groups,
    s, dh]: q and k as the attention took them, normed and turned, in its
    dtype and head-major in either layout, with no gradient (what
    `index_kl` reads). On the fused path they are the
    kernel pair's own outputs, the arrays the flash kernels read (counter
    `attn_qk_prep_handed_back`); elsewhere the `jnp` preparation's,
    transposed.

    Optional Admit: [b, sq, sk] int8, an admission that is data (a
    selection's: `sparse_select`): a pair whose entry is 0 is refused for
    every head, beside what `causal` and `window` refuse; no gradient.
    Attr `admit_keys`: the keys a query admits at most, which the flash
    kernels' declarations count by. Optional output Lse: each row's
    log-sum-exp over its admitted scaled scores, [b, heads, sq] float32
    in either layout, with no gradient (what `index_kl` rebuilds the
    probabilities from). Both on the "flash" and "xla" paths alone.

    Attr `diffusion_block` (optional, B > 0; layout "bshd"): block
    diffusion's training mask in place of `causal`. Axis 1 holds a
    sequence twice, its L noisy rows and then its L clean rows, both at
    positions 0..L-1 (the norm and the rotation see them as two rows of
    the batch), under `block_diffusion_mask(L, B)`: on the flash path
    three calls of the kernels under a granule of B, told apart in a
    device trace by the scopes `attn.clean`, `attn.noisy_past` and
    `attn.noisy_own` under the op's (`_block_diffusion_flash`), on the
    "xla" path the mask as an admission. No bias, window, dropout,
    admission or extra output with it. Gauge `attn_diffusion_block`.

    Attr `q_lora_rank` (optional, > 0) labels a latent-attention call
    whose query came through a compressed latent; it changes nothing
    computed and counts `attn_latent_q_lora` once a lowering.
    """
    q = ctx.in_(op, "Q")
    k = ctx.in_(op, "K")
    v = ctx.in_(op, "V")
    bias = ctx.in_(op, "KeyBias")
    admit = ctx.in_(op, "Admit")
    with_lse = bool(op.output("Lse"))
    with_prepared = bool(op.output("QPrepared"))
    q_norm, k_norm = ctx.in_(op, "QNorm"), ctx.in_(op, "KNorm")
    norm_eps = float(op.attr("qk_norm_epsilon", 1e-5))
    rope_theta = float(op.attr("rope_theta", 0.0) or 0.0)
    rope_scaling = rope_scaling_attr(op, "rope_scaling")
    rotary_dim = int(op.attr("rotary_dim", 0) or 0)
    causal = op.attr("causal", False)
    dropout = float(op.attr("attn_dropout", 0.0))
    is_test = op.attr("is_test", False) or ctx.is_test
    sm_scale = op.attr("sm_scale", 0.0) or None
    layout = op.attr("layout", "bhsd") or "bhsd"
    bshd = layout == "bshd"
    window = int(op.attr("window", 0) or 0)
    h_ax = 2 if bshd else 1
    group = q.shape[h_ax] // k.shape[h_ax]
    block = int(op.attr("diffusion_block", 0) or 0)
    if window and not causal:
        raise ValueError("fused_multihead_attention: a window needs causal")
    if block and (not bshd or window or dropout or bias is not None
                  or admit is not None or with_lse or with_prepared
                  or q.shape[1] % (2 * block)):
        raise ValueError(
            "fused_multihead_attention: diffusion_block takes layout "
            "\"bshd\" with 2 x L rows, L a multiple of the block, and no "
            "bias, window, dropout, admission, Lse or prepared output")
    if (q_norm is None) != (k_norm is None):
        raise ValueError(
            "fused_multihead_attention: QNorm and KNorm come together")
    if rope_theta and not bshd:
        raise ValueError(
            "fused_multihead_attention: rope_theta needs layout \"bshd\", "
            "whose axis 1 the positions count")
    if rope_scaling and not rope_theta:
        raise ValueError(
            "fused_multihead_attention: rope_scaling needs rope_theta")
    if rotary_dim == q.shape[-1]:
        rotary_dim = 0
    if rotary_dim and (not rope_theta or rotary_dim % 2
                       or rotary_dim > q.shape[-1]):
        raise ValueError(
            f"fused_multihead_attention: rotary_dim {rotary_dim} needs "
            f"rope_theta, and to be even and at most the head's "
            f"{q.shape[-1]} lanes")

    shapes = q.shape, k.shape, v.shape
    if block:
        # the noisy and the clean copy as two rows of the batch: each
        # counts its own positions 0..L-1
        q, k, v = (t.reshape(2 * t.shape[0], t.shape[1] // 2, *t.shape[2:])
                   for t in (q, k, v))
    prepare = q_norm is not None or bool(rope_theta)
    if prepare:
        raw = q, k, v

        def prepared():
            """q and k as the ops `rms_norm` and `rotary_embedding` leave
            them, then in the attention's dtype."""
            q, k, _ = raw
            if q_norm is not None:
                q = rms_norm(q, q_norm, norm_eps, 3)
                k = rms_norm(k, k_norm, norm_eps, 3)
            if rope_theta:
                q = rotate_half(q, rope_theta, rope_scaling, rotary_dim)
                k = rotate_half(k, rope_theta, rope_scaling, rotary_dim)
            return ctx.amp_cast(op, q, k)

    q, k, v = ctx.amp_cast(op, q, k, v)
    if bias is not None:
        bias = jnp.asarray(bias, jnp.float32)

    if is_test:
        dropout = 0.0
    rng = ctx.rng_for(op.output("Out")[0]) if dropout > 0.0 else None

    mesh = ctx.mesh
    path = attention_path(*shapes, layout=layout, causal=causal,
                          window=window, group=group, mesh=mesh)
    if path == "ring" and (window or group != 1):
        raise ValueError(
            "fused_multihead_attention: ring sequence parallelism takes "
            "neither a window nor grouped key/value heads")
    if path in ("ring", "short") and (admit is not None or with_lse
                                      or block):
        raise ValueError(
            f"fused_multihead_attention: the {path!r} path takes no "
            "admission or diffusion_block and gives no log-sum-exp rows")
    profiler.bump_counter(f"attn_dispatch_{path}")
    if path == "flash" and window:
        profiler.bump_counter("attn_dispatch_flash_window")
    profiler.set_counter("attn_kv_group", group)
    if rope_scaling:
        profiler.bump_counter("attn_rope_scaled")
    if rotary_dim:
        profiler.set_counter("attn_rotary_lanes", rotary_dim)
    if op.attr("q_lora_rank", 0):
        profiler.bump_counter("attn_latent_q_lora")
    if block:
        profiler.set_counter("attn_diffusion_block", block)
    fused = (prepare and path == "flash" and bshd
             and qk_prep_viable(q.shape[-1], v.shape[-1]))
    if prepare and not fused:
        q, k = prepared()

    def swap(t):  # bshd <-> bhsd; the flash and ring kernels are head-major
        return jnp.transpose(t, (0, 2, 1, 3)) if bshd else t

    if path == "short":
        # [b, s, nh, dh] back to the [b, s, nh*dh] the projection wrote:
        # XLA folds this with the Program's reshape2 into nothing. On a
        # mesh that shards `batch` alone each chip's rows are a whole
        # problem of the kernel's shape, so it runs per shard
        # (ops/pallas/on_mesh.py) and nothing is partitioned
        b, sq, nh, dh = q.shape
        if on_mesh.batch_shards(mesh, b) > 1:
            profiler.bump_counter("pallas_on_mesh_calls")
        out = mha_short(
            q.reshape(b, sq, nh * dh), k.reshape(b, -1, nh * dh),
            v.reshape(b, -1, nh * dh), nh, bias=bias, causal=causal,
            sm_scale=sm_scale, dropout=dropout, rng_key=rng, mesh=mesh,
        ).reshape(b, sq, nh, dh)
    elif path == "xla":
        # plain traced code: GSPMD partitions it from the feed and
        # parameter shardings, head (`model`) parallelism included
        scale = sm_scale or 1.0 / float(np.sqrt(q.shape[-1]))
        if block:
            mask = block_diffusion_mask(shapes[0][1] // 2, block)
            out = _xla_attention(
                q.reshape(shapes[0]), k.reshape(shapes[1]),
                v.reshape(shapes[2]), None, False, scale, 0.0, None,
                layout=layout, admit=jnp.asarray(mask[None], jnp.int8))
        else:
            out = _xla_attention(q, k, v, bias, causal, scale, dropout, rng,
                                 layout=layout, window=window, admit=admit,
                                 with_lse=with_lse)
    elif path == "flash":
        if fused:
            # from the arrays as they came: the kernel pair norms (with
            # weights) and rotates in float32 and writes the attention's
            # dtype, head-major (ops/pallas/qk_prep.py)
            profiler.bump_counter("attn_qk_prep_fused")
            if q_norm is None:
                profiler.bump_counter("attn_qk_prep_rope_only")
            if with_prepared:
                profiler.bump_counter("attn_qk_prep_handed_back")
            operands = qk_prep(*raw, q_norm, k_norm, epsilon=norm_eps,
                               theta=rope_theta, scaling=rope_scaling,
                               out_dtype=q.dtype, rotary_dim=rotary_dim)
        else:
            operands = swap(q), swap(k), swap(v)
        # values narrower or wider than the keys: the kernel takes them at
        # their own width in whole lanes, and so writes the output
        if block:
            out = _block_diffusion_flash(*operands, block, sm_scale)
            out = swap(out).reshape(shapes[0][:3] + v.shape[3:])
        else:
            out = flash_attention(
                *operands, bias=bias, causal=causal, sm_scale=sm_scale,
                dropout=dropout, rng_key=rng, window=window, admit=admit,
                admit_keys=int(op.attr("admit_keys", 0) or 0),
                with_lse=with_lse)
            out = (swap(out[0]), out[1]) if with_lse else swap(out)
    else:  # "ring"
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from .pallas.ring_attention import ring_attention

        # on GLOBAL arrays: GSPMD places the collectives, and the chunk
        # accesses lower to the ICI ring. The sequence dim is PINNED onto
        # 'model' (and the output back): ring's O(s/n) per-device memory
        # depends on the sequence actually being sharded, and propagation
        # from batch-sharded feeds alone is free to replicate it
        seq_sh = NamedSharding(mesh, P("batch", None, "model", None))

        def pin(t):
            return jax.lax.with_sharding_constraint(t, seq_sh)

        if bias is not None:
            bias = jax.lax.with_sharding_constraint(
                bias, NamedSharding(mesh, P("batch", "model")))
        out = swap(pin(ring_attention(
            pin(swap(q)), pin(swap(k)), pin(swap(v)), "model",
            axis_size=mesh.shape["model"], bias=bias, causal=causal,
            sm_scale=sm_scale, dropout=dropout, rng_key=rng,
        ).astype(q.dtype)))
    if with_lse:
        out, lse = out
        ctx.out(op, "Lse", lse)
    if with_prepared:
        # the arrays the flash kernels read, where they ran
        handed = operands[:2] if path == "flash" else (swap(q), swap(k))
        for slot, t in zip(("QPrepared", "KPrepared"), handed):
            ctx.out(op, slot, jax.lax.stop_gradient(t))
    ctx.out(op, "Out", out)
