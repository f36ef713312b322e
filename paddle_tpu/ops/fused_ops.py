"""Fused-op lowerings (reference: paddle/fluid/operators/fused/ — e.g.
fused_elemwise_activation, fusion_lstm; Fluid fuses on CUDA via hand-written
kernels and IR passes). On TPU, XLA already fuses elementwise chains into
matmuls; the ops here are the ones that need a real kernel: blocked flash
attention (Pallas) so the [s, s] score matrix never materializes in HBM.
"""

from __future__ import annotations

import functools
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np

from .. import profiler
from ..analysis.artifacts import load_artifact
from .nn_ops import rms_norm, rope_scaling_attr, rotate_half
from .pallas import on_mesh
from .pallas.flash_attention import _xla_attention, flash_attention
from .pallas.mha_short import mha_short, mha_short_viable
from .pallas.qk_prep import qk_prep, qk_prep_viable
from .registry import register_op

_logger = logging.getLogger(__name__)

# Attention kernel selection, from what the lowering can observe:
#   short  ops/pallas/mha_short.py, where a few batch rows of whole score
#          rows fit VMEM: a backend that runs Pallas, layout "bshd",
#          head_dim 64 or 128 with heads*head_dim a multiple of 128, and
#          sq, sk up to mha_short.MAX_SHORT_SEQ (set from chip runs of the
#          benchmark's one-chip cells, PERF.md). Its operands are the
#          [b, s, heads*dh] arrays the projections write, so the head
#          relayout copies XLA puts around its own batched products are
#          not in the step. On one device, and on a mesh that shards the
#          `batch` axis alone and divides the batch: there each chip calls
#          the kernel on its own rows (ops/pallas/on_mesh.py), which are a
#          whole problem of the kernel's shape, so nothing is partitioned.
#   flash  ops/pallas/flash_attention.py, once the [b, h, sq, sk] float32
#          scores stop fitting HBM comfortably (by score-tensor memory,
#          batch counts as much as length) or above the `flash_min_seq`
#          of the checked-in table (ops/pallas/attn_dispatch_table.json).
#          Measured on v5e at s=512: XLA 299 ms a step, the blocked kernel
#          2,069: it pays only beyond the HBM knee. The kernels visit only
#          the blocks of scores in which `causal` and `window` admit a
#          pair (a band; the masked part of a visited block is computed
#          and thrown away), index the K and V blocks of a query head by
#          `head // group` where K and V have fewer heads, and the forward
#          op and its gradient op share one `flash_fwd` call. At Kimi
#          Linear's latent layer (s=4,096, b=1, 32 heads, keys 192 and
#          values 128 wide, both padded to 256 lanes, causal) the three
#          calls a step now visit 36 of 64 blocks a head; when no block was
#          skipped and the forward ran twice they took 20.7 ms (PERF.md has
#          what they take now). At Trinity's layers (s=8,192, 32 query
#          heads over 4 key/value heads of 128, three layers with a
#          2,048-key window to one full) the fifteen calls a step take
#          73 ms, 47% of peak on the pairs the masks admit; the kernel
#          stays head-major there too: cutting a head's blocks from the
#          [b, s, heads*128] arrays the projections write was built and
#          measured 4.8% slower end to end (PERF.md, PR 33). XLA's path
#          was run at neither: its float32 scores of one row are 2.1 GB
#          and 8.6 GB. Where the op is given QK-norm weights (and
#          `rope_theta`), this path's way into the kernels, the norm of a
#          head's lanes, the positions and the head-major write, is one
#          kernel pair over q, k and v (ops/pallas/qk_prep.py; PERF.md,
#          PR 34); the other paths run `rms_norm` and `rotate_half` first.
#          Values narrower than the keys travel through this kernel at
#          their own width in whole lanes (128 beside the keys' 256 at
#          latent attention's 192 and 128; PERF.md, PR 40); the other
#          paths take them as they are.
#   xla    _xla_attention everywhere else: the "bhsd" layout, the CPU, and
#          every other mesh of several devices (tensor or pipeline
#          parallel, a batch the axis does not divide: GSPMD cannot
#          partition a custom call; past the knee sequence parallelism
#          takes over there).
#
# Env surface:
#   PADDLE_TPU_ATTN_DISPATCH = auto (default) | xla | flash: force a
#       path; "xla" means no Pallas anywhere, "flash" on a backend that
#       cannot compile the kernel raises.
#   PADDLE_TPU_FLASH_SCORE_BYTES: override the score-bytes knee.
#   PADDLE_TPU_SP_MODE = ring | ulysses | off: sequence parallelism
#       over the mesh 'model' axis; unset means AUTO (ring above the
#       table's ring_min_seq when the sequence divides the axis).
_TABLE_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "pallas", "attn_dispatch_table.json",
)
_DEFAULT_THRESHOLDS = {
    "flash_min_score_bytes": 2 << 30,
    "flash_min_seq": 2048,
    "ring_min_seq": 4096,
}


@functools.lru_cache(maxsize=1)
def attn_dispatch_thresholds() -> dict:
    """The checked-in dispatch table's thresholds (code defaults when
    the data file is missing/corrupt — dispatch must never crash a
    training step over a data file). Loaded through the keyed artifact
    accessor so the (backend, signature) lookup is observable; the
    backend key comes from the env (not jax.default_backend()) because
    this runs at import and must not initialize the platform."""
    t = dict(_DEFAULT_THRESHOLDS)
    table = load_artifact(
        _TABLE_PATH,
        backend=os.environ.get("JAX_PLATFORMS", "auto"),
        signature="thresholds:" + ",".join(sorted(_DEFAULT_THRESHOLDS)),
        default=None,
    )
    loaded = table.get("thresholds") if isinstance(table, dict) else None
    if isinstance(loaded, dict):
        for k, default in _DEFAULT_THRESHOLDS.items():
            try:
                t[k] = int(loaded.get(k, default))
            except (TypeError, ValueError):
                t[k] = default  # per-key fallback on nulls/garbage
    return t


def _flash_score_bytes() -> int:
    env = os.environ.get("PADDLE_TPU_FLASH_SCORE_BYTES")
    if env is not None:
        return int(env)
    return int(attn_dispatch_thresholds()["flash_min_score_bytes"])


def _use_flash(q, k):
    """Score-bytes knee OR the table's measured seq floor — the
    longseq_study decision: default-ON above the threshold. An explicit
    PADDLE_TPU_FLASH_SCORE_BYTES is a FORCE (the longseq study pins each
    path with it), so the seq floor only applies when it is unset."""
    b, h, sq, _ = q.shape
    sk = k.shape[2]
    if b * h * sq * sk * 4 > _flash_score_bytes():
        return True
    if os.environ.get("PADDLE_TPU_FLASH_SCORE_BYTES") is not None:
        return False
    return min(sq, sk) >= int(attn_dispatch_thresholds()["flash_min_seq"])


def _use_pallas() -> bool:
    # asked of the module at each call, as nn_ops asks it: the v5e compile
    # test steers the answer there
    from .pallas.flash_attention import _use_pallas as can_run

    return can_run()


def _dispatch_mode() -> str:
    mode = os.environ.get("PADDLE_TPU_ATTN_DISPATCH", "auto").strip().lower()
    if mode not in ("auto", "xla", "flash"):
        raise ValueError(
            f"PADDLE_TPU_ATTN_DISPATCH={mode!r}: expected auto|xla|flash")
    return mode


def _flash_dispatch(qb, kb) -> str:
    """Resolve the flash-vs-XLA decision for bhsd-shaped q/k. `auto`
    chooses from what it can observe — the shape against the table's
    thresholds, and whether a Pallas kernel can run on this backend at
    all. PADDLE_TPU_ATTN_DISPATCH=flash asks for the kernel by name:
    flash_attention then raises on a backend that cannot compile it."""
    mode = _dispatch_mode()
    if mode == "auto":
        return "flash" if _use_flash(qb, kb) and _use_pallas() else "xla"
    return mode


def _attn_dispatch(q, k, bshd, shards=1, plain=True) -> str:
    """"short", "flash" or "xla" for the rows of the op's q/k that one
    device holds, a `shards`-th of the batch: the short-sequence kernel
    takes from `auto`'s XLA side the shapes it is built for, in the layout
    whose operands it can read in place, where the attention is `plain`:
    values as wide as the keys, as many key/value heads as query heads,
    no window."""
    def bhsd(t):
        b, h, s, d = t.shape
        if bshd:
            h, s = s, h
        return jax.ShapeDtypeStruct((b // shards, h, s, d), t.dtype)

    qb, kb = bhsd(q), bhsd(k)
    path = _flash_dispatch(qb, kb)
    _, nh, sq, dh = qb.shape
    if (path == "xla" and bshd and _dispatch_mode() == "auto"
            and plain
            and _use_pallas() and mha_short_viable(sq, kb.shape[2], nh, dh)):
        return "short"
    return path


@register_op("fused_multihead_attention", no_grad_inputs=("KeyBias",))
def _fused_mha(ctx, op):
    """Q/K/V: [b, nh, s, dh] (layout attr "bhsd", default) or
    [b, s, nh, dh] ("bshd" — the shape the model's QKV reshape produces,
    no head transposes anywhere in the graph); optional KeyBias: [b, sk]
    additive (0 keep, large-negative drop). Out matches the input layout.
    V's last dim may be narrower than Q's and K's (latent attention), and
    is then Out's. K and V may have fewer heads than Q, a divisor of its
    count: query head n reads key/value head n // group. Attr `window`
    (0: none; needs `causal`) admits only the last `window` keys a query
    may see: key j for query i iff 0 <= i - j < window.

    Replaces the unfused matmul->softmax->dropout->matmul chain
    (reference model pattern, e.g. the Fluid transformer/BERT models) with
    one Pallas kernel; in-kernel dropout is regenerated in the backward.

    Optional QNorm, KNorm ([dh] each, together): q and k are first normed
    head by head as the op `rms_norm` norms the last axis, with attr
    `qk_norm_epsilon`; attr `rope_theta` > 0 then turns them by the op
    `rotary_embedding`'s positions, under attr `rope_scaling` (YaRN's five
    numbers, `nn_ops.yarn_frequencies`) by its scaled tables, whether the
    layer has a window or none. On the flash path with layout "bshd"
    and heads of whole 128-lane slices, that and the head-major write the
    kernel wants are one kernel pair (ops/pallas/qk_prep.py); on every
    other path the two ops' own functions run first, in `jnp`.

    Attr `q_lora_rank` (optional, > 0) labels a latent-attention call
    whose query came through a compressed latent; it changes nothing
    computed and counts `attn_latent_q_lora` once a lowering.
    """
    q = ctx.in_(op, "Q")
    k = ctx.in_(op, "K")
    v = ctx.in_(op, "V")
    bias = ctx.in_(op, "KeyBias")
    q_norm, k_norm = ctx.in_(op, "QNorm"), ctx.in_(op, "KNorm")
    norm_eps = float(op.attr("qk_norm_epsilon", 1e-5))
    rope_theta = float(op.attr("rope_theta", 0.0) or 0.0)
    rope_scaling = rope_scaling_attr(op, "rope_scaling")
    causal = op.attr("causal", False)
    dropout = float(op.attr("attn_dropout", 0.0))
    is_test = op.attr("is_test", False) or ctx.is_test
    sm_scale = op.attr("sm_scale", 0.0) or None
    layout = op.attr("layout", "bhsd") or "bhsd"
    bshd = layout == "bshd"
    window = int(op.attr("window", 0) or 0)
    h_ax = 2 if bshd else 1
    group = q.shape[h_ax] // k.shape[h_ax]
    if window and not causal:
        raise ValueError("fused_multihead_attention: a window needs causal")
    if (q_norm is None) != (k_norm is None):
        raise ValueError(
            "fused_multihead_attention: QNorm and KNorm come together")
    if rope_theta and (q_norm is None or not bshd):
        raise ValueError(
            "fused_multihead_attention: rope_theta needs QNorm and KNorm, "
            "and layout \"bshd\", whose axis 1 the positions count")
    if rope_scaling and not rope_theta:
        raise ValueError(
            "fused_multihead_attention: rope_scaling needs rope_theta")

    prepare = q_norm is not None
    if prepare:
        raw = q, k, v

        def prepared():
            """q and k as the ops `rms_norm` and `rotary_embedding` leave
            them, then in the attention's dtype."""
            q, k, _ = raw
            q = rms_norm(q, q_norm, norm_eps, 3)
            k = rms_norm(k, k_norm, norm_eps, 3)
            if rope_theta:
                q = rotate_half(q, rope_theta, rope_scaling)
                k = rotate_half(k, rope_theta, rope_scaling)
            return ctx.amp_cast(op, q, k)

    q, k, v = ctx.amp_cast(op, q, k, v)
    if bias is not None:
        bias = jnp.asarray(bias, jnp.float32)

    if is_test:
        dropout = 0.0
    rng = ctx.rng_for(op.output("Out")[0]) if dropout > 0.0 else None

    def attend(q, k, v, bias, rng, shards):
        # `shards` is on_mesh.batch_shards' answer. The Pallas kernels are
        # custom calls GSPMD cannot partition, so on a mesh of several
        # devices only what runs per shard of the batch is a kernel, and
        # that is mha_short; everything else there takes the XLA
        # formulation, which shards by propagation like the rest of the
        # graph. Past the HBM knee where flash wins, sequence parallelism
        # (PADDLE_TPU_SP_MODE / the ring_min_seq auto-default) takes over
        # instead.
        dv = v.shape[-1]
        plain = dv == q.shape[-1] and group == 1 and not window
        path = (_attn_dispatch(q, k, bshd, shards, plain)
                if shards else "xla")
        if shards > 1 and path != "short":
            path = "xla"
        profiler.bump_counter(f"attn_dispatch_{path}")
        if path == "flash" and window:
            profiler.bump_counter("attn_dispatch_flash_window")
        profiler.set_counter("attn_kv_group", group)
        if rope_scaling:
            profiler.bump_counter("attn_rope_scaled")
        if op.attr("q_lora_rank", 0):
            profiler.bump_counter("attn_latent_q_lora")
        fused = (prepare and path == "flash" and bshd
                 and qk_prep_viable(q.shape[-1], dv))
        if prepare and not fused:
            q, k = prepared()
        if path == "short":
            # [b, s, nh, dh] back to the [b, s, nh*dh] the projection
            # wrote: XLA folds this with the Program's reshape2 into nothing
            b, sq, nh, dh = q.shape
            if shards > 1:
                profiler.bump_counter("pallas_on_mesh_calls")
            out = mha_short(
                q.reshape(b, sq, nh * dh), k.reshape(b, -1, nh * dh),
                v.reshape(b, -1, nh * dh), nh, bias=bias, causal=causal,
                sm_scale=sm_scale, dropout=dropout, rng_key=rng,
                mesh=mesh,
            )
            return out.reshape(b, sq, nh, dh)
        if path == "xla":
            scale = sm_scale or 1.0 / float(np.sqrt(q.shape[-1]))
            return _xla_attention(q, k, v, bias, causal, scale, dropout,
                                  rng, layout=layout, window=window)
        def swap(t):  # bshd <-> bhsd; the flash kernel is head-major
            return jnp.transpose(t, (0, 2, 1, 3)) if bshd else t

        if fused:
            # from the arrays as they came: the kernel norms and rotates
            # in float32 and writes the attention's dtype, head-major
            profiler.bump_counter("attn_qk_prep_fused")
            return swap(flash_attention(
                *qk_prep(*raw, q_norm, k_norm, epsilon=norm_eps,
                         theta=rope_theta, scaling=rope_scaling,
                         out_dtype=q.dtype),
                bias=bias, causal=causal, sm_scale=sm_scale, dropout=dropout,
                rng_key=rng, window=window))
        # values narrower than the keys: the kernel takes them at their own
        # width in whole lanes, and so writes the output
        return swap(flash_attention(
            swap(q), swap(k), swap(v), bias=bias, causal=causal,
            sm_scale=sm_scale, dropout=dropout, rng_key=rng, window=window,
        ))

    mesh = ctx.mesh
    model_n = (
        mesh.shape.get("model", 1)
        if mesh is not None and mesh.devices.size > 1 else 1
    )
    seq_axis = 1 if bshd else 2
    # sequence parallelism: explicit PADDLE_TPU_SP_MODE wins; with the
    # env UNSET, the dispatch table's ring_min_seq makes ring the
    # DEFAULT above the memory knee (s >= 4096: the [s, s/n] chunk pair
    # is the only thing keeping long context on-chip — see the
    # longseq_study mesh table). Below the knee the axis stays pure
    # tensor/expert parallelism: a TP-only workload must not be
    # silently rerouted through the chunked ring (different fp32
    # accumulation order / chunk-pair dropout seeds than plain
    # attention). PADDLE_TPU_SP_MODE=off disables the auto-default.
    sp_raw = os.environ.get("PADDLE_TPU_SP_MODE")
    sp_mode = (sp_raw or "").strip().lower()
    if sp_mode in ("off", "none", "0"):
        sp_mode = ""
        sp_raw = ""  # explicit off: no auto-default either
    if sp_mode and sp_mode not in ("ring", "ulysses"):
        raise ValueError(
            f"PADDLE_TPU_SP_MODE={sp_mode!r}: expected 'ring', "
            "'ulysses' or 'off'"
        )
    if (
        sp_raw is None
        and model_n > 1
        # a forced PADDLE_TPU_ATTN_DISPATCH=xla means "plain XLA
        # attention, no Pallas anywhere" — it must suppress the ring
        # AUTO-default too (an explicit PADDLE_TPU_SP_MODE=ring is its
        # own explicit opt-in and still wins)
        and os.environ.get("PADDLE_TPU_ATTN_DISPATCH", "auto")
        .strip().lower() != "xla"
        and q.shape[seq_axis] >= int(
            attn_dispatch_thresholds()["ring_min_seq"])
        and q.shape[seq_axis] % model_n == 0
        and k.shape[seq_axis] % model_n == 0
    ):
        sp_mode = "ring"
        _logger.info(
            "attention dispatch: seq %d >= ring_min_seq %d on a "
            "model-axis-%d mesh — defaulting to ring sequence "
            "parallelism (PADDLE_TPU_SP_MODE=off to disable)",
            q.shape[seq_axis],
            int(attn_dispatch_thresholds()["ring_min_seq"]), model_n,
        )
    if sp_mode and model_n > 1 and (
        q.shape[seq_axis] % model_n or k.shape[seq_axis] % model_n
    ):
        # the user explicitly asked for sequence parallelism: an
        # indivisible sequence is a configuration error, not a silent
        # fallback (the legacy sp-axis contract)
        raise ValueError(
            f"sequence length {q.shape[seq_axis]}/{k.shape[seq_axis]} "
            f"not divisible by the model axis ({model_n}) — pad the "
            "sequence or resize the mesh for "
            f"PADDLE_TPU_SP_MODE={sp_mode}"
        )
    if sp_mode and model_n > 1 and (window or group != 1):
        raise ValueError(
            "fused_multihead_attention: ring and ulysses sequence "
            "parallelism take neither a window nor grouped key/value heads")
    if sp_mode and model_n > 1:
        if prepare:
            q, k = prepared()
        # sequence parallelism over the unified mesh's 'model' axis: the
        # attention runs on GLOBAL arrays and GSPMD places the
        # collectives (the legacy version hand-wrote them under
        # shard-map). Two formulations, env-selected:
        #   ring    — blocked chunk merge (ops/pallas/ring_attention);
        #             sequence stays sharded, chunk accesses lower to the
        #             ICI ring.
        #   ulysses — sharding-constraint flips seq<->heads
        #             (parallel/ulysses.py); GSPMD emits the all-to-alls.
        # ring/ulysses kernels are bhsd-native: global-array transposes
        # are layout changes XLA folds into the sharded matmuls
        def _to_bhsd(t):
            return jnp.transpose(t, (0, 2, 1, 3)) if bshd else t

        def _from_bhsd(t):
            return jnp.transpose(t, (0, 2, 1, 3)) if bshd else t

        if sp_mode == "ulysses":
            from ..parallel.ulysses import ulysses_attention

            profiler.bump_counter("attn_dispatch_ulysses")
            out = _from_bhsd(ulysses_attention(
                _to_bhsd(q), _to_bhsd(k), _to_bhsd(v), "model",
                axis_size=model_n, bias=bias, causal=causal,
                sm_scale=sm_scale, dropout=dropout, rng_key=rng,
                mesh=mesh,
            ))
        else:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from .pallas.ring_attention import ring_attention

            profiler.bump_counter("attn_dispatch_ring")
            # PIN the sequence dim onto 'model' (and the output back):
            # ring SP's O(s/n) per-device memory depends on the sequence
            # actually being sharded — propagation from batch-sharded
            # feeds alone is free to replicate it (the legacy manual
            # in_specs guaranteed this; the constraint is its GSPMD form)
            seq_sh = NamedSharding(mesh, P("batch", None, "model", None))

            def _pin(t):
                return jax.lax.with_sharding_constraint(t, seq_sh)

            qr, kr, vr = _pin(_to_bhsd(q)), _pin(_to_bhsd(k)), \
                _pin(_to_bhsd(v))
            if bias is not None:
                bias = jax.lax.with_sharding_constraint(
                    bias, NamedSharding(mesh, P("batch", "model")))
            out = _from_bhsd(_pin(ring_attention(
                qr, kr, vr, "model",
                axis_size=model_n, bias=bias, causal=causal,
                sm_scale=sm_scale, dropout=dropout, rng_key=rng,
            ).astype(q.dtype)))
    else:
        # head ('model') parallelism needs no special handling: the XLA
        # lowering is plain traced code, so GSPMD partitions it from the
        # feed/param shardings. Pure batch parallelism needs no
        # partitioning at all: each chip's rows are a whole attention
        # problem, so the kernel runs per shard of 'batch'
        out = attend(q, k, v, bias, rng,
                     shards=on_mesh.batch_shards(mesh, q.shape[0], k.shape[0]))
    ctx.out(op, "Out", out)
