"""Flash attention as a Pallas TPU kernel (fwd + custom-vjp bwd).

TPU-native replacement for the reference's unfused attention math
(matmul -> softmax .cu kernel -> matmul; e.g. paddle/fluid/operators/
softmax_op.cu + matmul_op; Fluid has no fused attention at this vintage) —
designed MXU/VMEM-first instead: blocked online-softmax so the [s, s]
score matrix never hits HBM, fp32 accumulation, optional in-kernel
dropout regenerated (not stored) in the backward pass.

Layout: q [b, h, sq, d], k/v [b, h, sk, d], optional additive key bias
[b, sk] (the padding-mask case), `causal` flag. Head dim is zero-padded
to a lane multiple (128); sequence dims are padded to block multiples
with fully-masked keys.

Mosaic compiles the kernel on a TPU. PADDLE_TPU_PALLAS_INTERPRET=1 runs
it in the Pallas interpreter so CPU tests exercise the real kernel body;
asked for on any other backend without that variable, it raises.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANE = 128


def _interpret():
    """Interpreter mode is the environment variable and nothing else."""
    return bool(os.environ.get("PADDLE_TPU_PALLAS_INTERPRET"))


def _use_pallas():
    """True where a Pallas kernel can run at all: compiled by Mosaic on a
    TPU, or interpreted. Dispatch reads this to choose a path."""
    return _interpret() or jax.default_backend() == "tpu"


def require_pallas(kernel: str) -> None:
    """A kernel called on a backend that cannot compile it raises; it is
    never swapped for other math behind the caller's back."""
    if not _use_pallas():
        raise RuntimeError(
            f"Pallas kernel {kernel!r} was asked for on the "
            f"{jax.default_backend()!r} backend: Mosaic compiles only for "
            "a TPU (PADDLE_TPU_PALLAS_INTERPRET=1 runs the interpreter)"
        )


def _ceil_to(x, m):
    return (x + m - 1) // m * m


def _dropout_keep(seed, bh_idx, q0, k0, shape, dropout):
    """Stateless keep-mask: a murmur-style integer hash of the *global*
    (batch*head, q index, k index, seed) coordinates, so the identical mask
    is regenerated in the backward kernels (never stored to HBM) and is
    independent of block-size choices. Portable across TPU and the
    interpreter, unlike pltpu.prng_*."""
    u32 = lambda x: jax.lax.convert_element_type(x, jnp.uint32)
    qi = u32(q0) + jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    ki = u32(k0) + jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    h = (
        qi * jnp.uint32(0x9E3779B1)
        ^ ki * jnp.uint32(0x85EBCA6B)
        ^ (u32(seed) + u32(bh_idx) * jnp.uint32(0xC2B2AE35))
    )
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    thresh = jnp.uint32(min(int(dropout * 2**32), 2**32 - 1))
    return h >= thresh


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    seed_ref,
    q_ref,
    k_ref,
    v_ref,
    bias_ref,
    o_ref,
    lse_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    sm_scale,
    causal,
    causal_offset,
    dropout,
    block_q,
    block_k,
    nk,
):
    j = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # dots run in the input dtype (bf16 on the MXU) accumulating fp32;
    # only the softmax math stays fp32
    q = q_ref[0]
    k = k_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    s = s * sm_scale
    if bias_ref is not None:
        s = s + bias_ref[0, 0].astype(jnp.float32)[None, :]
    if causal:
        # bottom-right aligned: query row qi sees keys up to qi + offset
        qi = j * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        ki = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(qi + causal_offset >= ki, s, NEG_INF)

    m_prev = m_scr[:, :1]
    l_prev = l_scr[:, :1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)

    if dropout > 0.0:
        keep = _dropout_keep(
            seed_ref[0], pl.program_id(0), j * block_q, kb * block_k,
            p.shape, dropout,
        )
        p_use = jnp.where(keep, p / (1.0 - dropout), 0.0)
    else:
        p_use = p

    v = v_ref[0]
    acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
        p_use.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(kb == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[:, 0] + jnp.log(l_safe[:, 0])).astype(jnp.float32)


def _fwd_pallas(q, k, v, bias, seed, h, *, sm_scale, causal, causal_offset, dropout, block_q, block_k):
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq, nk = sq // block_q, sk // block_k

    bias_spec = []
    bias_args = []
    if bias is not None:
        # bias is [bh, 1, sk]: 3-D so the block's trailing dims obey the
        # (8, 128) tiling rule (middle dim 1 == array dim)
        bias_spec = [
            pl.BlockSpec(
                (1, 1, block_k), lambda i, j, kb: (i // h, 0, kb),
                memory_space=pltpu.VMEM,
            )
        ]
        bias_args = [bias]

    kernel = functools.partial(
        _fwd_kernel if bias is not None else _fwd_kernel_nobias,
        sm_scale=sm_scale,
        causal=causal,
        causal_offset=causal_offset,
        dropout=dropout,
        block_q=block_q,
        block_k=block_k,
        nk=nk,
    )

    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # seed
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda i, j, kb: (i, kb, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda i, j, kb: (i, kb, 0), memory_space=pltpu.VMEM),
            *bias_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), lambda i, j, kb: (i, 0, j), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, LANE), jnp.float32),
            pltpu.VMEM((block_q, LANE), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_fwd",
    )(seed, q, k, v, *bias_args)
    return out, lse


def _fwd_kernel_nobias(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *scr, **kw):
    _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, None, o_ref, lse_ref, *scr, **kw)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    seed_ref,
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    bias_ref,
    dq_ref,
    dq_scr,
    *,
    sm_scale,
    causal,
    causal_offset,
    dropout,
    block_q,
    block_k,
    nk,
):
    j = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, 0].astype(jnp.float32)[:, None]
    delta = delta_ref[0, 0].astype(jnp.float32)[:, None]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale
    if bias_ref is not None:
        s = s + bias_ref[0, 0].astype(jnp.float32)[None, :]
    if causal:
        qi = j * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        ki = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(qi + causal_offset >= ki, s, NEG_INF)
    p = jnp.exp(s - lse)  # normalized probs (fp32)

    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    if dropout > 0.0:
        keep = _dropout_keep(
            seed_ref[0], pl.program_id(0), j * block_q, kb * block_k,
            dp.shape, dropout,
        )
        dp = jnp.where(keep, dp / (1.0 - dropout), 0.0)
    ds = p * (dp - delta) * sm_scale
    dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(kb == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dq_nobias(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *scr, **kw):
    _bwd_dq_kernel(
        seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, None, dq_ref, *scr, **kw
    )


def _bwd_dkv_kernel(
    seed_ref,
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    bias_ref,
    dk_ref,
    dv_ref,
    dk_scr,
    dv_scr,
    *,
    sm_scale,
    causal,
    causal_offset,
    dropout,
    block_q,
    block_k,
    nq,
):
    kb = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, 0].astype(jnp.float32)[:, None]
    delta = delta_ref[0, 0].astype(jnp.float32)[:, None]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale
    if bias_ref is not None:
        s = s + bias_ref[0, 0].astype(jnp.float32)[None, :]
    if causal:
        qi = j * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        ki = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(qi + causal_offset >= ki, s, NEG_INF)
    p = jnp.exp(s - lse)  # [bq, bk]

    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    if dropout > 0.0:
        keep = _dropout_keep(
            seed_ref[0], pl.program_id(0), j * block_q, kb * block_k,
            p.shape, dropout,
        )
        p_drop = jnp.where(keep, p / (1.0 - dropout), 0.0)
        dp = jnp.where(keep, dp / (1.0 - dropout), 0.0)
    else:
        p_drop = p
    dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
        p_drop.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta) * sm_scale
    dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(j == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dkv_nobias(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *scr, **kw):
    _bwd_dkv_kernel(
        seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, None, dk_ref, dv_ref, *scr, **kw
    )


def _bwd_pallas(q, k, v, bias, seed, out, lse, do, h, *, sm_scale, causal, causal_offset, dropout, block_q, block_k, delta=None):
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq, nk = sq // block_q, sk // block_k

    if delta is None:
        delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)[:, None, :]

    common = dict(sm_scale=sm_scale, causal=causal,
                  causal_offset=causal_offset, dropout=dropout,
                  block_q=block_q, block_k=block_k)
    qspec = lambda i, j, kb: (i, j, 0)
    kspec = lambda i, j, kb: (i, kb, 0)
    rowspec = lambda i, j, kb: (i, 0, j)

    bias_in, bias_specs_q, bias_specs_k = [], [], []
    if bias is not None:
        bias_in = [bias]
        bias_specs_q = [pl.BlockSpec((1, 1, block_k), lambda i, j, kb: (i // h, 0, kb), memory_space=pltpu.VMEM)]
        bias_specs_k = [pl.BlockSpec((1, 1, block_k), lambda i, kb, j: (i // h, 0, kb), memory_space=pltpu.VMEM)]

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel if bias is not None else _bwd_dq_nobias, nk=nk, **common
        ),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), qspec, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), kspec, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), kspec, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, d), qspec, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), rowspec, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), rowspec, memory_space=pltpu.VMEM),
            *bias_specs_q,
        ],
        out_specs=pl.BlockSpec((1, block_q, d), qspec, memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(seed, q, k, v, do, lse, delta, *bias_in)

    kq = lambda i, kb, j: (i, j, 0)
    kk = lambda i, kb, j: (i, kb, 0)
    krow = lambda i, kb, j: (i, 0, j)
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel if bias is not None else _bwd_dkv_nobias, nq=nq, **common
        ),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), kq, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), kk, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), kk, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, d), kq, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), krow, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), krow, memory_space=pltpu.VMEM),
            *bias_specs_k,
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), kk, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), kk, memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(seed, q, k, v, do, lse, delta, *bias_in)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry: custom_vjp over padded/flattened layout
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash_core(q, k, v, bias, seed, h, sm_scale, causal, causal_offset,
                dropout, block_q, block_k):
    out, _ = _fwd_pallas(
        q, k, v, bias, seed, h,
        sm_scale=sm_scale, causal=causal, causal_offset=causal_offset,
        dropout=dropout, block_q=block_q, block_k=block_k,
    )
    return out


def _flash_core_fwd(q, k, v, bias, seed, h, sm_scale, causal, causal_offset,
                    dropout, block_q, block_k):
    out, lse = _fwd_pallas(
        q, k, v, bias, seed, h,
        sm_scale=sm_scale, causal=causal, causal_offset=causal_offset,
        dropout=dropout, block_q=block_q, block_k=block_k,
    )
    return out, (q, k, v, bias, seed, out, lse)


def _flash_core_bwd(h, sm_scale, causal, causal_offset, dropout, block_q,
                    block_k, res, do):
    q, k, v, bias, seed, out, lse = res
    dq, dk, dv = _bwd_pallas(
        q, k, v, bias, seed, out, lse, do, h,
        sm_scale=sm_scale, causal=causal, causal_offset=causal_offset,
        dropout=dropout, block_q=block_q, block_k=block_k,
    )
    dbias = None if bias is None else jnp.zeros_like(bias)
    dseed = np.zeros((1,), dtype=jax.dtypes.float0)
    return dq, dk, dv, dbias, dseed


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _pad_inputs(q, k, v, bias, block_q, block_k):
    """Flatten [b, h, s, d] -> [b*h, s_p, d_p] with lane/sublane padding for
    the kernels: block sizes sublane-aligned (16 covers bf16's (16, 128) min
    tile), head dim padded to a lane multiple, sequence dims padded to block
    multiples with padded keys masked via NEG_INF bias. Shared by the flash
    and ring entry points so their layouts (and dropout-mask coordinates)
    stay bit-compatible. Returns (qf, kf, vf, biasf, bq, bk); biasf is
    [b, 1, sk_p] or None."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = min(block_q or 512, _ceil_to(max(LANE, sq), 16))
    bk = min(block_k or 512, _ceil_to(max(LANE, sk), 16))
    bq, bk = _ceil_to(bq, 16), _ceil_to(bk, 16)
    sq_p, sk_p, d_p = _ceil_to(sq, bq), _ceil_to(sk, bk), _ceil_to(d, LANE)

    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    if d_p != d:
        pad = [(0, 0), (0, 0), (0, d_p - d)]
        qf, kf, vf = (jnp.pad(x, pad) for x in (qf, kf, vf))
    if sq_p != sq:
        qf = jnp.pad(qf, [(0, 0), (0, sq_p - sq), (0, 0)])
    biasf = bias
    if sk_p != sk:
        kf = jnp.pad(kf, [(0, 0), (0, sk_p - sk), (0, 0)])
        vf = jnp.pad(vf, [(0, 0), (0, sk_p - sk), (0, 0)])
        if biasf is None:
            biasf = jnp.zeros((b, sk), jnp.float32)
        biasf = jnp.pad(biasf, [(0, 0), (0, sk_p - sk)], constant_values=NEG_INF)
    if biasf is not None:
        # [b, 1, sk]: kernels map the batch*head grid index back to the
        # batch row (i // h) — no h-fold HBM duplication
        biasf = biasf.astype(jnp.float32)[:, None, :]
    return qf, kf, vf, biasf, bq, bk


def _attention_unfused(q, k, v, bias, causal, sm_scale, dropout, rng_key,
                       f32_residuals, layout="bhsd"):
    """One implementation of the plain-XLA attention semantics (bias /
    bottom-right-aligned causal mask / murmur-hash dropout — the contract
    the Pallas kernels are validated against), with the dtype discipline
    parameterized:

    f32_residuals=True — the all-f32 gold (_reference_attention): scores
    and probs live in f32, maximally accurate for kernel tests.
    f32_residuals=False — the production below-cutover fallback
    (_xla_attention): scores/probs live in the INPUT dtype on HBM, only
    the softmax interior upcasts. Measured on BERT b=256 s=128 (v5e):
    the f32 discipline costs ~5% end-to-end (186.3-188.1k vs 195.1-198.4k
    tok/s) — f32 score/prob tensors double the HBM bytes and are saved
    as f32 residuals by the auto-vjp (the round-2 BN/LN lesson); casting
    only the probs@V input recovered nothing, the bytes/residual effect
    dominates.

    layout="bshd": q/k/v arrive [b, s, h, d] (the shape the model's QKV
    reshape produces) and the head axis is routed through dot_general
    BATCH dims instead of an explicit [b, h, s, d] transpose — the
    round-4 xplane showed those transposes materialize as ~0.15 ms HBM
    relayout copies per q/k/v per layer on BERT (and 26% of device time
    on Transformer-base)."""
    if layout == "bshd":
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    else:
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k)
    if f32_residuals:
        s = s.astype(jnp.float32)
    sf = (s * jnp.asarray(sm_scale, s.dtype)).astype(jnp.float32)
    if bias is not None:
        sf = sf + bias[:, None, None, :].astype(jnp.float32)
    if causal:
        sq, sk = sf.shape[-2], sf.shape[-1]
        mask = np.tril(np.ones((sq, sk), np.bool_), k=sk - sq)
        sf = jnp.where(mask, sf, NEG_INF)
    p = jax.nn.softmax(sf, axis=-1)
    if not f32_residuals:
        p = p.astype(q.dtype)
    if dropout > 0.0:
        # murmur counter-hash mask, 2^-32 keep-prob granularity (see
        # nn_ops._dropout_keep_mask)
        from ..nn_ops import _dropout_keep_mask

        keep, keep_prob = _dropout_keep_mask(rng_key, dropout, p.shape)
        p = jnp.where(keep, p / jnp.asarray(keep_prob, p.dtype),
                      jnp.zeros((), p.dtype))
    if layout == "bshd":
        out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(p.dtype))
    else:
        out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(p.dtype))
    return out.astype(q.dtype)


def _reference_attention(q, k, v, bias, causal, sm_scale, dropout, rng_key):
    """All-f32 gold (CPU tests / kernel validation / ragged shapes)."""
    return _attention_unfused(q, k, v, bias, causal, sm_scale, dropout,
                              rng_key, f32_residuals=True)


def _xla_attention(q, k, v, bias, causal, sm_scale, dropout, rng_key,
                   layout="bhsd"):
    """Production below-cutover fallback: input-dtype HBM discipline."""
    return _attention_unfused(q, k, v, bias, causal, sm_scale, dropout,
                              rng_key, f32_residuals=False, layout=layout)


def flash_attention(
    q,
    k,
    v,
    bias=None,
    causal=False,
    sm_scale=None,
    dropout=0.0,
    rng_key=None,
    block_q=None,
    block_k=None,
):
    """Fused multi-head attention.

    q: [b, h, sq, d]; k, v: [b, h, sk, d]; bias: additive key bias [b, sk]
    (0 keep / -inf drop) or None. Returns [b, h, sq, d] in q's dtype.
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))

    require_pallas("flash_attention")
    if dropout > 0.0 and rng_key is None:
        raise ValueError("dropout requires rng_key")
    if dropout > 0.0:
        seed = jax.random.randint(
            rng_key, (1,), 0, np.iinfo(np.int32).max, jnp.int32
        )
    else:
        seed = jnp.zeros((1,), jnp.int32)

    # bottom-right-aligned causal offset in ORIGINAL coords (matches the
    # XLA reference path when sq != sk); padding doesn't shift it because
    # padded q rows are sliced away and padded keys are bias-masked
    causal_offset = sk - sq
    qf, kf, vf, biasf, bq, bk = _pad_inputs(q, k, v, bias, block_q, block_k)

    out = _flash_core(
        qf, kf, vf, biasf, seed, h, sm_scale, causal, causal_offset,
        float(dropout), bq, bk,
    )
    out = out[:, :sq, :d].reshape(b, h, sq, d)
    return out
