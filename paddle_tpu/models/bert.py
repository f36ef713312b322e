"""BERT-base pretrain model — the flagship workload (the benchmark's
bert_base cells: examples/sec/chip). Built entirely through the framework's
layers API; tensor-parallel PartitionSpecs annotate attention/FFN weights
along "tp" (Megatron-style column→row split), consumed by the GSPMD compile
path. Reference capability: the fleet-collective BERT config (SURVEY.md §3.3);
TP itself is a new first-class capability (SURVEY.md §2.8)."""

from __future__ import annotations

import math

from jax.sharding import PartitionSpec as P

from .. import layers
from ..framework import default_main_program
from ..initializer import Constant, Normal, TruncatedNormal
from ..param_attr import ParamAttr
from ..parallel import shard_parameter

__all__ = ["BertConfig", "build_bert_pretrain", "bert_encoder"]


class BertConfig:
    def __init__(
        self,
        vocab_size=30522,
        hidden_size=768,
        num_layers=12,
        num_heads=12,
        intermediate_size=3072,
        max_position=512,
        type_vocab_size=2,
        hidden_dropout=0.1,
        attention_dropout=0.1,
        initializer_range=0.02,
        use_flash_attention=True,
        recompute=False,
        tie_mlm_weights=True,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size
        self.max_position = max_position
        self.type_vocab_size = type_vocab_size
        self.hidden_dropout = hidden_dropout
        self.attention_dropout = attention_dropout
        self.initializer_range = initializer_range
        self.use_flash_attention = use_flash_attention
        # tie the MLM output projection to the word embedding (the
        # reference Paddle BERT/LARK pretrain head does matmul with the
        # embedding table transposed — halves the vocab-sized params and
        # removes one [h, V] Adam update per step)
        self.tie_mlm_weights = tie_mlm_weights
        self.recompute = recompute

    @staticmethod
    def base():
        return BertConfig()

    @staticmethod
    def tiny():
        """for tests / dry runs"""
        return BertConfig(
            vocab_size=128,
            hidden_size=64,
            num_layers=2,
            num_heads=4,
            intermediate_size=128,
            max_position=64,
        )


def _fc(x, size, name, cfg, act=None, num_flatten_dims=2, tp_spec=None,
        bias_tp=None):
    init = TruncatedNormal(0.0, cfg.initializer_range)
    out = layers.fc(
        x,
        size,
        num_flatten_dims=num_flatten_dims,
        act=act,
        param_attr=ParamAttr(name=name + ".w_0", initializer=init),
        bias_attr=ParamAttr(name=name + ".b_0", initializer=Constant(0.0)),
    )
    prog = default_main_program()
    if tp_spec is not None:
        shard_parameter(prog, name + ".w_0", tp_spec)
        if bias_tp is not None:
            shard_parameter(prog, name + ".b_0", bias_tp)
    return out


def _attention(x, attn_bias, cfg, name, is_test=False):
    """Multi-head self-attention; qkv column-parallel, output row-parallel."""
    b, s, h = x.shape
    nh = cfg.num_heads
    dh = cfg.hidden_size // nh
    q = _fc(x, cfg.hidden_size, name + ".q", cfg,
            tp_spec=P(None, "tp"), bias_tp=P("tp"))
    k = _fc(x, cfg.hidden_size, name + ".k", cfg,
            tp_spec=P(None, "tp"), bias_tp=P("tp"))
    v = _fc(x, cfg.hidden_size, name + ".v", cfg,
            tp_spec=P(None, "tp"), bias_tp=P("tp"))

    if cfg.use_flash_attention:
        # bshd layout: the fused op consumes the head-split RESHAPE
        # directly, so the graph has zero head transposes — the round-4
        # xplane showed each [b,s,h,d] transpose materializes as an HBM
        # relayout copy (~0.15 ms x 3 tensors x 12 layers on BERT-base)
        qh = layers.reshape(q, [b, s, nh, dh])
        kh = layers.reshape(k, [b, s, nh, dh])
        vh = layers.reshape(v, [b, s, nh, dh])
        # one Pallas kernel: scores/softmax/dropout never hit HBM
        ctxv = layers.fused_multihead_attention(
            qh, kh, vh, key_bias=attn_bias, sm_scale=1.0 / math.sqrt(dh),
            attn_dropout=cfg.attention_dropout if not is_test else 0.0,
            is_test=is_test, layout="bshd",
        )
        merged = layers.reshape(ctxv, [b, s, h])
    else:
        def heads(t):
            r = layers.reshape(t, [b, s, nh, dh])
            return layers.transpose(r, [0, 2, 1, 3])  # [b, nh, s, dh]

        qh, kh, vh = heads(q), heads(k), heads(v)
        scores = layers.matmul(qh, kh, transpose_y=True,
                               alpha=1.0 / math.sqrt(dh))
        if attn_bias is not None:
            scores = layers.elementwise_add(scores, attn_bias)
        probs = layers.softmax(scores)
        if cfg.attention_dropout and not is_test:
            probs = layers.dropout(
                probs, cfg.attention_dropout,
                dropout_implementation="upscale_in_train", is_test=is_test,
            )
        ctxv = layers.matmul(probs, vh)  # [b, nh, s, dh]
        merged = layers.reshape(
            layers.transpose(ctxv, [0, 2, 1, 3]), [b, s, h])
    return _fc(merged, cfg.hidden_size, name + ".out", cfg,
               tp_spec=P("tp", None))


def _encoder_layer(x, attn_bias, cfg, name, is_test=False):
    attn = _attention(x, attn_bias, cfg, name + ".attn", is_test)
    if cfg.hidden_dropout and not is_test:
        attn = layers.dropout(
            attn, cfg.hidden_dropout,
            dropout_implementation="upscale_in_train", is_test=is_test,
        )
    x = layers.layer_norm(
        layers.elementwise_add(x, attn), begin_norm_axis=2,
        name=name + ".ln1",
    )
    ffn1 = _fc(x, cfg.intermediate_size, name + ".ffn1", cfg,
               act={"type": "gelu", "approximate": True},
               tp_spec=P(None, "tp"), bias_tp=P("tp"))
    ffn2 = _fc(ffn1, cfg.hidden_size, name + ".ffn2", cfg,
               tp_spec=P("tp", None))
    if cfg.hidden_dropout and not is_test:
        ffn2 = layers.dropout(
            ffn2, cfg.hidden_dropout,
            dropout_implementation="upscale_in_train", is_test=is_test,
        )
    return layers.layer_norm(
        layers.elementwise_add(x, ffn2), begin_norm_axis=2,
        name=name + ".ln2",
    )


def bert_encoder(input_ids, segment_ids, position_ids, input_mask, cfg,
                 is_test=False, pp_stages=1):
    """Returns final hidden states [b, s, h]. With pp_stages > 1 the
    embedding lives on stage 0 and encoder layers are tagged with
    device_guard stages (reference: fluid.device_guard pipeline cuts) for
    the Program-pipeline executor path."""
    import contextlib as _ctx

    from ..framework import device_guard

    def stage_of_layer(i):
        return min(i * pp_stages // max(cfg.num_layers, 1), pp_stages - 1)

    def stage_guard(s):
        return device_guard(f"gpu:{s}") if pp_stages > 1 \
            else _ctx.nullcontext()

    init = TruncatedNormal(0.0, cfg.initializer_range)
    with stage_guard(0):
        emb, attn_bias = _bert_embedding(
            input_ids, segment_ids, position_ids, input_mask, cfg,
            is_test, init,
        )
    x = emb
    import contextlib

    from ..framework import recompute_scope

    for i in range(cfg.num_layers):
        # one remat segment per encoder layer under RecomputeOptimizer
        scope = (recompute_scope(i) if cfg.recompute
                 else contextlib.nullcontext())
        with scope, stage_guard(stage_of_layer(i)):
            x = _encoder_layer(x, attn_bias, cfg, f"bert.layer{i}", is_test)
    return x


def _bert_embedding(input_ids, segment_ids, position_ids, input_mask, cfg,
                    is_test, init):
    word_emb = layers.embedding(
        input_ids, (cfg.vocab_size, cfg.hidden_size),
        param_attr=ParamAttr(name="bert.word_emb", initializer=init),
    )
    pos_emb = layers.embedding(
        position_ids, (cfg.max_position, cfg.hidden_size),
        param_attr=ParamAttr(name="bert.pos_emb", initializer=init),
    )
    seg_emb = layers.embedding(
        segment_ids, (cfg.type_vocab_size, cfg.hidden_size),
        param_attr=ParamAttr(name="bert.seg_emb", initializer=init),
    )
    emb = layers.elementwise_add(
        layers.elementwise_add(word_emb, pos_emb), seg_emb
    )
    emb = layers.layer_norm(emb, begin_norm_axis=2, name="bert.emb_ln")
    if cfg.hidden_dropout and not is_test:
        emb = layers.dropout(
            emb, cfg.hidden_dropout,
            dropout_implementation="upscale_in_train", is_test=is_test,
        )
    # additive attention bias from the [b, s] mask: 0 keep, -1e4 drop
    b, s = input_ids.shape[0], input_ids.shape[1]
    if cfg.use_flash_attention:
        # flash path takes the key bias as [b, s] directly
        attn_bias = layers.scale(input_mask, scale=1e4, bias=-1.0,
                                 bias_after_scale=False)
    else:
        mask2 = layers.reshape(input_mask, [b, 1, 1, s])
        # (mask - 1) * 1e4 : 0 for keep, -1e4 for pad
        attn_bias = layers.scale(mask2, scale=1e4, bias=-1.0,
                                 bias_after_scale=False)
    return emb, attn_bias


def tied_logits(x, table_name, vocab_size, bias_name):
    """Weight-tied vocab projection: logits = x @ table^T + b, reusing an
    existing embedding parameter transposed (the reference LARK/BERT head
    and the Fluid transformer's weight_sharing) — no separate [h, V]
    parameter, optimizer state, or update pass."""
    from ..framework import default_main_program
    from ..layer_helper import LayerHelper

    table = default_main_program().global_block().var(table_name)
    logits = layers.matmul(x, table, transpose_y=True)
    helper = LayerHelper(bias_name.replace(".", "_"))
    bias = helper.create_parameter(
        ParamAttr(name=bias_name), [vocab_size],
        dtype="float32", is_bias=True,
    )
    return layers.elementwise_add(logits, bias)


def _mlm_logits(trans, cfg, num_flatten_dims):
    """MLM vocab projection. tie_mlm_weights=True (default, the reference
    LARK/BERT pretrain head): logits = trans @ word_emb^T + b — the
    embedding table is reused transposed, so there is no separate [h, V]
    parameter (or its optimizer state / update pass). Otherwise a plain
    fc, sharded over tp."""
    if cfg.tie_mlm_weights:
        return tied_logits(trans, "bert.word_emb", cfg.vocab_size,
                           "mlm.out_b")
    return _fc(trans, cfg.vocab_size, "mlm.out", cfg,
               num_flatten_dims=num_flatten_dims,
               tp_spec=P(None, "tp"), bias_tp=P("tp"))


def build_bert_pretrain(cfg, batch_size, seq_len, is_test=False,
                        mlm_only=False, max_preds=None, pp_stages=1):
    """Declares data vars + the MLM(+NSP) pretrain loss. Returns a dict of
    handles. Feed int ids as [b, s] int64, mask/weights float32.

    max_preds: when set (the reference BERT pretrain convention,
    max_predictions_per_seq), the MLM head gathers only the masked
    positions — feed `mask_pos` [b, max_preds] int64 PER-ROW positions in
    [0, s) plus `mask_label`/`mask_weight` of shape [b, max_preds]. This
    cuts the vocab-projection FLOPs by ~s/max_preds (the dominant head
    cost). The gather is a flat gather with RUNTIME-derived row offsets
    (exclusive cumsum of a batch-sized ones column), so PipelineOptimizer
    microbatching — which shrinks the batch dim — still indexes
    correctly. With max_preds=None the head scores every position and
    mask_label/mask_weight are [b, s] (backward-compatible)."""
    input_ids = layers.data("src_ids", [batch_size, seq_len], dtype="int64",
                            append_batch_size=False)
    segment_ids = layers.data("sent_ids", [batch_size, seq_len], dtype="int64",
                              append_batch_size=False)
    position_ids = layers.data("pos_ids", [batch_size, seq_len], dtype="int64",
                               append_batch_size=False)
    input_mask = layers.data("input_mask", [batch_size, seq_len],
                             dtype="float32", append_batch_size=False)
    lbl_shape = (
        [batch_size, max_preds] if max_preds else [batch_size, seq_len]
    )
    mlm_labels = layers.data("mask_label", lbl_shape, dtype="int64",
                             append_batch_size=False)
    mlm_weights = layers.data("mask_weight", lbl_shape,
                              dtype="float32", append_batch_size=False)
    mask_pos = None
    if max_preds:
        mask_pos = layers.data("mask_pos", [batch_size, max_preds],
                               dtype="int64", append_batch_size=False)

    hidden = bert_encoder(input_ids, segment_ids, position_ids, input_mask,
                          cfg, is_test, pp_stages=pp_stages)

    import contextlib as _ctx2

    from ..framework import device_guard as _dg

    def _build_head():
        # MLM head: transform + output projection tied-shape to vocab
        if max_preds:
            # flat gather over [b*s, h] (the fast XLA path). Row offsets are
            # derived from a runtime-batch-sized cumsum — NOT baked constants —
            # so PipelineOptimizer microbatching (which shrinks the batch dim)
            # still indexes correctly.
            ones = layers.fill_constant_batch_size_like(
                mask_pos, shape=[-1, 1], dtype="int64", value=1)
            row_id = layers.cumsum(ones, axis=0, exclusive=True)  # [b, 1]
            flat_pos = layers.reshape(
                mask_pos + row_id * seq_len, [batch_size * max_preds])
            flat = layers.reshape(
                hidden, [batch_size * seq_len, cfg.hidden_size])
            picked = layers.gather(flat, flat_pos)  # [b*P, h]
            trans = _fc(picked, cfg.hidden_size, "mlm.trans", cfg,
                        act={"type": "gelu", "approximate": True},
                        num_flatten_dims=1)
            trans = layers.layer_norm(trans, begin_norm_axis=1, name="mlm.ln")
            logits = _mlm_logits(trans, cfg, num_flatten_dims=1)
            labels2 = layers.reshape(mlm_labels, [batch_size * max_preds, 1])
            per_tok = layers.softmax_with_cross_entropy(logits, labels2)
            w = layers.reshape(mlm_weights, [batch_size * max_preds, 1])
        else:
            trans = _fc(hidden, cfg.hidden_size, "mlm.trans", cfg,
                        act={"type": "gelu", "approximate": True})
            trans = layers.layer_norm(trans, begin_norm_axis=2, name="mlm.ln")
            logits = _mlm_logits(trans, cfg, num_flatten_dims=2)
            labels3 = layers.reshape(mlm_labels, [batch_size, seq_len, 1])
            per_tok = layers.softmax_with_cross_entropy(logits, labels3)
            per_tok = layers.reshape(per_tok, [batch_size, seq_len])
            w = mlm_weights
        masked = layers.elementwise_mul(per_tok, w)
        denom = layers.reduce_sum(w)
        mlm_loss = layers.elementwise_div(
            layers.reduce_sum(masked),
            layers.elementwise_add(
                denom, layers.fill_constant([1], "float32", 1e-6)
            ),
        )

        return logits, mlm_loss

    with (_dg(f"gpu:{pp_stages - 1}") if pp_stages > 1
          else _ctx2.nullcontext()):
        logits, mlm_loss = _build_head()
    handles = {
        "feeds": ["src_ids", "sent_ids", "pos_ids", "input_mask",
                  "mask_label", "mask_weight"]
        + (["mask_pos"] if max_preds else []),
        "hidden": hidden,
        "logits": logits,
        "mlm_loss": mlm_loss,
        "loss": mlm_loss,
    }

    if not mlm_only:
        nsp_labels = layers.data("nsp_label", [batch_size, 1], dtype="int64",
                                 append_batch_size=False)
        cls = layers.slice(hidden, [1], [0], [1])  # [b, 1, h]
        cls = layers.reshape(cls, [batch_size, cfg.hidden_size])
        pooled = layers.fc(
            cls, cfg.hidden_size, act="tanh",
            param_attr=ParamAttr(name="pooler.w_0",
                                 initializer=TruncatedNormal(0.0, 0.02)),
            bias_attr=ParamAttr(name="pooler.b_0",
                                initializer=Constant(0.0)),
        )
        nsp_logits = layers.fc(
            pooled, 2,
            param_attr=ParamAttr(name="nsp.w_0",
                                 initializer=TruncatedNormal(0.0, 0.02)),
            bias_attr=ParamAttr(name="nsp.b_0", initializer=Constant(0.0)),
        )
        nsp_loss = layers.mean(
            layers.softmax_with_cross_entropy(nsp_logits, nsp_labels)
        )
        total = layers.elementwise_add(
            layers.reshape(mlm_loss, [1]), layers.reshape(nsp_loss, [1])
        )
        handles["feeds"].append("nsp_label")
        handles["nsp_loss"] = nsp_loss
        handles["loss"] = total
    return handles


def bert_flops_per_token(cfg, seq_len=None, max_preds=None) -> float:
    """Approximate train FLOPs/token (fwd+bwd ≈ 3x fwd, 2*params matmul).
    With masked-position MLM (max_preds), the vocab projection runs on only
    max_preds/seq_len of the tokens; attention score/value matmuls are
    included when seq_len is given."""
    h, l, ff, v = (cfg.hidden_size, cfg.num_layers, cfg.intermediate_size,
                   cfg.vocab_size)
    per_layer = 2 * (4 * h * h + 2 * h * ff)  # qkv+out + ffn, fwd mult-adds
    if seq_len:
        per_layer += 2 * 2 * seq_len * h  # QK^T + PV per token
    embed_out = 2 * h * v
    if max_preds and seq_len:
        embed_out = embed_out * max_preds / seq_len
    fwd = l * per_layer + embed_out
    return 3.0 * fwd
