"""BERT masked-LM pretraining: the Program through the repo's public
builder, seeded batches, FLOPs per example, and the plain reference.

The reference is written from the paper's equations (Devlin et al. 2018,
and the encoder of Vaswani et al. 2017) in float32 `jax.numpy`. It shares
nothing with `paddle_tpu`'s lowerings but the parameters' names.
Departures from the published model, all the program's own and followed
here: LayerNorm's epsilon is 1e-5 (published 1e-12); GELU is the tanh
approximation (as in the original TensorFlow code); the MLM output
projection is the word embedding transposed plus a bias (as published).
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.harness.datagen import distinct_positions, zipf_ids

MASK_ID = 103  # [MASK] in the uncased vocabulary
SCORED_SEQUENCES = 8  # the harness compares logits on this many sequences

# |program - reference| on the logits of the scored positions, over the
# reference's own root-mean-square. The program computes its matrix
# products in bf16 with float32 accumulation, which rounds each input to 8
# bits (2^-9 relative, about 0.2%); through twelve residual layers the
# chip measured 0.87-0.92% at BERT-base (PERF.md section 6). fp8 inputs,
# or a bf16 accumulator, round at 2^-4 to 2^-5 and land far outside. The loss
# is compared to 0.06 only: the program hands its loss back in bf16, whose
# neighbours between 8 and 16 lie 0.0625 apart.
TOLERANCE = {"logits_rel_rms": 0.03, "loss_abs": 0.06}


def config(model: dict):
    from paddle_tpu.models.bert import BertConfig

    return BertConfig(
        vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"],
        intermediate_size=model["intermediate_size"],
        max_position=model["max_position_embeddings"],
        type_vocab_size=model["type_vocab_size"],
        hidden_dropout=model["hidden_dropout_prob"],
        attention_dropout=model["attention_probs_dropout_prob"],
        initializer_range=model["initializer_range"],
    )


def build(model: dict, traffic: dict) -> dict:
    """Declare the pretraining program in the current default programs.
    `check` names what the reference check fetches from the `for_test`
    clone: the loss and the logits of the first scored sequences."""
    from paddle_tpu import layers
    from paddle_tpu.models.bert import build_bert_pretrain

    cfg = config(model)
    b, s, p = traffic["batch"], traffic["seq_len"], traffic["masked_positions"]
    handles = build_bert_pretrain(cfg, b, s, mlm_only=True, max_preds=p)
    scored = layers.slice(handles["logits"], axes=[0], starts=[0],
                          ends=[min(b, SCORED_SEQUENCES) * p])
    return {"loss": handles["loss"].name, "feeds": handles["feeds"],
            "check": [handles["loss"].name, scored.name]}


def make_batch(rng, model: dict, traffic: dict) -> dict:
    """One pretraining batch as the reference's data pipeline packs it:
    full-length sentence pairs, `masked_positions` distinct positions a
    row of which 80% show [MASK], 10% a random token and 10% the token
    itself. Token ids are Zipf(1.1) over the vocabulary."""
    b, s, p = traffic["batch"], traffic["seq_len"], traffic["masked_positions"]
    vocab = model["vocab_size"]
    tokens = zipf_ids(rng, (b, s), vocab)
    pos = distinct_positions(rng, b, s, p)
    rows = np.arange(b)[:, None]
    labels = tokens[rows, pos]
    how = rng.random_sample((b, p))
    shown = np.where(how < 0.8, MASK_ID,
                     np.where(how < 0.9, rng.randint(0, vocab, (b, p)), labels))
    src = tokens.copy()
    src[rows, pos] = shown
    split = rng.randint(s // 4, 3 * s // 4, (b, 1))
    return {
        "src_ids": src,
        "sent_ids": (np.arange(s)[None, :] >= split).astype(np.int64),
        "pos_ids": np.tile(np.arange(s, dtype=np.int64), (b, 1)),
        "input_mask": np.ones((b, s), np.float32),
        "mask_label": labels.astype(np.int64),
        "mask_weight": np.ones((b, p), np.float32),
        "mask_pos": pos,
    }


def tokens_per_example(model: dict, traffic: dict) -> int:
    return traffic["seq_len"]  # packed to full length: no padding


def flops_per_example(model: dict, traffic: dict) -> float:
    """Matrix-product FLOPs forward and backward (3 x forward) for one
    sequence. Copied from `paddle_tpu.models.bert.bert_flops_per_token`:
    per token and layer the four attention projections, the two
    feed-forward products and the score and value products over `s` keys;
    the vocabulary projection on the masked positions only. Recomputed
    operations, the embedding gathers and the optimizer do not count."""
    h, n, ff, v = (model["hidden_size"], model["num_hidden_layers"],
                   model["intermediate_size"], model["vocab_size"])
    s, p = traffic["seq_len"], traffic["masked_positions"]
    per_token = n * (2 * (4 * h * h + 2 * h * ff) + 2 * 2 * s * h)
    # masked positions only: the transform (h x h) and the vocabulary
    head = p * (2 * h * h + 2 * h * v)
    return 3.0 * (s * per_token + head)


# ------------------------------------------------------------ reference


def _ln(x, p, name, eps=1e-5):
    import jax.numpy as jnp

    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p[name + ".w_0"] + p[name + ".w_1"]


def _gelu(x):
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _dense(x, p, name):
    return x @ p[name + ".w_0"] + p[name + ".b_0"]


def reference(p: dict, batch: dict, model: dict, drop_layers: int = 0):
    """Forward pass without dropout on some rows of a batch. Returns the
    sum of the weighted negative log-likelihoods, the sum of the weights,
    and the logits at the masked positions, `[rows, P, vocab]`.
    `drop_layers` leaves out that many encoder layers: the tests use it
    to show that a wrong model is caught."""
    import jax
    import jax.numpy as jnp

    nh = model["num_attention_heads"]
    x = (p["bert.word_emb"][batch["src_ids"]]
         + p["bert.pos_emb"][batch["pos_ids"]]
         + p["bert.seg_emb"][batch["sent_ids"]])
    x = _ln(x, p, "bert.emb_ln")
    b, s, h = x.shape
    dh = h // nh
    bias = (batch["input_mask"] - 1.0)[:, None, None, :] * 1e4
    for i in range(model["num_hidden_layers"] - drop_layers):
        n = f"bert.layer{i}"
        q, k, v = (_dense(x, p, f"{n}.attn.{t}").reshape(b, s, nh, dh)
                   for t in "qkv")
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh) + bias
        ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        x = _ln(x + _dense(ctx.reshape(b, s, h), p, n + ".attn.out"),
                p, n + ".ln1")
        ff = _dense(_gelu(_dense(x, p, n + ".ffn1")), p, n + ".ffn2")
        x = _ln(x + ff, p, n + ".ln2")
    picked = jnp.take_along_axis(x, batch["mask_pos"][:, :, None], axis=1)
    picked = picked.reshape(-1, h)
    trans = _ln(_gelu(_dense(picked, p, "mlm.trans")), p, "mlm.ln")
    logits = trans @ p["bert.word_emb"].T + p["mlm.out_b"]
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(
        logp, batch["mask_label"].reshape(-1, 1), axis=1)[:, 0]
    w = batch["mask_weight"].reshape(-1)
    return jnp.sum(nll * w), jnp.sum(w), logits.reshape(b, -1, logits.shape[-1])
