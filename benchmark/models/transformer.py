"""Transformer encoder-decoder for translation: the Program through the
repo's public builder, seeded sentence pairs, FLOPs per example, and the
plain reference.

The reference follows "Attention Is All You Need" (Vaswani et al. 2017),
sections 3.1-3.5: post-LayerNorm residual blocks, scaled dot-product
attention, ReLU feed-forward, embeddings scaled by sqrt(d_model), fixed
sinusoidal positions, and one shared table used as source embedding,
target embedding and output projection (section 3.4). Departures, all the
program's own and followed here: LayerNorm's epsilon is 1e-5; the output
projection adds a bias; the loss is the plain cross-entropy, without the
paper's label smoothing (the repo's builder has none).
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.harness.datagen import zipf_ids
from benchmark.models.bert import _dense, _ln

SCORED_SEQUENCES = 8

# see benchmark/models/bert.py: bf16 inputs with float32 accumulation
# measured 0.55-0.56% through 6 + 6 layers on the chip; the bound is the
# same one
TOLERANCE = {"logits_rel_rms": 0.03, "loss_abs": 0.06}


def config(model: dict):
    from paddle_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        src_vocab=model["vocab_size"], trg_vocab=model["vocab_size"],
        d_model=model["d_model"], n_heads=model["n_heads"],
        d_ff=model["d_ff"], n_layers=model["n_layers"],
        max_len=model["max_len"], dropout=model["dropout"],
        weight_sharing=model["weight_sharing"])


def build(model: dict, traffic: dict) -> dict:
    from paddle_tpu import layers
    from paddle_tpu.models.transformer import build_transformer

    b, s, t = traffic["batch"], traffic["src_len"], traffic["trg_len"]
    handles = build_transformer(config(model), b, s, t)
    scored = layers.slice(handles["logits"], axes=[0], starts=[0],
                          ends=[min(b, SCORED_SEQUENCES)])
    return {"loss": handles["loss"].name, "feeds": handles["feeds"],
            "check": [handles["loss"].name, scored.name]}


def make_batch(rng, model: dict, traffic: dict) -> dict:
    """Sentence pairs padded to one bucket length, as a length-bucketed
    reader hands them over; every position is a real token (id 0 is kept
    for padding and never drawn). The decoder reads the target shifted by
    one and is scored on the next token."""
    b, s, t = traffic["batch"], traffic["src_len"], traffic["trg_len"]
    vocab = model["vocab_size"]
    src = zipf_ids(rng, (b, s), vocab, first=1)
    trg = zipf_ids(rng, (b, t + 1), vocab, first=1)
    return {
        "src_ids": src,
        "trg_ids": trg[:, :-1].copy(),
        "lbl_ids": trg[:, 1:].copy(),
        "src_mask": np.ones((b, s), np.float32),
        "trg_mask": np.ones((b, t), np.float32),
        f"src_emb_posids_{s}": np.tile(np.arange(s, dtype=np.int64), (b, 1)),
        f"trg_emb_posids_{t}": np.tile(np.arange(t, dtype=np.int64), (b, 1)),
    }


def tokens_per_example(model: dict, traffic: dict) -> int:
    return traffic["trg_len"]  # target tokens, the field's convention


def flops_per_example(model: dict, traffic: dict) -> float:
    """Matrix-product FLOPs forward and backward (3 x forward) for one
    sentence pair. Copied from
    `paddle_tpu.models.transformer.transformer_flops_per_trg_token`
    (which assumes equal lengths) and written per side: four projections
    and the score and value products of each attention, the two
    feed-forward products, and the vocabulary projection at every target
    position."""
    d, ff, n, v = (model["d_model"], model["d_ff"], model["n_layers"],
                   model["vocab_size"])
    s, t = traffic["src_len"], traffic["trg_len"]
    attn = lambda q, k: 2 * 4 * d * d * q + 2 * 2 * q * k * d  # noqa: E731
    ffn = lambda q: 2 * 2 * d * ff * q  # noqa: E731
    enc = n * (attn(s, s) + ffn(s))
    # cross attention projects K and V from the s source positions
    cross = 2 * 2 * d * d * t + 2 * 2 * d * d * s + 2 * 2 * t * s * d
    dec = n * (attn(t, t) + cross + ffn(t))
    return 3.0 * (enc + dec + 2 * d * v * t)


# ------------------------------------------------------------ reference


def _positions(length: int, d: int):
    """PE(pos, 2i) = sin(pos / 10000^(2i/d)), PE(pos, 2i+1) = cos(same)."""
    import jax.numpy as jnp

    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    i = jnp.arange(d)[None, :]
    angle = pos / jnp.power(10000.0, (2 * (i // 2)) / d)
    return jnp.where(i % 2 == 0, jnp.sin(angle), jnp.cos(angle))


def _attend(x_q, x_kv, p, name, nh, bias):
    import jax
    import jax.numpy as jnp

    b, sq, d = x_q.shape
    sk = x_kv.shape[1]
    dh = d // nh
    q = _dense(x_q, p, name + ".q").reshape(b, sq, nh, dh)
    k = _dense(x_kv, p, name + ".k").reshape(b, sk, nh, dh)
    v = _dense(x_kv, p, name + ".v").reshape(b, sk, nh, dh)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh) + bias
    ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return _dense(ctx.reshape(b, sq, d), p, name + ".out")


def _ffn(x, p, name):
    import jax

    return _dense(jax.nn.relu(_dense(x, p, name + ".fc1")), p, name + ".fc2")


def reference(p: dict, batch: dict, model: dict, drop_layers: int = 0):
    """Forward pass without dropout on some rows of a batch. Returns the
    sum of the masked negative log-likelihoods, the sum of the mask, and
    the logits `[rows, trg_len, vocab]`. `drop_layers` leaves out that
    many decoder layers (tests only)."""
    import jax
    import jax.numpy as jnp

    d, nh, n = model["d_model"], model["n_heads"], model["n_layers"]
    table = p["shared_emb"]
    src, trg = batch["src_ids"], batch["trg_ids"]
    s, t = src.shape[1], trg.shape[1]
    src_bias = (batch["src_mask"] - 1.0)[:, None, None, :] * 1e4
    causal = jnp.where(jnp.arange(t)[:, None] >= jnp.arange(t)[None, :],
                       0.0, -1e9)
    trg_bias = (batch["trg_mask"] - 1.0)[:, None, None, :] * 1e4 + causal

    x = table[src] * math.sqrt(d) + _positions(s, d)
    for i in range(n):
        x = _ln(x + _attend(x, x, p, f"enc{i}.self", nh, src_bias),
                p, f"enc{i}.ln1")
        x = _ln(x + _ffn(x, p, f"enc{i}.ffn"), p, f"enc{i}.ln2")
    y = table[trg] * math.sqrt(d) + _positions(t, d)
    for i in range(n - drop_layers):
        y = _ln(y + _attend(y, y, p, f"dec{i}.self", nh, trg_bias),
                p, f"dec{i}.ln1")
        y = _ln(y + _attend(y, x, p, f"dec{i}.cross", nh, src_bias),
                p, f"dec{i}.ln2")
        y = _ln(y + _ffn(y, p, f"dec{i}.ffn"), p, f"dec{i}.ln3")
    logits = y @ table.T + p["proj.b"]
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, batch["lbl_ids"][..., None], axis=2)[..., 0]
    return (jnp.sum(nll * batch["trg_mask"]), jnp.sum(batch["trg_mask"]),
            logits)
