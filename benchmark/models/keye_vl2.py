"""Keye-VL-2.0-30B-A3B's language model, one chip's share of a 16-chip
expert-parallel group: the Program through the repo's public builder,
seeded documents, FLOPs per example, and the plain reference.

The reference is written from the equations in
`paddle_tpu/models/keye_vl2.py`'s docstring (the model's public
`config.json`; what it leaves open is listed under `assumed` in the
configuration file) in float32 `jax.numpy`. It shares nothing with
`paddle_tpu`'s lowerings but the parameters' names:

- The indexer's score, the selection and both attentions are explicit
  [queries, keys] arrays with explicit masks, in blocks of `QUERY_BLOCK`
  queries against the whole row of keys (32 heads x 512 x 8,192 float32
  is 0.5 GB). The selection is a plain sort of a row: the K-th largest is
  read off the sorted row. The program bisects on the values' bits and
  never sorts.
- The target of the indexer's loss is the mean over the heads of the
  softmax the attention itself used, the very array; the program rebuilds
  it from q, k and the attention kernel's log-sum-exp rows.
- Positions are `mrope_section` written out: a `[3, b, s]` array of
  positions (temporal, height, width), each frequency of a head reading
  the channel its section names, rotate-half with a concatenation. On
  text the three channels all count 0..s-1, which is what `reference`
  fills in, and the rotation is then the plain one the program's op
  makes.
- The experts are a loop over the experts held, each over every token
  with a mask as its weight.
- The share is the program's: the router scores all
  `num_experts_published` experts and what the experts held elsewhere
  would add is left out; ids, logits and loss are over the slice of the
  vocabulary; the layers are the published ones from `first_layer_held`.
"""

from __future__ import annotations

import math

from benchmark.harness.datagen import zipf_ids

SCORED_SEQUENCES = 1  # the harness compares logits on this many sequences
SCORED_EVERY = 16  # ... at every sixteenth position of each
QUERY_BLOCK = 512  # the reference's attention and indexer, queries a block

# |program - reference| on the scored logits over the reference's own
# root-mean-square, and on the loss, which is the language-model loss
# plus the four layers' indexer losses (0.33 to 0.36 of 10.6, so that a
# fault in the indexer's loss does not hide: the dense stage's loss reads
# 1.87 for 0.348 and a target averaged before the softmax 0.313). The
# logits' limit lies between two readings on the chip (PERF.md section 6,
# PR 60, has every one). The program computes its products in bf16 with
# float32 accumulation, keeps its activations in bf16 and so selects
# 0.42 to 0.74% of a layer's pairs otherwise than the reference: through
# four layers it read 0.00597 to 0.00626 on the logits and at most
# 0.00043 on the loss over eleven seeds. The reference with its matrices
# rounded to fp8 (e4m3) reads 0.0484 on the logits, which is what refuses
# it, and 0.0008 to 0.0012 on the loss, which does not. The wrong models
# that change the selection read 0.0189 to 0.156 on the logits and 0.043
# to 0.47 on the loss (the mildest, no LayerNorm on the indexer's key:
# 0.0189 and 0.0204, 0.043). The logits' limit leaves 2.4 times the
# program's largest reading of room, since fresh seeds read higher, and
# the fp8 reference 3.2 times above it. The loss's limit is the one the
# harness's other decoder cells have, seven times the largest reading.
TOLERANCE = {"logits_rel_rms": 0.015, "loss_abs": 0.003}

# what `reference(wrong=...)` can be made to get wrong, for the tests and
# the chip readings that place the limits
WRONG = ("dense_attention", "select_before_causal", "no_relu",
         "unit_index_weights", "no_key_layernorm", "kl_over_all_keys",
         "target_not_detached", "indexer_reads_live_stream",
         "target_mean_of_logits")


def held_layers(model: dict) -> list[int]:
    """Published index of each layer held."""
    first = model["first_layer_held"]
    return list(range(first, first + model["num_hidden_layers"]))


def config(model: dict):
    from paddle_tpu.models.keye_vl2 import KeyeVL2Config

    return KeyeVL2Config(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        num_hidden_layers=model["num_hidden_layers"],
        first_layer=model["first_layer_held"],
        num_attention_heads=model["num_attention_heads"],
        num_key_value_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"], rope_theta=model["rope_theta"],
        mrope_section=model["rope_scaling"]["mrope_section"],
        sa_config=model["sa_config"],
        moe_intermediate_size=model["moe_intermediate_size"],
        num_experts=model["num_experts_published"],
        experts_held=model["num_experts"], held_from=model["held_from"],
        num_experts_per_token=model["num_experts_per_tok"],
        norm_topk_prob=model["norm_topk_prob"],
        rms_norm_eps=model["rms_norm_eps"],
        layer_norm_eps=model["indexer_layer_norm_eps"],
        index_loss_weight=model["index_loss_weight"],
        initializer_range=model["initializer_range"],
        embedding_initializer_range=model["embedding_initializer_range"])


def build(model: dict, traffic: dict) -> dict:
    """Declare the training program in the current default programs.
    `check` names what the reference check fetches from the `for_test`
    clone: the loss (both terms) and the logits at every
    `SCORED_EVERY`-th position; `terms` the language-model loss and the
    sum of the layers' indexer losses, and `admits` each layer's
    selection, which the suite and the chip readings fetch beside them."""
    from paddle_tpu import layers
    from paddle_tpu.models.keye_vl2 import build_keye_vl2

    b, s = traffic["batch"], traffic["seq_len"]
    handles = build_keye_vl2(config(model), b, s)
    scored = layers.strided_slice(
        handles["logits"], axes=[0, 1], starts=[0, 0],
        ends=[min(b, SCORED_SEQUENCES), s], strides=[1, SCORED_EVERY])
    return {"loss": handles["loss"].name, "feeds": handles["feeds"],
            "check": [handles["loss"].name, scored.name],
            "terms": [handles["lm_loss"].name, handles["index_loss"].name],
            "loads": [v.name for v in handles["loads"]],
            "admits": [v.name for v in handles["admits"]]}


def make_batch(rng, model: dict, traffic: dict) -> dict:
    """One document a row, `seq_len` tokens and the token after each as its
    label: no padding, no packing, every position scored. Ids are
    Zipf(1.1) over the rows of the vocabulary held here."""
    b, s = traffic["batch"], traffic["seq_len"]
    doc = zipf_ids(rng, (b, s + 1), model["vocab_size"])
    return {"tokens": doc[:, :-1].copy(), "labels": doc[:, 1:].copy()}


def tokens_per_example(model: dict, traffic: dict) -> int:
    return traffic["seq_len"]


def matrix_params_per_token(model: dict) -> float:
    """Weights of the matrix products one token passes through in the
    layers held here: attention's four, the indexer's three, the router,
    and the routed experts at the share of a token's
    `num_experts_per_tok` assignments that a balanced router sends to the
    `num_experts` held of `num_experts_published`: 0.5 of 8."""
    h = model["hidden_size"]
    hd = model["num_attention_heads"] * model["head_dim"]
    kvd = model["num_key_value_heads"] * model["head_dim"]
    sa = model["sa_config"]
    attn = h * (hd + 2 * kvd) + hd * h  # q, k, v; o
    indexer = h * (sa["indexer_num_heads"] * sa["indexer_head_dim"]
                   + sa["indexer_head_dim"] + sa["indexer_num_heads"])
    held = (model["num_experts_per_tok"] * model["num_experts"]
            / model["num_experts_published"])
    expert = (h * model["num_experts_published"]
              + 3 * h * model["moe_intermediate_size"] * held)
    return (len(held_layers(model)) * (attn + indexer + expert)
            + h * model["vocab_size"])


def admitted_pairs(s: int, topk: int) -> int:
    """(query, key) pairs of one head that the selection admits over a
    row of `s` tokens: query t keeps min(t + 1, topk) keys."""
    full = min(s, topk)
    return full * (full + 1) // 2 + (s - full) * full


def flops_per_example(model: dict, traffic: dict) -> float:
    """Matrix-product FLOPs forward and backward (3 x forward) for one
    document, from the shapes: two a weight a token
    (`matrix_params_per_token`); the main heads' scores and values over
    the pairs the selection **admits** (2 x head_dim each a pair a head),
    so that the masked pairs a kernel computes cannot flatter the
    utilisation; the indexer's scores over every causal pair (2 x
    indexer_head_dim a pair an indexer head), since it has to score a key
    to refuse it. The indexer's loss costs nothing more: its target is
    the forward's own probabilities. The embedding gather, the selection,
    the router's sort, the norms, the rotations and the optimizer do not
    count."""
    s, sa = traffic["seq_len"], model["sa_config"]
    layers = len(held_layers(model))
    attn = (admitted_pairs(s, sa["topk"]) * model["num_attention_heads"]
            * 4 * model["head_dim"])
    index = (s * (s + 1) // 2 * sa["indexer_num_heads"]
             * 2 * sa["indexer_head_dim"])
    return 3.0 * (2 * s * matrix_params_per_token(model)
                  + layers * (attn + index))


# ------------------------------------------------------------ reference


def _rms(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _layer_norm(x, w, b, eps):
    import jax.numpy as jnp

    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def _silu(x):
    import jax

    return x * jax.nn.sigmoid(x)


def mrope_angles(positions, d, theta, section):
    """[b, s, d/2] angles from `positions` [3, b, s] (temporal, height,
    width): frequency i = theta^(-2i/d) reads the channel its section
    names, the first `section[0]` frequencies the temporal one, the next
    `section[1]` the height's, the rest the width's. `section` None: a
    head too narrow for the sections (the indexer's), which reads the
    temporal channel throughout."""
    import jax.numpy as jnp
    import numpy as np

    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if section is None:
        channel = np.zeros(d // 2, np.int32)
    else:
        if sum(section) != d // 2:
            raise ValueError(f"mrope_section {section} over {d // 2} "
                             "frequencies")
        channel = np.repeat(np.arange(3), section)
    # [3, b, s, d/2] -> each frequency from its channel
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.stack([angles[c, :, :, i] for i, c in enumerate(channel)], -1)


def _rope(x, angle):
    """x: [b, s, heads, d], angle [b, s, d/2], rotate-half:
    `x * cos + [-x2, x1] * sin`, the angles of the first half repeated."""
    import jax.numpy as jnp

    d = x.shape[3]
    angle = jnp.concatenate([angle, angle], -1)[:, :, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(angle) + turned * jnp.sin(angle)


def index_scores(p, a, name, model, positions, wrong=()):
    """The indexer's score of every (query, key) pair, [b, s, s] float32,
    with nothing masked: `sum_j w[t,j] H^-1/2 d^-1/2 relu(qI[t,j] .
    kI[s])`, in blocks of queries."""
    import jax
    import jax.numpy as jnp

    sa = model["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    b, s, _ = a.shape
    angle = mrope_angles(positions, di, model["rope_theta"], None)
    qi = _rope((a @ p[name + ".indexer.q.w_0"]).reshape(b, s, hi, di), angle)
    ki = a @ p[name + ".indexer.k.w_0"]
    if "no_key_layernorm" not in wrong:
        ki = _layer_norm(ki, p[name + ".indexer.k_norm.w_0"],
                         p[name + ".indexer.k_norm.b_0"],
                         model["indexer_layer_norm_eps"])
    ki = _rope(ki.reshape(b, s, 1, di), angle)[:, :, 0]
    w = a @ p[name + ".indexer.w.w_0"]
    if "unit_index_weights" in wrong:
        w = jnp.ones_like(w)
    out = []
    for lo in range(0, s, QUERY_BLOCK):
        hi_q = min(lo + QUERY_BLOCK, s)
        dots = jnp.einsum("bqhd,bkd->bqhk", qi[:, lo:hi_q], ki)
        if "no_relu" not in wrong:
            dots = jax.nn.relu(dots)
        out.append(jnp.einsum("bqhk,bqh->bqk", dots, w[:, lo:hi_q])
                   * hi ** -0.5 * di ** -0.5)
    return jnp.concatenate(out, 1)


def selection(index, topk, wrong=()):
    """[b, s, s] bool: S_t, the causal keys whose score is at least the
    K-th largest of the row's causal scores (every causal key where there
    are no more than K), by a plain sort of each row."""
    import jax.numpy as jnp

    s = index.shape[1]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    if "dense_attention" in wrong or topk >= s:
        return jnp.broadcast_to(causal, index.shape)
    if "select_before_causal" in wrong:
        # the K largest of the whole row, then the causal mask (and the
        # query's own key, so that no early row is left with nothing)
        tau = jnp.sort(index, -1)[..., s - topk]
        return ((index >= tau[..., None]) & causal) | jnp.eye(s, dtype=bool)
    tau = jnp.sort(jnp.where(causal, index, -jnp.inf), -1)[..., s - topk]
    tau = jnp.where(jnp.arange(s) < topk, -jnp.inf, tau)  # the K-th largest
    return (index >= tau[..., None]) & causal


def sparse_attention(p, a, name, model, positions, wrong=()):
    """a: [b, s, hidden], the normed stream -> ([b, s, hidden], the
    layer's L_I summed over the tokens, [b, s, s] bool the selection)."""
    import jax
    import jax.numpy as jnp

    h, g, d = (model["num_attention_heads"], model["num_key_value_heads"],
               model["head_dim"])
    eps = model["rms_norm_eps"]
    b, s, _ = a.shape
    angle = mrope_angles(positions, d, model["rope_theta"],
                         model["rope_scaling"]["mrope_section"])
    q = _rope(_rms((a @ p[name + ".q.w_0"]).reshape(b, s, h, d),
                   p[name + ".q_norm.w_0"], eps), angle)
    k = _rope(_rms((a @ p[name + ".k.w_0"]).reshape(b, s, g, d),
                   p[name + ".k_norm.w_0"], eps), angle)
    v = (a @ p[name + ".v.w_0"]).reshape(b, s, g, d)
    read = a if "indexer_reads_live_stream" in wrong else (
        jax.lax.stop_gradient(a))
    index = index_scores(p, read, name, model, positions, wrong)
    kept = selection(jax.lax.stop_gradient(index), model["sa_config"]["topk"],
                     wrong)
    # query head n reads key/value head n // (h / g)
    kv_of = jnp.arange(h) // (h // g)
    k, v = k[:, :, kv_of], v[:, :, kv_of]
    out, kl = [], 0.0
    for lo in range(0, s, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, s)
        here = kept[:, lo:hi]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k) / math.sqrt(d)
        scores = jnp.where(here[:, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, -1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", probs, v))
        target = jnp.mean(probs, 1)
        if "target_mean_of_logits" in wrong:  # heads averaged too early
            target = jax.nn.softmax(jnp.mean(scores, 1), -1)
        if "target_not_detached" not in wrong:
            target = jax.lax.stop_gradient(target)
        over = here
        if "kl_over_all_keys" in wrong:  # the dense stage's loss
            over = jnp.broadcast_to(
                jnp.arange(lo, hi)[:, None] >= jnp.arange(s)[None, :],
                here.shape)
        logq = jax.nn.log_softmax(
            jnp.where(over, index[:, lo:hi], -jnp.inf), -1)
        held = target > 0  # 0 log 0 = 0
        kl = kl + jnp.sum(jnp.where(
            held, target * (jnp.log(jnp.where(held, target, 1.0))
                            - jnp.where(held, logq, 0.0)), 0.0))
    o = jnp.concatenate(out, 1).reshape(b, s, h * d)
    return o @ p[name + ".o.w_0"], kl, kept


def expert_ffn(p, u, name, model):
    """The experts held: one dense FFN an expert over every token,
    weighted by what the router gave that expert there."""
    import jax
    import jax.numpy as jnp

    k = model["num_experts_per_tok"]
    scores = jax.nn.softmax(u @ p[name + ".moe.gate"], -1)
    w, chosen = jax.lax.top_k(scores, k)
    if model["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    y = 0.0
    for e in range(model["num_experts"]):
        here = jnp.sum(jnp.where(chosen == model["held_from"] + e, w, 0.0), -1)
        one = (_silu(u @ p[name + ".moe.w_gate"][e])
               * (u @ p[name + ".moe.w_up"][e])) @ p[name + ".moe.w_down"][e]
        y = y + here[..., None] * one
    return y


def reference_terms(p: dict, batch: dict, model: dict, drop_layers: int = 0,
                    wrong=()):
    """The forward pass on some rows of a batch, term by term: the sum of
    the negative log-likelihoods of the labels (`nll`), the layers' L_I
    summed over layers and tokens (`index`), the tokens' count, the logits
    `[rows, s, vocab]` and each layer's selection `[rows, s, s]` bool.
    `batch["positions"]`, where given, is the `[3, rows, s]` array the
    rotation reads; text rows count 0..s-1 on all three channels."""
    import jax
    import jax.numpy as jnp

    eps = model["rms_norm_eps"]
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (3, b, s))
    x = p["keye.embed"][tokens]
    layers = held_layers(model)
    index, kept = 0.0, []
    for i in layers[:len(layers) - drop_layers]:
        n = f"keye.layer{i}"
        a = _rms(x, p[n + ".input_norm.w_0"], eps)
        mixed, kl, sel = sparse_attention(p, a, n + ".attn", model, positions,
                                          wrong)
        index, kept = index + kl, kept + [sel]
        x = x + mixed
        u = _rms(x, p[n + ".post_attn_norm.w_0"], eps)
        x = x + expert_ffn(p, u, n, model)
    logits = _rms(x, p["keye.final_norm.w_0"], eps) @ p["keye.head.w_0"]
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, batch["labels"][..., None], -1)[..., 0]
    return {"nll": jnp.sum(nll), "index": index,
            "count": jnp.asarray(nll.size, jnp.float32), "logits": logits,
            "kept": kept}


def reference(p: dict, batch: dict, model: dict, drop_layers: int = 0,
              wrong=()):
    """Returns the loss's numerator (the sum of the negative
    log-likelihoods of the labels plus `index_loss_weight` times the
    layers' L_I summed over the tokens, so that over the count it is the
    program's two-term loss), the tokens' count, and the logits at every
    `SCORED_EVERY`-th position, `[rows, s / SCORED_EVERY, vocab]`.
    `drop_layers` leaves out that many of the last layers and `wrong`
    names departures of `WRONG`: the tests and the chip readings use them
    to show that a wrong model is caught."""
    t = reference_terms(p, batch, model, drop_layers, wrong)
    return (t["nll"] + model["index_loss_weight"] * t["index"], t["count"],
            t["logits"][:, ::SCORED_EVERY])
