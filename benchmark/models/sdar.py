"""SDAR-30B-A3B-Chat's language model in block-diffusion training, one
chip's share of an 8-chip expert-parallel group: the Program through the
repo's public builder, seeded documents with their noise, FLOPs per
example, and the plain reference.

The reference is written from the equations in
`paddle_tpu/models/sdar.py`'s docstring (the model's public `config.json`;
what it leaves open is listed under `assumed` in the configuration file)
in float32 `jax.numpy`. It shares nothing with `paddle_tpu`'s lowerings
but the parameters' names:

- Attention is plain softmax over explicit masks written from `beta(i) =
  i // B`, in blocks of `QUERY_BLOCK` queries of one copy against the keys
  that copy may see at all (a noisy block: the clean keys before the
  block's end and the block's own noisy keys, side by side in one
  softmax; 32 heads x 512 x 4,608 float32 is 0.3 GB), so the [2L, 2L]
  scores never exist whole. The program runs three calls of the flash
  kernels under a granule and joins two of them by their log-sum-exp
  rows; the reference knows no granule and joins nothing.
- Positions are an explicit array, 0..L-1 for each copy, rotate-half with
  a concatenation; the program folds the two copies into the batch.
- The experts are a loop over the experts held, each over every row with
  a mask as its weight.
- The share is the program's: the router scores all
  `num_experts_published` experts and what the experts held elsewhere
  would add is left out; ids, logits and loss are over the slice of the
  vocabulary; the layers are the published ones from `first_layer_held`.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.harness.datagen import zipf_ids

SCORED_SEQUENCES = 1  # the harness compares logits on this many sequences
# ... at every fifth position of each (the noisy copy's), so that the four
# places of a block of 4 all come up; 820 rows of 18,992 logits, 62 MB
SCORED_EVERY = 5
QUERY_BLOCK = 512  # the reference's attention, queries of one copy a block

# |program - reference| on the scored logits over the reference's own
# root-mean-square, and on the loss (the 1/t-weighted sum over the masked
# positions over L: 10.2 on seeded weights). Each limit lies between two
# readings on the chip (PERF.md section 6, PR 68, has every one). The
# program computes its products in bf16 with float32 accumulation and
# keeps its activations, the residual stream among them, in bf16: through
# four layers it read 0.00449 to 0.00551 on the logits and at most
# 0.00113 on the loss over sixteen seeds. The reference with
# its matrices rounded to fp8 (e4m3) reads 0.0466 and 0.0102 and is
# refused by both limits. The mildest wrong model, a plain causal mask on
# the clean copy (a token not seeing the later tokens of its own block),
# reads 0.0143 on the logits, 1.6 times the limit, which is what sets it:
# the limit leaves twice the program's reading of room below, since fresh
# seeds read higher. The leak (noisy queries seeing their own block's
# clean keys) reads 0.0233, the noisy copy at positions L..2L-1 0.124,
# the others of `WRONG` that move the logits 0.0197 to 0.0321. The two
# that leave the logits alone move the loss by 0.021 (the next token's
# label) and 5.66 (no 1/t weights): the loss's limit lies between the
# program's reading and the first, and is not the other decoder cells'
# 0.003 because the rehearsal's loss, a 1/t-weighted mean of 96 bf16
# per-token losses, reads up to 0.0055 (the cell's is one of 4,096).
TOLERANCE = {"logits_rel_rms": 0.009, "loss_abs": 0.01}

# what `reference(wrong=...)` can be made to get wrong, for the tests and
# the chip readings that place the limits: the three mildest first (a
# plain causal mask on the clean copy; a noisy query seeing its own
# block's clean keys, the leak; the noisy copy at positions L..2L-1)
WRONG = ("clean_causal", "own_clean_block", "noisy_positions_after",
         "noisy_causal", "no_qk_norm", "shifted_targets", "unweighted_loss")


def held_layers(model: dict) -> list[int]:
    """Published index of each layer held."""
    first = model["first_layer_held"]
    return list(range(first, first + model["num_hidden_layers"]))


def config(model: dict):
    from paddle_tpu.models.sdar import SdarConfig

    return SdarConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        num_hidden_layers=model["num_hidden_layers"],
        first_layer=model["first_layer_held"],
        num_attention_heads=model["num_attention_heads"],
        num_key_value_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"], rope_theta=model["rope_theta"],
        moe_intermediate_size=model["moe_intermediate_size"],
        num_experts=model["num_experts_published"],
        experts_held=model["num_experts"], held_from=model["held_from"],
        num_experts_per_token=model["num_experts_per_tok"],
        norm_topk_prob=model["norm_topk_prob"],
        rms_norm_eps=model["rms_norm_eps"],
        block_length=model["block_length"],
        mask_token_id=model["mask_token_id"],
        initializer_range=model["initializer_range"],
        embedding_initializer_range=model["embedding_initializer_range"])


def build(model: dict, traffic: dict) -> dict:
    """Declare the training program in the current default programs.
    `check` names what the reference check fetches from the `for_test`
    clone: the loss and the noisy rows' logits at every
    `SCORED_EVERY`-th position."""
    from paddle_tpu import layers
    from paddle_tpu.models.sdar import build_sdar

    b, s = traffic["batch"], traffic["seq_len"]
    handles = build_sdar(config(model), b, s)
    scored = layers.strided_slice(
        handles["logits"], axes=[0, 1], starts=[0, 0],
        ends=[min(b, SCORED_SEQUENCES), s], strides=[1, SCORED_EVERY])
    return {"loss": handles["loss"].name, "feeds": handles["feeds"],
            "check": [handles["loss"].name, scored.name],
            "loads": [v.name for v in handles["loads"]]}


def make_batch(rng, model: dict, traffic: dict) -> dict:
    """One document a row, `seq_len` tokens, no padding, no packing: ids
    Zipf(1.1) over the rows of the vocabulary below the mask's. A noise
    level t a block of `block_length` tokens, uniform on (0, 1] (and not
    under `noise_floor`), each token of the block replaced by the mask id
    with probability t, independently. `weights` is 1/t at the masked
    positions and 0 elsewhere: the loss's weights, so the program draws
    nothing."""
    b, s = traffic["batch"], traffic["seq_len"]
    block, mask_id = model["block_length"], model["mask_token_id"]
    tokens = zipf_ids(rng, (b, s), mask_id)
    t = np.maximum(1.0 - rng.random_sample((b, s // block)),
                   model["noise_floor"])
    t = np.repeat(t, block, axis=1)
    masked = rng.random_sample((b, s)) < t
    return {"noisy": np.where(masked, mask_id, tokens).astype(np.int64),
            "tokens": tokens,
            "weights": np.where(masked, 1.0 / t, 0.0).astype(np.float32)}


def tokens_per_example(model: dict, traffic: dict) -> int:
    """A document's tokens: an example is a document, whose two copies
    are the step's way of scoring it. The runner asks once its window is
    over, when every program has been built and lowered: the first call
    of a process prints `counters_note()` among the run's lines (the
    harness gives an adapter no other line of its own, and no metric file
    may be added for the two ratios: PERF.md section 7)."""
    if not _noted:
        _noted.append(True)
        print(counters_note(), flush=True)
    return traffic["seq_len"]


_noted = []  # whether this process has printed its note


def counters_note() -> str:
    """What the mask costs by the program's own counters: the pairs a
    head admits of the doubled row's causal pairs (a build bumps both
    once a layer) and the blocks of scores the flash grids visit, of the
    three rectangles the calls cover and of the doubled row's whole
    square, a quarter of which (the clean copy on the noisy one) no call
    covers (a lowering bumps both counters once a call, so a ratio).
    `keye_pairs_admitted_pct` and `flash_blocks_visited_pct` read the
    same counters in their own cells."""
    from paddle_tpu import profiler

    c = profiler.counters()
    pairs = (c.get("attn_pairs_admitted", 0), c.get("attn_pairs_causal", 0))
    blocks = (c.get("flash_blocks_visited", 0), c.get("flash_blocks_total", 0))
    share = lambda a, b: f"{100.0 * a / b:.2f}%" if b else "none"  # noqa: E731
    return (f"block diffusion: block {c.get('diffusion_block_length')}, "
            f"{c.get('diffusion_layers', 0)} layers built, loss_terms "
            f"{c.get('loss_terms')}; attn_pairs_admitted {pairs[0]} of "
            f"attn_pairs_causal {pairs[1]} = {share(*pairs)}; "
            f"flash_blocks_visited {blocks[0]} of flash_blocks_total "
            f"{blocks[1]} = {share(*blocks)} of the calls' rectangles, "
            f"{share(3 * blocks[0], 4 * blocks[1])} of the doubled row's "
            "square")


def matrix_params_per_row(model: dict) -> tuple[float, float]:
    """(weights of the matrix products one row of a layer's input passes
    through for its keys and values alone, for everything else of the
    layer): k and v; q, o, the router, and the routed experts at the
    share of a row's `num_experts_per_tok` assignments that a balanced
    router sends to the `num_experts` held of `num_experts_published`: 1
    of 8."""
    h = model["hidden_size"]
    hd = model["num_attention_heads"] * model["head_dim"]
    kvd = model["num_key_value_heads"] * model["head_dim"]
    held = (model["num_experts_per_tok"] * model["num_experts"]
            / model["num_experts_published"])
    return 2 * h * kvd, (2 * h * hd + h * model["num_experts_published"]
                         + 3 * h * model["moe_intermediate_size"] * held)


def admitted_pairs(s: int, block: int) -> tuple[int, int, int]:
    """(query, key) pairs of one head that the mask admits over a row of
    `s` tokens in blocks of `block`: (noisy on its own noisy block, noisy
    on the clean blocks before it, clean on the clean blocks up to and
    with its own)."""
    return s * block, (s * s - s * block) // 2, (s * s + s * block) // 2


def flops_per_example(model: dict, traffic: dict) -> float:
    """Matrix-product FLOPs forward and backward (3 x forward) for one
    document, **of what the loss needs**: two a weight a row over both
    copies' 2 x `seq_len` rows in every layer but the last held, where
    the clean copy gives its keys and values and nothing else (its
    queries, their output product and their expert rows feed only the
    clean rows out of the layer, which on this chip feed nothing; in the
    deployment they feed the next stage); the scores and the values over
    the pairs the mask **admits** (2 x head_dim each a pair a head), the
    clean copy's own pairs left out of the last layer likewise; the head
    over the noisy rows. Where the compiled step computes the last
    layer's clean rows all the same, that work is not useful and does not
    count, so the utilisation cannot be flattered by it. The embedding
    gather, the router's sort, the norms, the rotation and the optimizer
    do not count."""
    s, layers = traffic["seq_len"], len(held_layers(model))
    kv, rest = matrix_params_per_row(model)
    own, past, clean = admitted_pairs(s, model["block_length"])
    lanes = model["num_attention_heads"] * 4 * model["head_dim"]
    rows = 2 * s * layers * kv + (2 * s * (layers - 1) + s) * rest
    pairs = layers * (own + past) + (layers - 1) * clean
    head = s * model["hidden_size"] * model["vocab_size"]
    return 3.0 * (2 * (rows + head) + pairs * lanes)


# ------------------------------------------------------------ reference


def _rms(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _silu(x):
    import jax

    return x * jax.nn.sigmoid(x)


def _rope(x, positions, theta):
    """x: [b, rows, heads, d], `positions` [rows], rotate-half:
    `x * cos + [-x2, x1] * sin`, the angles of the first half repeated."""
    import jax.numpy as jnp

    d = x.shape[3]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], -1)[None, :, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(angle) + turned * jnp.sin(angle)


def attention_mixer(p, u, name, model, wrong=()):
    """u: [b, 2L, hidden], the noisy rows and then the clean rows, to
    [b, 2L, hidden]."""
    import jax
    import jax.numpy as jnp

    h, g, d = (model["num_attention_heads"], model["num_key_value_heads"],
               model["head_dim"])
    eps, block = model["rms_norm_eps"], model["block_length"]
    b, rows, _ = u.shape
    length = rows // 2
    q = (u @ p[name + ".q.w_0"]).reshape(b, rows, h, d)
    k = (u @ p[name + ".k.w_0"]).reshape(b, rows, g, d)
    v = (u @ p[name + ".v.w_0"]).reshape(b, rows, g, d)
    if "no_qk_norm" not in wrong:
        q = _rms(q, p[name + ".q_norm.w_0"], eps)
        k = _rms(k, p[name + ".k_norm.w_0"], eps)
    # both copies count 0..L-1
    positions = jnp.concatenate([jnp.arange(length)] * 2)
    if "noisy_positions_after" in wrong:
        positions = jnp.concatenate(
            [jnp.arange(length, 2 * length), jnp.arange(length)])
    q = _rope(q, positions, model["rope_theta"])
    k = _rope(k, positions, model["rope_theta"])
    # query head n reads key/value head n // (h / g)
    kv_of = jnp.arange(h) // (h // g)
    k, v = k[:, :, kv_of], v[:, :, kv_of]
    (qn, qc), (kn, kc), (vn, vc) = (
        (t[:, :length], t[:, length:]) for t in (q, k, v))

    def softmax_over(q_, keys, values, visible):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_, keys) / math.sqrt(d)
        scores = jnp.where(visible, scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                          values)

    noisy, clean = [], []
    for lo in range(0, length, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, length)
        i = jnp.arange(lo, hi)[:, None]
        past, here = jnp.arange(hi)[None, :], jnp.arange(lo, hi)[None, :]
        beta = lambda t: t // block  # noqa: E731
        # a clean row: the clean blocks up to and with its own
        see = beta(past) <= beta(i)
        if "clean_causal" in wrong:
            see = past <= i
        clean.append(softmax_over(qc[:, lo:hi], kc[:, :hi], vc[:, :hi], see))
        # a noisy row: the clean blocks before its own, and its own noisy
        # block both ways, one softmax over the two
        before = beta(past) < beta(i)
        if "own_clean_block" in wrong:
            before = beta(past) <= beta(i)
        own = beta(here) == beta(i)
        if "noisy_causal" in wrong:
            own = own & (here <= i)
        noisy.append(softmax_over(
            qn[:, lo:hi],
            jnp.concatenate([kc[:, :hi], kn[:, lo:hi]], 1),
            jnp.concatenate([vc[:, :hi], vn[:, lo:hi]], 1),
            jnp.concatenate([before, own], 1)))
    a = jnp.concatenate(noisy + clean, 1).reshape(b, rows, h * d)
    return a @ p[name + ".o.w_0"]


def expert_ffn(p, u, name, model):
    """The experts held: one dense FFN an expert over every row,
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


def reference(p: dict, batch: dict, model: dict, drop_layers: int = 0,
              wrong=()):
    """Forward pass on some rows of a batch. Returns the loss's numerator
    (the sum over the masked positions of the cross-entropy of the token
    itself, each times 1/t of its block), the rows' tokens' count (so
    that the one over the other is the program's loss), and the noisy
    rows' logits at every `SCORED_EVERY`-th position,
    `[rows, ceil(L / SCORED_EVERY), vocab]`. `drop_layers` leaves out that
    many of the last layers and `wrong` names departures of `WRONG`: the
    tests and the chip readings use them to show that a wrong model is
    caught."""
    import jax
    import jax.numpy as jnp

    eps = model["rms_norm_eps"]
    tokens, weights = batch["tokens"], batch["weights"]
    length = tokens.shape[1]
    x = p["sdar.embed"][jnp.concatenate([batch["noisy"], tokens], 1)]
    layers = held_layers(model)
    for i in layers[:len(layers) - drop_layers]:
        n = f"sdar.layer{i}"
        a = _rms(x, p[n + ".input_norm.w_0"], eps)
        x = x + attention_mixer(p, a, n + ".attn", model, wrong)
        u = _rms(x, p[n + ".post_attn_norm.w_0"], eps)
        x = x + expert_ffn(p, u, n, model)
    logits = (_rms(x[:, :length], p["sdar.final_norm.w_0"], eps)
              @ p["sdar.head.w_0"])
    logp = jax.nn.log_softmax(logits, -1)
    targets = tokens
    if "shifted_targets" in wrong:  # the next token's, as a decoder's
        targets = jnp.roll(tokens, -1, axis=1)
    nll = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
    if "unweighted_loss" in wrong:
        weights = (weights > 0).astype(jnp.float32)
    return (jnp.sum(nll * weights), jnp.asarray(nll.size, jnp.float32),
            logits[:, ::SCORED_EVERY])
