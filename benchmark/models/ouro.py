"""Ouro-2.6B, the first pipeline stage of a looped decoder: the Program
through the repo's public builder, seeded documents, FLOPs per example,
and the plain reference.

The reference is written from the equations in
`paddle_tpu/models/ouro.py`'s docstring (the model's public `config.json`;
the public `modeling_ouro.py` and arXiv:2510.25741 for what the config
leaves open, listed under `assumed` in the configuration file) in float32
`jax.numpy`. It shares nothing with `paddle_tpu`'s lowerings but the
parameters' names:

- The loop is a Python loop over the steps and, inside it, over the
  layers, reading the same entries of the parameter dict at every step,
  so `jax.grad` of it sums a weight's gradient over its uses by itself.
- Attention is plain softmax over an explicit causal mask, in blocks of
  queries so that the float32 scores of 16 heads x 512 x 4,096 (0.13 GB)
  fit beside the state the device holds during the set-up check.
- Positions are the rotate-half form written out with a concatenation;
  the program rolls the lanes and folds the sign into the sine.
- The exit gate is a matrix-vector product and the distribution over the
  exits a running product, term by term as the paper writes it.
- The share is the program's: ids, logits and the losses are over the
  slice of the vocabulary, and the layers are the published ones from
  `first_layer_held`.
"""

from __future__ import annotations

import math

from benchmark.harness.datagen import zipf_ids

SCORED_SEQUENCES = 1  # the harness compares logits on this many sequences
SCORED_EVERY = 16  # ... at every sixteenth position of each, at every step
QUERY_BLOCK = 512  # the reference's attention, queries a block

# |program - reference| on the scored logits of all four steps over the
# reference's own root-mean-square, and on the loss; each limit lies
# between two readings on the chip (PERF.md section 6, PR 57, has every
# one). The program computes its matrix products in bf16 with float32
# accumulation and keeps its activations, the residual stream among them,
# in bf16; the norms' statistics, the rotary angles, the softmaxes, the
# gate and the loss's sums are float32. The stream passes through 24 layer
# applications before the last exit and every output norm puts a
# sublayer's rounding back at unit scale, so the error grows with the
# step: 1.0, 1.3, 1.6 and 1.9% at the four exits, 1.36-1.67% over the
# four together and 0.0001-0.0019 on the loss over sixteen seeds. The reference
# with its matrices rounded to fp8 (e4m3), the nearest precision below,
# reads 21.3-21.5% on the logits (11.8% at the first step, 28.8% at the
# last) and 0.0135-0.0226 on the loss: either limit refuses it. The wrong
# models of `WRONG` that change the stack read 32.5-36.6% (no positions,
# the mildest), 69-71% (a layer left out), 87-93% (no norm between the
# steps: the first step's logits are the right model's, the later ones
# 74-125% off), 111% (no output norms) and 119-121% (weights of its own a
# step); the three that change the loss alone leave the logits at the
# program's reading and move the loss by 0.092-0.126 (no entropy term),
# 0.097-1.24 (the last exit's mass lost) and 0.25-0.30 (the last exit
# alone). The logits' limit, 5%, leaves 3.0 times the program's largest
# reading of room, since fresh seeds read higher, and has the fp8
# reference 4.3 times and the mildest wrong model 6.5 times above it. The
# loss's limit, 0.005, is the geometric mean of the program's largest
# reading and the fp8 reference's smallest, 2.6 and 2.7 times from them,
# and the mildest wrong loss reads 18 times it: the loss carries the
# check for those three. The model hands its loss back in float32, so it
# is no step of bf16 near ln 6,144; what it reads is the logits' own
# error, which differs by seed.
TOLERANCE = {"logits_rel_rms": 0.05, "loss_abs": 0.005}

# what `reference(wrong=...)` can be made to get wrong, for the tests and
# the chip readings that place the limits: three wrong models of the
# stack, which the logits show, then three of the loss, which the loss
# alone shows, then one of the mixer
WRONG = ("pre_norm_only", "no_norm_between_steps", "fresh_weights_a_step",
         "last_exit_only", "gate_mass_lost", "no_entropy", "no_rope")


def held_layers(model: dict) -> list[int]:
    """Published index of each layer held."""
    first = model["first_layer_held"]
    return list(range(first, first + model["num_hidden_layers"]))


def config(model: dict):
    from paddle_tpu.models.ouro import OuroConfig

    return OuroConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        num_hidden_layers=model["num_hidden_layers_published"],
        first_layer=model["first_layer_held"],
        layers_held=model["num_hidden_layers"],
        num_attention_heads=model["num_attention_heads"],
        num_key_value_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"],
        intermediate_size=model["intermediate_size"],
        rope_theta=model["rope_theta"], rms_norm_eps=model["rms_norm_eps"],
        total_ut_steps=model["total_ut_steps"],
        entropy_weight=model["entropy_weight"],
        initializer_range=model["initializer_range"],
        embedding_initializer_range=model["embedding_initializer_range"])


def build(model: dict, traffic: dict) -> dict:
    """Declare the training program in the current default programs.
    `check` names what the reference check fetches from the `for_test`
    clone: the loss and one array of scored logits, every step's at every
    `SCORED_EVERY`-th position, step after step along the positions' axis
    (`[rows, total_ut_steps * s / SCORED_EVERY, vocab]`), so that a fault
    in one step cannot hide behind the last. The train step fetches the
    loss alone, so the array is dead code there. `terms`: the expected
    cross-entropy under the exit distribution and the mean entropy."""
    from paddle_tpu import layers
    from paddle_tpu.models.ouro import build_ouro

    b, s = traffic["batch"], traffic["seq_len"]
    if s % SCORED_EVERY:
        raise ValueError(f"seq_len {s}: no multiple of {SCORED_EVERY}, so "
                         "the steps' scored positions would not line up")
    handles = build_ouro(config(model), b, s)
    # one stride over the steps laid end to end along the positions
    scored = layers.strided_slice(
        layers.concat(handles["step_logits"], axis=1), axes=[0, 1],
        starts=[0, 0],
        ends=[min(b, SCORED_SEQUENCES), len(handles["step_logits"]) * s],
        strides=[1, SCORED_EVERY])
    return {"loss": handles["loss"].name, "feeds": handles["feeds"],
            "check": [handles["loss"].name, scored.name],
            "terms": [handles["task_loss"].name, handles["entropy"].name],
            "loads": []}


def make_batch(rng, model: dict, traffic: dict) -> dict:
    """One document a row, `seq_len` tokens and the token after each as its
    label: no padding, no packing, every position scored. Ids are
    Zipf(1.1) over the rows of the vocabulary held here."""
    b, s = traffic["batch"], traffic["seq_len"]
    doc = zipf_ids(rng, (b, s + 1), model["vocab_size"])
    return {"tokens": doc[:, :-1].copy(), "labels": doc[:, 1:].copy()}


def tokens_per_example(model: dict, traffic: dict) -> int:
    return traffic["seq_len"]


def layer_matrix_params(model: dict) -> int:
    """Weights of the matrix products of one layer: q, k, v, o and the
    three of the feed-forward."""
    h = model["hidden_size"]
    hd = model["num_attention_heads"] * model["head_dim"]
    kvd = model["num_key_value_heads"] * model["head_dim"]
    return h * (hd + 2 * kvd) + hd * h + 3 * h * model["intermediate_size"]


def matrix_params_per_token(model: dict) -> int:
    """Weights of the matrix products one token passes through in a train
    step: `total_ut_steps` applications of each layer held and as many of
    the head's slice (untied: the embedding's gather is no product; the
    gate's 2,048 weights are no matrix)."""
    return model["total_ut_steps"] * (
        model["num_hidden_layers"] * layer_matrix_params(model)
        + model["hidden_size"] * model["vocab_size"])


def admitted_pairs(s: int) -> int:
    """(query, key) pairs of one head that the causal mask admits over a
    row of `s` tokens."""
    return s * (s + 1) // 2


def flops_per_example(model: dict, traffic: dict) -> float:
    """Matrix-product FLOPs forward and backward (3 x forward) for one
    document, from the shapes: two a weight a token
    (`matrix_params_per_token`: every layer and the head once a step of
    the loop) and, for each of the `total_ut_steps` x layers attention
    calls, the scores and the values of only the pairs the mask admits (2
    x head_dim each a pair a head). The embedding gather, the norms, the
    rotation, the gate, the losses and the optimizer do not count."""
    s = traffic["seq_len"]
    calls = model["total_ut_steps"] * model["num_hidden_layers"]
    attn = (calls * admitted_pairs(s) * model["num_attention_heads"]
            * 4 * model["head_dim"])
    return 3.0 * (2 * s * matrix_params_per_token(model) + attn)


# ------------------------------------------------------------ reference


def _rms(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _silu(x):
    import jax

    return x * jax.nn.sigmoid(x)


def _ffn(p, u, name):
    return (_silu(u @ p[name + ".gate.w_0"]) * (u @ p[name + ".up.w_0"])
            ) @ p[name + ".down.w_0"]


def _rope(x, theta):
    """x: [b, s, heads, d], positions 0..s-1, rotate-half:
    `x * cos + [-x2, x1] * sin`, the angles of the first half repeated."""
    import jax.numpy as jnp

    s, d = x.shape[1], x.shape[3]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], -1)[None, :, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(angle) + turned * jnp.sin(angle)


def attention_mixer(p, u, name, model, wrong=()):
    """u: [b, s, hidden] -> [b, s, hidden]: full causal, a key/value head
    to every query head."""
    import jax
    import jax.numpy as jnp

    h, g, d = (model["num_attention_heads"], model["num_key_value_heads"],
               model["head_dim"])
    b, s, _ = u.shape
    q = (u @ p[name + ".q.w_0"]).reshape(b, s, h, d)
    k = (u @ p[name + ".k.w_0"]).reshape(b, s, g, d)
    v = (u @ p[name + ".v.w_0"]).reshape(b, s, g, d)
    if "no_rope" not in wrong:
        q, k = _rope(q, model["rope_theta"]), _rope(k, model["rope_theta"])
    kv_of = jnp.arange(h) // (h // g)
    k, v = k[:, :, kv_of], v[:, :, kv_of]
    out = []
    for lo in range(0, s, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, s)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi])
        scores = scores / math.sqrt(d)
        visible = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        scores = jnp.where(visible, scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                              v[:, :hi]))
    return jnp.concatenate(out, 1).reshape(b, s, h * d) @ p[name + ".o.w_0"]


def sandwich_block(p, h, name, model, wrong=()):
    """One layer on the stream `h`: each sublayer normed on its way in
    and on its way out."""
    eps = model["rms_norm_eps"]

    def out(y, which):
        if "pre_norm_only" in wrong:
            return y
        return _rms(y, p[f"{name}.{which}_2.w_0"], eps)

    a = attention_mixer(p, _rms(h, p[name + ".input_norm.w_0"], eps),
                        name + ".attn", model, wrong)
    h = h + out(a, "input_norm")
    f = _ffn(p, _rms(h, p[name + ".post_attn_norm.w_0"], eps), name + ".mlp")
    return h + out(f, "post_attn_norm")


def _fresh(p, step, std):
    """`p` with every matrix of the layers drawn anew for `step`, at the
    seeding's deviation: what a model without the loop's sharing holds."""
    import zlib

    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(17), step)
    return {n: (std * jax.random.normal(
        jax.random.fold_in(key, zlib.crc32(n.encode()) & 0x7FFFFFFF),
        v.shape, v.dtype) if ".layer" in n and v.ndim == 2 else v)
        for n, v in p.items()}


def exit_distribution(lams, wrong=()):
    """`p_t = lam_t S_{t-1}` for t < T and `p_T = S_{T-1}` from the T
    gates `lams` (the last one's value is in no term)."""
    ps, survived = [], 1.0
    for lam in lams[:-1]:
        ps.append(lam * survived)
        survived = survived * (1.0 - lam)
    return ps + [lams[-1] * survived if "gate_mass_lost" in wrong
                 else survived]


def reference(p: dict, batch: dict, model: dict, drop_layers: int = 0,
              wrong=()):
    """Forward pass on some rows of a batch. Returns the sum over the
    positions of the loss's per-token term, the count of positions, and
    the logits of every step at every `SCORED_EVERY`-th position,
    `[rows, total_ut_steps, s / SCORED_EVERY, vocab]`. `drop_layers`
    leaves out that many of the last layers (at every step) and `wrong`
    names departures of `WRONG` (no output norms; the next step reading
    the stream before the final norm; a step with weights of its own; the
    loss of the last exit alone; the last exit's mass multiplied by its
    gate, so that the distribution no longer sums to 1; no entropy term;
    no positions): the tests and the chip readings use them to show that
    a wrong model is caught."""
    import jax
    import jax.numpy as jnp

    eps = model["rms_norm_eps"]
    layers = held_layers(model)
    layers = layers[:len(layers) - drop_layers]
    labels = batch["labels"][..., None]
    x = p["ouro.embed"][batch["tokens"]]
    logits, nlls, lams = [], [], []
    for t in range(model["total_ut_steps"]):
        p_t = (_fresh(p, t, model["initializer_range"])
               if t and "fresh_weights_a_step" in wrong else p)
        h = x
        for l in layers:
            h = sandwich_block(p_t, h, f"ouro.layer{l}", model, wrong)
        x = _rms(h, p["ouro.final_norm.w_0"], eps)
        z = x @ p["ouro.head.w_0"]
        logits.append(z)
        nlls.append(-jnp.take_along_axis(
            jax.nn.log_softmax(z, -1), labels, -1)[..., 0])
        lams.append(jax.nn.sigmoid(x @ p["ouro.exit_gate.w_0"]
                                   + p["ouro.exit_gate.b_0"][0]))
        if "no_norm_between_steps" in wrong:
            x = h
    if "last_exit_only" in wrong:
        per_token = nlls[-1]
    else:
        ps = exit_distribution(lams, wrong)
        per_token = sum(q * nll for q, nll in zip(ps, nlls))
        if "no_entropy" not in wrong:
            per_token = per_token + model["entropy_weight"] * sum(
                q * jnp.log(q) for q in ps)
    return (jnp.sum(per_token), jnp.asarray(per_token.size, jnp.float32),
            jnp.stack(logits, 1)[:, :, ::SCORED_EVERY])
