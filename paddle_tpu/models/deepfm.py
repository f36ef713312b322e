"""DeepFM + Wide&Deep CTR models (the reference's CTR workloads:
`unittests/dist_ctr.py`, `incubate/fleet/tests/fleet_deep_ctr.py`).

Sparse slots are dense [batch, max_len] int64 id arrays (padding id 0 —
LoD → padded, SURVEY.md §5); embedding bags are mean-pooled over the slot
the way `fused_embedding_seq_pool` / sequence_pool over LoD works in the
reference (operators/fused/fused_embedding_seq_pool_op.cc)."""

from __future__ import annotations

from .. import initializer, layers
from ..param_attr import ParamAttr

__all__ = ["deepfm", "wide_and_deep", "ctr_dnn"]


def _slot_embed(slot, vocab_size, dim, name, pooled=True):
    """Embed one sparse slot [b, L] -> [b, dim] (mean over non-pad ids)."""
    emb = layers.embedding(
        slot,
        size=[vocab_size, dim],
        is_sparse=True,
        padding_idx=0,
        param_attr=ParamAttr(
            name=name, initializer=initializer.Uniform(-0.05, 0.05)
        ),
    )  # [b, L, dim] — or [b, dim] for width-1 slots (trailing 1 squeezed)
    if not pooled or len(emb.shape) == 2:
        # single-id slot: the "bag" is the embedding itself (padding_idx=0
        # already zeroes missing ids)
        return emb
    mask = layers.cast(
        layers.not_equal(slot, layers.zeros_like(slot)), "float32"
    )
    denom = layers.clip(
        layers.reduce_sum(mask, dim=[1], keep_dim=True), 1.0, 1e30
    )
    summed = layers.reduce_sum(
        emb * layers.unsqueeze(mask, [2]), dim=[1]
    )
    return summed / denom


def deepfm(
    sparse_slots,
    dense_input=None,
    label=None,
    vocab_size=1000001,
    embedding_dim=9,
    fc_sizes=(400, 400, 400),
):
    """DeepFM: y = sigmoid(first_order + fm_second_order + dnn).

    sparse_slots: list of [b, L] int64 vars (one per feature field).
    Returns (predict, avg_loss, auc_var) when label given, else predict.
    """
    # first-order: per-field scalar embedding
    first = [
        _slot_embed(s, vocab_size, 1, f"fm_first_{i}")
        for i, s in enumerate(sparse_slots)
    ]
    y_first = layers.sums(first)  # [b, 1]

    # second-order: shared k-dim embeddings; FM identity
    # 0.5 * ((sum v)^2 - sum v^2)
    embs = [
        _slot_embed(s, vocab_size, embedding_dim, f"fm_second_{i}")
        for i, s in enumerate(sparse_slots)
    ]
    sum_v = layers.sums(embs)  # [b, k]
    sum_v_sq = sum_v * sum_v
    sq_sum = layers.sums([e * e for e in embs])
    y_second = 0.5 * layers.reduce_sum(
        sum_v_sq - sq_sum, dim=[1], keep_dim=True
    )

    # deep: concat field embeddings (+ dense features) -> MLP
    deep_in = layers.concat(embs, axis=1)
    if dense_input is not None:
        deep_in = layers.concat([deep_in, dense_input], axis=1)
    h = deep_in
    for i, sz in enumerate(fc_sizes):
        h = layers.fc(h, sz, act="relu")
    y_deep = layers.fc(h, 1)

    logit = y_first + y_second + y_deep
    predict = layers.sigmoid(logit)
    if label is None:
        return predict

    label_f = layers.cast(label, "float32")
    loss = layers.sigmoid_cross_entropy_with_logits(logit, label_f)
    avg_loss = layers.mean(loss)
    two_class = layers.concat([1.0 - predict, predict], axis=1)
    auc_var, _batch_auc, _states = layers.auc(two_class, label)
    return predict, avg_loss, auc_var


def wide_and_deep(
    sparse_slots,
    dense_input=None,
    label=None,
    vocab_size=1000001,
    embedding_dim=16,
    fc_sizes=(256, 128, 64),
):
    """Wide & Deep: linear (wide) part over ids + DNN (deep) part."""
    wide = [
        _slot_embed(s, vocab_size, 1, f"wide_{i}")
        for i, s in enumerate(sparse_slots)
    ]
    y_wide = layers.sums(wide)

    embs = [
        _slot_embed(s, vocab_size, embedding_dim, f"deep_emb_{i}")
        for i, s in enumerate(sparse_slots)
    ]
    deep_in = layers.concat(embs, axis=1)
    if dense_input is not None:
        deep_in = layers.concat([deep_in, dense_input], axis=1)
    h = deep_in
    for sz in fc_sizes:
        h = layers.fc(h, sz, act="relu")
    y_deep = layers.fc(h, 1)

    logit = y_wide + y_deep
    predict = layers.sigmoid(logit)
    if label is None:
        return predict
    label_f = layers.cast(label, "float32")
    avg_loss = layers.mean(
        layers.sigmoid_cross_entropy_with_logits(logit, label_f)
    )
    two_class = layers.concat([1.0 - predict, predict], axis=1)
    auc_var, _batch_auc, _states = layers.auc(two_class, label)
    return predict, avg_loss, auc_var


def ctr_dnn(sparse_slots, label=None, vocab_size=1000001, embedding_dim=10,
            fc_sizes=(128, 64, 32), show_click=None, dense_input=None,
            use_data_norm=False):
    """The plain CTR DNN of dist_ctr.py / fleet_deep_ctr.py: embedding-bag
    per slot -> concat -> MLP -> softmax over 2 classes.

    show_click: optional [b, 2] show/click tensor — prepended to each
    slot embedding and passed through `continuous_value_model`
    (cvm_op.cc), the fleet_deep_ctr pattern. dense_input with
    use_data_norm=True normalizes dense features by the accumulated batch
    stats (data_norm_op.cc)."""
    embs = [
        _slot_embed(s, vocab_size, embedding_dim, f"ctr_emb_{i}")
        for i, s in enumerate(sparse_slots)
    ]
    if show_click is not None:
        embs = [
            layers.continuous_value_model(
                layers.concat([show_click, e], axis=1), show_click
            )
            for e in embs
        ]
    if dense_input is not None:
        d = (layers.data_norm(dense_input, name="ctr_dense_dn")
             if use_data_norm else dense_input)
        embs = embs + [d]
    h = layers.concat(embs, axis=1)
    for sz in fc_sizes:
        h = layers.fc(h, sz, act="relu")
    predict = layers.fc(h, 2, act="softmax")
    if label is None:
        return predict
    loss = layers.mean(layers.cross_entropy(predict, label))
    auc_var, _batch_auc, _states = layers.auc(predict, label)
    return predict, loss, auc_var
