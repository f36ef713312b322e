"""Model zoo smoke + convergence tests (reference: tests/book/)."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models import bert as bert_mod
from paddle_tpu.models.resnet import resnet


def test_resnet18_forward_backward():
    img = fluid.layers.data("img", [3, 32, 32])
    label = fluid.layers.data("label", [1], dtype="int64")
    pred, loss, acc1, acc5 = resnet(img, label, depth=18, class_num=10)
    # lr 0.05: 0.1 genuinely diverges on this 4-sample batch (measured
    # 2.39 -> 2.77 -> 9.2 -> 20.8 across repeats of the same batch; 0.05
    # converges 2.39 -> 0.74 -> 0.22) — the old value sat on the
    # stability knife edge and flipped with XLA CPU conv rounding
    fluid.optimizer.Momentum(0.05, 0.9).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    x = rng.randn(4, 3, 32, 32).astype("float32")
    y = rng.randint(0, 10, (4, 1)).astype("int64")
    l1 = exe.run(feed={"img": x, "label": y}, fetch_list=[loss])[0]
    l2 = exe.run(feed={"img": x, "label": y}, fetch_list=[loss])[0]
    assert np.isfinite(l1).all() and np.isfinite(l2).all()
    assert float(l2[0]) < float(l1[0])  # same batch twice -> loss drops


def _bert_batch(rng, cfg, b, s):
    ids = rng.randint(0, cfg.vocab_size, (b, s)).astype("int64")
    seg = rng.randint(0, cfg.type_vocab_size, (b, s)).astype("int64")
    pos = np.tile(np.arange(s), (b, 1)).astype("int64")
    mask = np.ones((b, s), dtype="float32")
    mlm_label = rng.randint(0, cfg.vocab_size, (b, s)).astype("int64")
    mlm_w = (rng.rand(b, s) < 0.15).astype("float32")
    nsp = rng.randint(0, 2, (b, 1)).astype("int64")
    return {
        "src_ids": ids, "sent_ids": seg, "pos_ids": pos, "input_mask": mask,
        "mask_label": mlm_label, "mask_weight": mlm_w, "nsp_label": nsp,
    }


def test_bert_tiny_trains():
    cfg = bert_mod.BertConfig.tiny()
    b, s = 4, 16
    h = bert_mod.build_bert_pretrain(cfg, b, s)
    fluid.optimizer.Adam(1e-3).minimize(h["loss"])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = _bert_batch(rng, cfg, b, s)
    losses = []
    for _ in range(8):
        (lv,) = exe.run(feed=feed, fetch_list=[h["loss"]])
        losses.append(float(lv[0]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]  # same batch memorization


def test_bert_padding_mask_ignores_pad_tokens():
    cfg = bert_mod.BertConfig.tiny()
    b, s = 2, 8
    h = bert_mod.build_bert_pretrain(cfg, b, s, is_test=True, mlm_only=True)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(1)
    feed = _bert_batch(rng, cfg, b, s)
    del feed["nsp_label"]
    (h1,) = exe.run(feed=feed, fetch_list=[h["hidden"]])
    # changing ids in fully-masked (pad) positions must not change unmasked rows
    feed2 = {k: v.copy() for k, v in feed.items()}
    feed2["input_mask"][:, -3:] = 0.0
    (base,) = exe.run(feed=feed2, fetch_list=[h["hidden"]])
    feed3 = {k: v.copy() for k, v in feed2.items()}
    feed3["src_ids"][:, -3:] = 1  # perturb pad tokens
    (pert,) = exe.run(feed=feed3, fetch_list=[h["hidden"]])
    np.testing.assert_allclose(base[:, :-3], pert[:, :-3], atol=1e-5)


def test_bert_tp_specs_annotated():
    cfg = bert_mod.BertConfig.tiny()
    h = bert_mod.build_bert_pretrain(cfg, 2, 8)
    specs = fluid.default_main_program()._sharding_specs
    assert any(".qkv.w_0" in k or ".q.w_0" in k for k in specs)
    assert any(".ffn1.w_0" in k for k in specs)
    # tied MLM head reuses the embedding table (no mlm.out.w_0 param);
    # the untied form keeps its tp annotation
    cfg2 = bert_mod.BertConfig.tiny()
    cfg2.tie_mlm_weights = False
    fluid.framework.switch_main_program(fluid.Program())
    fluid.framework.switch_startup_program(fluid.Program())
    fluid.framework.unique_name.switch()
    bert_mod.build_bert_pretrain(cfg2, 2, 8)
    specs2 = fluid.default_main_program()._sharding_specs
    assert any("mlm.out.w_0" in k for k in specs2)


# ~55 s — slow-marked for tier-1 headroom (round 11); covered by the
# tools/ci.sh slow-model stage instead
@pytest.mark.slow
def test_se_resnext_trains_and_dp_equivalence():
    """SE-ResNeXt (reference dist_se_resnext.py workload): a slimmed
    variant trains single-device, and the SAME build under
    with_data_parallel on the dp mesh produces loss-equivalent steps —
    the reference's ParallelExecutor seresnext comparison."""
    from paddle_tpu.framework import Program
    from paddle_tpu.models.se_resnext import se_resnext

    rng = np.random.RandomState(0)
    b = 8
    x = rng.rand(b, 3, 32, 32).astype("float32")
    y = rng.randint(0, 10, (b, 1)).astype("int64")

    def build():
        main, startup = Program(), Program()
        main.random_seed = 6
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                img = fluid.layers.data("img", [b, 3, 32, 32],
                                        append_batch_size=False)
                label = fluid.layers.data("label", [b, 1], dtype="int64",
                                          append_batch_size=False)
                # depth 26 (one block per stage): deep-50 stacks ~53
                # BNs whose reduction-order noise amplifies chaotically
                # across steps, making cross-partitioning equivalence
                # meaningless; 26 exercises the same SE/grouped/BN paths
                pred, loss, acc = se_resnext(
                    img, label, depth=26, cardinality=4,
                    reduction_ratio=4, class_num=10)
                fluid.optimizer.Momentum(0.005, 0.9).minimize(loss)
        return main, startup, loss

    def run(compiled_wrap):
        main, startup, loss = build()
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        prog = (fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name) if compiled_wrap else main)
        with fluid.scope_guard(scope):
            exe.run(startup)
            return [
                float(np.asarray(exe.run(
                    prog, feed={"img": x, "label": y},
                    fetch_list=[loss])[0]).reshape(-1)[0])
                for _ in range(6)
            ]

    single = run(False)
    assert np.isfinite(single).all()
    assert min(single[1:]) < single[0], single
    parallel = run(True)
    # BN + SE + grouped convs amplify reduction-order float noise over
    # steps; compare the early steps tightly and the tail loosely
    np.testing.assert_allclose(single[:3], parallel[:3], rtol=2e-3,
                               atol=1e-5)
    np.testing.assert_allclose(single, parallel, rtol=8e-2, atol=1e-4)


def test_phi4_flash_tiny_builds_and_trains():
    """The stage that straddles Phi-4-mini-flash's two decoders at a tiny
    size: a Mamba layer, window attention, the Mamba layer whose scan is
    the memory, the full layer whose keys and values are shared, a memory
    unit and a cross layer, through `Executor.run`."""
    from paddle_tpu.models import Phi4FlashConfig, build_phi4_flash

    cfg = Phi4FlashConfig(
        vocab_size=96, hidden_size=32, first_layer=14, layers_held=6,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=64,
        sliding_window=8, mamba_d_state=4)
    assert [cfg.layer_kind(l) for l in range(14, 20)] == [
        "mamba", "window", "mamba", "full", "gmu", "cross"]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        handles = build_phi4_flash(cfg, 2, 24)
        fluid.optimizer.Adam(learning_rate=1e-2).minimize(handles["loss"])
    assert handles["feeds"] == ["tokens", "labels"] and handles["loads"] == []
    assert tuple(handles["logits"].shape) == (2, 24, 96)
    ops = [op.type for op in main.global_block().ops]
    assert ops.count("selective_scan") == 2
    assert ops.count("fused_multihead_attention") == 6
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        doc = np.random.RandomState(0).randint(0, 96, (2, 25))
        feed = {"tokens": doc[:, :-1], "labels": doc[:, 1:]}
        losses = [float(np.asarray(exe.run(
            main, feed=feed, fetch_list=[handles["loss"]])[0]).reshape(-1)[0])
            for _ in range(8)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.5


def test_lfm2_tiny_builds_and_trains():
    """One chip's share of LFM2 at a tiny size: the second leading dense
    layer (a convolution layer), then an attention layer and three
    convolution layers with experts, through `Executor.run`; the tied
    table is one parameter."""
    from paddle_tpu.models import Lfm2Config, build_lfm2

    cfg = Lfm2Config(
        vocab_size=96, hidden_size=32, first_layer=1, dense_layers=1,
        layer_types=["conv", "full_attention", "conv", "conv", "conv"],
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=64,
        moe_intermediate_size=16, num_experts=8, experts_held=4,
        num_experts_per_token=2, router_bias_scale=0.02)
    assert (cfg.head_dim, cfg.conv_L_cache) == (8, 3)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        handles = build_lfm2(cfg, 2, 24)
        fluid.optimizer.Adam(learning_rate=1e-2).minimize(handles["loss"])
    assert handles["feeds"] == ["tokens", "labels"]
    assert len(handles["loads"]) == 4
    assert tuple(handles["logits"].shape) == (2, 24, 96)
    ops = [op.type for op in main.global_block().ops]
    assert ops.count("short_conv1d") == 4
    assert ops.count("fused_multihead_attention") == 1
    assert ops.count("moe_experts") == 4
    names = [p.name for p in main.global_block().all_parameters()]
    assert names.count("lfm2.embed") == 1 and "lfm2.head.w_0" not in names
    assert "lfm2.layer1.mlp.gate.w_0" in names  # the dense layer held
    assert "lfm2.layer2.attn.q_norm.w_0" in names
    assert "lfm2.layer5.conv.conv.w_0" in names
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        doc = np.random.RandomState(0).randint(0, 96, (2, 25))
        feed = {"tokens": doc[:, :-1], "labels": doc[:, 1:]}
        out = [exe.run(main, feed=feed,
                       fetch_list=[handles["loss"]] + handles["loads"])
               for _ in range(8)]
    losses = [float(np.asarray(o[0]).reshape(-1)[0]) for o in out]
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.5
    # 2 x 24 tokens x 2 a token over 4 of 8 experts: some are held here
    assert all(0 < int(np.sum(x)) <= 96 for x in out[0][1:])


def test_qwen3_next_tiny_builds_and_trains():
    """One chip's share of Qwen3-Next at a tiny size: published layers 4
    to 7 (three Gated DeltaNet layers over two key heads and four value
    heads, then gated attention with a quarter of each head turned), every
    layer an expert layer with a gated shared expert, through
    `Executor.run`; embedding and head are two parameters."""
    from paddle_tpu.models import Qwen3NextConfig, build_qwen3_next

    cfg = Qwen3NextConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=4, first_layer=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=8,
        moe_intermediate_size=16, shared_expert_intermediate_size=16,
        num_experts=8, experts_held=4, num_experts_per_token=2)
    assert cfg.rotary_dim == 4 and cfg.num_shared_experts == 1
    assert [k for _, k in cfg.layer_kinds()] == (
        ["linear_attention"] * 3 + ["full_attention"])
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        handles = build_qwen3_next(cfg, 2, 24)
        fluid.optimizer.Adam(learning_rate=1e-2).minimize(handles["loss"])
    assert handles["feeds"] == ["tokens", "labels"]
    assert len(handles["loads"]) == 4
    assert tuple(handles["logits"].shape) == (2, 24, 96)
    ops = [op.type for op in main.global_block().ops]
    assert ops.count("short_conv1d") == 3  # q, k and v in one call a layer
    assert ops.count("kda_attention") == 3
    assert ops.count("fused_multihead_attention") == 1
    assert ops.count("moe_experts") == 4
    names = [p.name for p in main.global_block().all_parameters()]
    assert "qwen3next.embed" in names and "qwen3next.head.w_0" in names
    assert "qwen3next.layer4.gdn.A_log" in names
    assert "qwen3next.layer7.attn.gate.w_0" in names
    assert "qwen3next.layer5.shared_gate.w_0" in names
    block = main.global_block()
    assert tuple(block.var("qwen3next.layer4.gdn.dt_bias").shape) == (4,)
    assert tuple(block.var("qwen3next.layer4.gdn.conv.w_0").shape) == (64, 4)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        doc = np.random.RandomState(0).randint(0, 96, (2, 25))
        feed = {"tokens": doc[:, :-1], "labels": doc[:, 1:]}
        out = [exe.run(main, feed=feed,
                       fetch_list=[handles["loss"]] + handles["loads"])
               for _ in range(8)]
    losses = [float(np.asarray(o[0]).reshape(-1)[0]) for o in out]
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.5
    # 2 x 24 tokens x 2 a token over 4 of 8 experts: some are held here
    assert all(0 < int(np.sum(x)) <= 96 for x in out[0][1:])


def test_nemotron_h_tiny_builds_and_trains():
    """One chip's share of Nemotron-H at a tiny size: published blocks 7
    to 11 (`*EMEM`: attention with no positions, a latent expert layer
    with ungated experts, Mamba-2 with two of its heads in one group),
    each block one mixer behind one norm, through `Executor.run`;
    embedding and head are two parameters."""
    from paddle_tpu.models import NemotronHConfig, build_nemotron_h

    cfg = NemotronHConfig(
        vocab_size=96, hidden_size=32, hybrid_override_pattern="*EMEM",
        first_layer=7, mamba_num_heads=2, mamba_head_dim=8, mamba_n_groups=1,
        ssm_state_size=8, mamba_chunk_size=8, num_attention_heads=4,
        num_key_value_heads=2, head_dim=8, moe_intermediate_size=16,
        moe_latent_size=16, moe_shared_expert_intermediate_size=32,
        num_experts=8, experts_held=4, num_experts_per_token=3,
        initializer_range=0.1, rescale_prenorm_residual=False)
    assert cfg.num_shared_experts == 2 and cfg.out_std is None
    assert cfg.layer_kinds() == [(7, "attention"), (8, "experts"),
                                 (9, "mamba2"), (10, "experts"),
                                 (11, "mamba2")]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        handles = build_nemotron_h(cfg, 2, 24)
        fluid.optimizer.Adam(learning_rate=1e-2).minimize(handles["loss"])
    assert handles["feeds"] == ["tokens", "labels"]
    assert len(handles["loads"]) == 2
    assert tuple(handles["logits"].shape) == (2, 24, 96)
    ops = [op.type for op in main.global_block().ops]
    assert ops.count("short_conv1d") == 2  # x, B and C in one call a block
    assert ops.count("ssd_scan") == ops.count("ssd_scan_grad") == 2
    assert ops.count("fused_multihead_attention") == 1
    assert ops.count("moe_experts") == 2
    assert ops.count("rms_norm") == 5 + 2 + 1  # a block, a mixer's, final
    names = [p.name for p in main.global_block().all_parameters()]
    assert "nemotron.embed" in names and "nemotron.head.w_0" in names
    assert "nemotron.layer9.mamba.A_log" in names
    assert "nemotron.layer8.latent_in.w_0" in names
    assert not any(n.endswith("w_gate") or "q_norm" in n for n in names)
    block = main.global_block()
    assert tuple(block.var("nemotron.layer9.mamba.in_proj.w_0").shape) == (
        32, 2 * 16 + 2 * 8 + 2)
    assert tuple(block.var("nemotron.layer9.mamba.conv.w_0").shape) == (32, 4)
    assert tuple(block.var("nemotron.layer8.moe.gate").shape) == (32, 8)
    assert tuple(block.var("nemotron.layer8.moe.w_up").shape) == (4, 16, 16)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        doc = np.random.RandomState(0).randint(0, 96, (2, 25))
        feed = {"tokens": doc[:, :-1], "labels": doc[:, 1:]}
        out = [exe.run(main, feed=feed,
                       fetch_list=[handles["loss"]] + handles["loads"])
               for _ in range(8)]
    losses = [float(np.asarray(o[0]).reshape(-1)[0]) for o in out]
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.5
    # 2 x 24 tokens x 3 a token over 4 of 8 experts: some are held here
    assert all(0 < int(np.sum(x)) <= 144 for x in out[0][1:])
