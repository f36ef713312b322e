"""Data-parallel equivalence over the virtual 8-device CPU mesh — the
reference's single-vs-multi-device loss comparison pattern
(unittests/parallel_executor_test_base.py; SURVEY.md §4 implication b)."""

import numpy as np

import jax
import paddle_tpu as fluid
from paddle_tpu.framework import Program


def _build(main, startup, lr=0.1, seed=123):
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data("x", [16])
            y = fluid.layers.data("y", [1])
            h = fluid.layers.fc(
                x, 32, act="relu",
                param_attr=fluid.initializer.Constant(0.05),
            )
            pred = fluid.layers.fc(
                h, 1, param_attr=fluid.initializer.Constant(0.1),
            )
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y)
            )
            fluid.optimizer.SGD(lr).minimize(loss)
    return loss


def test_eight_devices_available():
    assert len(jax.devices()) == 8, jax.devices()


def test_dp_matches_single_device():
    rng = np.random.RandomState(3)
    w_true = rng.randn(16, 1).astype("float32")
    batches = []
    for _ in range(10):
        xv = rng.randn(64, 16).astype("float32")
        yv = xv @ w_true
        batches.append((xv, yv))

    # single device
    main1, startup1 = Program(), Program()
    loss1 = _build(main1, startup1)
    scope1 = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope1):
        exe.run(startup1)
        losses_single = [
            float(
                exe.run(main1, feed={"x": xv, "y": yv}, fetch_list=[loss1])[0][0]
            )
            for xv, yv in batches
        ]

    # 8-device data parallel via CompiledProgram (GSPMD mesh)
    main2, startup2 = Program(), Program()
    loss2 = _build(main2, startup2)
    scope2 = fluid.Scope()
    compiled = fluid.CompiledProgram(main2).with_data_parallel(
        loss_name=loss2.name
    )
    with fluid.scope_guard(scope2):
        exe.run(startup2)
        losses_dp = [
            float(
                exe.run(compiled, feed={"x": xv, "y": yv},
                        fetch_list=[loss2])[0][0]
            )
            for xv, yv in batches
        ]

    np.testing.assert_allclose(losses_single, losses_dp, rtol=1e-4, atol=1e-5)
    assert losses_single[-1] < losses_single[0]


def test_dp_param_sync_after_steps():
    rng = np.random.RandomState(5)
    main, startup = Program(), Program()
    loss = _build(main, startup)
    compiled = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name
    )
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(3):
            xv = rng.randn(32, 16).astype("float32")
            yv = rng.randn(32, 1).astype("float32")
            exe.run(compiled, feed={"x": xv, "y": yv}, fetch_list=[loss])
        # params must be fully replicated (one logical value) after updates
        for p in main.all_parameters():
            val = scope.get(p.name)
            assert np.asarray(val).shape == tuple(p.shape)


def _bert_steps(monkeypatch, places, zero1=False, steps=3):
    """Three Adam steps of a two-layer BERT-shaped program (hidden 128,
    two heads of 64, 64 rows of 64 positions, dropout off) under the
    Pallas interpreter: the losses, the updated parameters and the
    dispatch counters."""
    from paddle_tpu import profiler
    from paddle_tpu.models.bert import BertConfig, build_bert_pretrain

    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    cfg = BertConfig(vocab_size=128, hidden_size=128, num_layers=2,
                     num_heads=2, intermediate_size=256, max_position=64,
                     hidden_dropout=0.0, attention_dropout=0.0)
    b, s = 64, 64
    main, startup = Program(), Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        loss = build_bert_pretrain(cfg, b, s, mlm_only=True)["loss"]
        fluid.optimizer.Adam(1e-3).minimize(loss)
    rng = np.random.RandomState(11)
    feeds = [{
        "src_ids": rng.randint(0, cfg.vocab_size, (b, s)).astype("int64"),
        "sent_ids": rng.randint(0, 2, (b, s)).astype("int64"),
        "pos_ids": np.tile(np.arange(s), (b, 1)).astype("int64"),
        "input_mask": (rng.rand(b, s) > 0.1).astype("float32"),
        "mask_label": rng.randint(0, cfg.vocab_size, (b, s)).astype("int64"),
        "mask_weight": (rng.rand(b, s) < 0.2).astype("float32"),
    } for _ in range(steps)]
    for f in feeds:
        f["input_mask"][:, 0] = 1.0
    program = main
    if places:
        program = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, places=places, zero1=zero1)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    profiler.reset_profiler()
    with fluid.scope_guard(scope):
        exe.run(startup)
        losses = [float(np.asarray(
            exe.run(program, feed=f, fetch_list=[loss])[0]).reshape(-1)[0])
            for f in feeds]
        params = {p.name: np.asarray(scope.get(p.name))
                  for p in main.all_parameters()}
    return losses, params, profiler.counters()


def test_bert_on_a_batch_mesh_runs_the_kernels_per_shard(monkeypatch):
    """`with_data_parallel(places=4)` takes the Pallas kernels per shard
    of the batch (`pallas_on_mesh_calls`) and trains as one device does
    on the global batch; with `zero1=True`, which shards optimizer state
    and not activations, too."""
    want, want_params, one = _bert_steps(monkeypatch, None)
    assert one["attn_dispatch_short"] > 0
    assert not one.get("pallas_on_mesh_calls") and not one.get(
        "attn_dispatch_xla")
    assert want[-1] < want[0]
    for zero1 in (False, True):
        got, got_params, four = _bert_steps(monkeypatch, 4, zero1=zero1)
        assert four["attn_dispatch_short"] == one["attn_dispatch_short"]
        assert not four.get("attn_dispatch_xla")
        # the attention ops and the six LayerNorm backwards (two a layer,
        # the embedding's, the head's), each once per lowering
        assert four["pallas_on_mesh_calls"] == four["attn_dispatch_short"] + 6
        np.testing.assert_allclose(want, got, rtol=1e-4, atol=1e-5)
        for name, value in want_params.items():
            np.testing.assert_allclose(value, got_params[name], rtol=1e-4,
                                       atol=1e-5, err_msg=name)
