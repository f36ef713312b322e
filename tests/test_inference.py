"""Inference engine tests (reference: AnalysisPredictor api tests,
api_impl_tester.cc / analysis_predictor_tester.cc patterns)."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.inference import (
    AnalysisConfig,
    PaddleTensor,
    create_paddle_predictor,
)


def _train_and_export(tmp_path, steps=30):
    rng = np.random.RandomState(0)
    w_true = rng.randn(8, 1).astype("float32")
    x = fluid.layers.data("x", [8])
    y = fluid.layers.data("y", [1])
    h = fluid.layers.fc(x, 16, act="relu")
    pred = fluid.layers.fc(h, 1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.Adam(1e-2).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    for _ in range(steps):
        xv = rng.randn(32, 8).astype("float32")
        yv = xv @ w_true
        exe.run(feed={"x": xv, "y": yv}, fetch_list=[loss])
    d = str(tmp_path / "model")
    fluid.io.save_inference_model(d, ["x"], [pred], exe)
    # reference forward for comparison
    xv = rng.randn(4, 8).astype("float32")
    prog, feeds, fetches = fluid.io.load_inference_model(d, exe)
    ref = exe.run(prog, feed={feeds[0]: xv}, fetch_list=fetches)[0]
    return d, xv, np.asarray(ref)


def test_predictor_paddle_tensor_api(tmp_path):
    d, xv, ref = _train_and_export(tmp_path)
    config = AnalysisConfig()
    config.set_model(d)
    config.switch_ir_optim(True)
    config.enable_memory_optim()
    predictor = create_paddle_predictor(config)
    assert predictor.get_input_names() == ["x"]
    assert len(predictor.get_output_names()) == 1

    outs = predictor.run([PaddleTensor(xv, name="x")])
    np.testing.assert_allclose(outs[0].as_ndarray(), ref, atol=1e-6)


def test_predictor_zero_copy_api(tmp_path):
    d, xv, ref = _train_and_export(tmp_path)
    config = AnalysisConfig(model_dir=d)
    predictor = create_paddle_predictor(config)

    inp = predictor.get_input_handle("x")
    inp.copy_from_cpu(xv)
    predictor.zero_copy_run()
    out = predictor.get_output_handle(predictor.get_output_names()[0])
    np.testing.assert_allclose(out.copy_to_cpu(), ref, atol=1e-6)

    # repeated runs reuse the compiled executable (cache hit) and give
    # fresh results
    inp.copy_from_cpu(xv * 2.0)
    predictor.zero_copy_run()
    out2 = out.copy_to_cpu()
    assert not np.allclose(out2, ref)


def test_zero_copy_input_is_a_copy_of_the_callers_buffer(tmp_path):
    """`copy_from_cpu` keeps what the buffer held when it was called: a
    caller refills its array for the next batch while a run may still be
    in flight, and on the CPU backend jnp.asarray of an aligned numpy
    array shares its memory (several arrays, kept alive, so that one of
    them is aligned)."""
    d, xv, _ = _train_and_export(tmp_path)
    predictor = create_paddle_predictor(AnalysisConfig(model_dir=d))
    inp = predictor.get_input_handle("x")
    buffers = [xv.copy() for _ in range(16)]
    for buf in buffers:
        inp.copy_from_cpu(buf)
        buf += 1.0
        np.testing.assert_array_equal(np.asarray(inp._value), xv)


def test_predictor_dict_api_and_clone(tmp_path):
    d, xv, ref = _train_and_export(tmp_path)
    predictor = create_paddle_predictor(AnalysisConfig(model_dir=d))
    outs = predictor.run({"x": xv})
    np.testing.assert_allclose(outs[0], ref, atol=1e-6)

    p2 = predictor.clone()
    outs2 = p2.run({"x": xv})
    np.testing.assert_allclose(outs2[0], ref, atol=1e-6)


def test_predictor_errors(tmp_path):
    with pytest.raises(ValueError):
        create_paddle_predictor(AnalysisConfig())
    with pytest.raises(FileNotFoundError):
        create_paddle_predictor(AnalysisConfig(model_dir=str(tmp_path / "nope")))
    d, xv, _ = _train_and_export(tmp_path)
    predictor = create_paddle_predictor(AnalysisConfig(model_dir=d))
    with pytest.raises(RuntimeError, match="not set"):
        predictor.zero_copy_run()
