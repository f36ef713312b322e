"""Inference engine (reference: paddle/fluid/inference/ —
`AnalysisPredictor` api/analysis_predictor.cc:78,479, `AnalysisConfig`,
`CreatePaddlePredictor` :929, ZeroCopyTensor :620).

TPU-native redesign: the reference's analysis pass pipeline (fusion passes,
TRT/Anakin subgraph capture, paddle_pass_builder.cc:73) exists to hand-fuse
graphs for fixed engines — here the whole pruned inference program lowers to
ONE XLA computation and XLA performs those fusions; the predictor AOT-jits
per input signature and caches executables (the role of NaiveExecutor +
pass pipeline combined). ZeroCopy semantics map to device-resident
jax.Arrays: copy_from_cpu stages to device, run() keeps results on device
until copy_to_cpu."""

from __future__ import annotations

import os

import numpy as np

import jax
import jax.numpy as jnp

from .. import io as _io
from ..executor import Executor
from ..place import CPUPlace, TPUPlace
from ..scope import Scope

__all__ = [
    "AnalysisConfig",
    "AnalysisPredictor",
    "PaddleTensor",
    "ZeroCopyTensor",
    "create_paddle_predictor",
    "create_predictor",
]


class AnalysisConfig:
    """reference: inference/api/paddle_analysis_config.h. Knobs that have no
    TPU meaning (MKLDNN, TensorRT) are accepted and recorded so reference
    deployment scripts run; XLA already plays their role."""

    def __init__(self, model_dir=None, params_file=None):
        self._model_dir = model_dir
        self._params_file = params_file
        # False: run on whatever backend the process has (the chip, on a
        # machine with one). enable_use_gpu() makes the accelerator a
        # requirement the predictor checks.
        self._use_tpu = False
        self._ir_optim = True
        self._memory_optim = True
        self._cpu_math_threads = 1
        self._enable_profile = False

    # -- model location -------------------------------------------------
    def set_model(self, model_dir, params_file=None):
        self._model_dir = model_dir
        self._params_file = params_file

    def model_dir(self):
        return self._model_dir

    # -- device ----------------------------------------------------------
    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        # GPU knob from reference scripts: asks for the accelerator, and
        # the predictor raises if the process's backend is not the TPU
        self._use_tpu = True

    def disable_gpu(self):
        self._use_tpu = False

    def use_gpu(self):
        return self._use_tpu

    # -- optimization knobs (XLA supersedes; recorded for parity) --------
    def switch_ir_optim(self, flag=True):
        self._ir_optim = flag

    def ir_optim(self):
        return self._ir_optim

    def enable_memory_optim(self):
        self._memory_optim = True

    def enable_mkldnn(self):
        pass

    def enable_tensorrt_engine(self, *a, **k):
        pass

    def set_cpu_math_library_num_threads(self, n):
        self._cpu_math_threads = n

    def enable_profile(self):
        self._enable_profile = True

    def switch_use_feed_fetch_ops(self, flag=True):
        pass

    def switch_specify_input_names(self, flag=True):
        pass


class PaddleTensor:
    """Feed/fetch value for the non-zero-copy API (reference:
    paddle_api.h PaddleTensor)."""

    def __init__(self, data=None, name=None):
        self.name = name
        self.data = None if data is None else np.asarray(data)

    @property
    def shape(self):
        return None if self.data is None else list(self.data.shape)

    def as_ndarray(self):
        return self.data


class ZeroCopyTensor:
    """Device-resident input/output handle (reference:
    analysis_predictor.cc:620 ZeroCopyRun path)."""

    def __init__(self, name, predictor):
        self.name = name
        self._pred = predictor
        self._value = None  # jax.Array on device

    def copy_from_cpu(self, arr):
        # a copy, as the name says: jnp.asarray aliases an aligned numpy
        # buffer on the CPU backend, and the caller may refill `arr` for
        # its next batch while a zero_copy_run() is still in flight
        self._value = jnp.array(arr, copy=True)

    def copy_to_cpu(self):
        v = self._pred._outputs.get(self.name, self._value)
        return np.asarray(v)

    def reshape(self, shape):
        if self._value is not None:
            self._value = self._value.reshape(shape)

    def value(self):
        return self._pred._outputs.get(self.name, self._value)


class AnalysisPredictor:
    """Compiled predictor over a saved inference model."""

    def __init__(self, config: AnalysisConfig):
        if config.model_dir() is None:
            raise ValueError("AnalysisConfig.set_model(dirname) first")
        if not os.path.isdir(config.model_dir()):
            raise FileNotFoundError(config.model_dir())
        self._config = config
        self._scope = Scope()
        place = TPUPlace() if config.use_gpu() else CPUPlace()
        self._exe = Executor(place)
        from ..scope import scope_guard

        with scope_guard(self._scope):
            self._program, self._feed_names, self._fetch_vars = (
                _io.load_inference_model(config.model_dir(), self._exe)
            )
        self._fetch_names = [v.name for v in self._fetch_vars]
        self._input_handles = {
            n: ZeroCopyTensor(n, self) for n in self._feed_names
        }
        self._outputs = {}

    # -- introspection ---------------------------------------------------
    def get_input_names(self):
        return list(self._feed_names)

    def get_output_names(self):
        return list(self._fetch_names)

    def get_input_handle(self, name):
        return self._input_handles[name]

    get_input_tensor = get_input_handle

    def get_output_handle(self, name):
        return ZeroCopyTensor(name, self)

    get_output_tensor = get_output_handle

    # -- execution --------------------------------------------------------
    def _run_feed(self, feed: dict):
        outs = self._exe.run(
            self._program,
            feed=feed,
            fetch_list=self._fetch_names,
            scope=self._scope,
            return_numpy=False,
        )
        self._outputs = dict(zip(self._fetch_names, outs))
        return outs

    def run(self, inputs=None):
        """PaddleTensor-list API (reference PaddlePredictor::Run) or the
        zero-copy API when `inputs` is None (reference ZeroCopyRun)."""
        if inputs is None:  # zero-copy: values staged via input handles
            feed = {
                n: h._value for n, h in self._input_handles.items()
                if h._value is not None
            }
            missing = set(self._feed_names) - set(feed)
            if missing:
                raise RuntimeError(
                    f"zero-copy inputs not set: {sorted(missing)}"
                )
            self._run_feed(feed)
            return None
        if isinstance(inputs, dict):
            outs = self._run_feed(inputs)
            return [np.asarray(o) for o in outs]
        # list of PaddleTensor, positional against feed targets
        feed = {}
        for name, t in zip(self._feed_names, inputs):
            feed[t.name or name] = t.data
        outs = self._run_feed(feed)
        return [
            PaddleTensor(np.asarray(o), name=n)
            for n, o in zip(self._fetch_names, outs)
        ]

    def zero_copy_run(self):
        return self.run(None)

    # -- misc (reference surface) ----------------------------------------
    def clone(self):
        return AnalysisPredictor(self._config)

    def program(self):
        return self._program


def create_paddle_predictor(config: AnalysisConfig) -> AnalysisPredictor:
    """reference: CreatePaddlePredictor (analysis_predictor.cc:929)."""
    return AnalysisPredictor(config)


create_predictor = create_paddle_predictor
