"""The rules that keep a missing chip from hiding (PR 21): a place is
honoured or raises, a Pallas kernel is compiled or raises, the compile
cache sits where the environment or the checkout says, importing the
package touches no device, and the chip smoke fails without a chip."""

import json
import os
import subprocess
import sys

import pytest

import paddle_tpu as fluid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env=None, timeout=300):
    """A fresh interpreter from the repo root with the suite's CPU
    environment plus `env`; variables set to None are removed."""
    full = dict(os.environ)
    for k, v in (env or {}).items():
        if v is None:
            full.pop(k, None)
        else:
            full[k] = v
    return subprocess.run([sys.executable, *args], cwd=REPO, env=full,
                          capture_output=True, text=True, timeout=timeout)


# ------------------------------------------------------------------ place


@pytest.mark.parametrize("place_cls", ["TPUPlace", "XLAPlace", "CUDAPlace"])
def test_accelerator_place_raises_without_a_tpu(place_cls):
    place = getattr(fluid, place_cls)(0)
    with pytest.raises(RuntimeError, match="default JAX backend is 'cpu'"):
        fluid.Executor(place)


def test_cpu_place_makes_no_demand():
    fluid.Executor(fluid.CPUPlace())


def test_predictor_asked_for_the_accelerator_raises(tmp_path):
    x = fluid.layers.data("x", [4])
    y = fluid.layers.fc(x, 2)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    fluid.io.save_inference_model(str(tmp_path), ["x"], [y], exe)
    from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor

    create_paddle_predictor(AnalysisConfig(str(tmp_path)))  # process default
    config = AnalysisConfig(str(tmp_path))
    config.enable_use_gpu()
    with pytest.raises(RuntimeError, match="'cpu'"):
        create_paddle_predictor(config)


def test_peak_table_is_keyed_by_device_kind():
    from paddle_tpu.place import peak_bf16_flops

    assert peak_bf16_flops("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="no bf16 peak recorded.*'cpu'"):
        peak_bf16_flops("cpu")


# ---------------------------------------------------------------- kernels


def test_interpret_follows_only_the_variable(monkeypatch):
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import layer_norm
    from paddle_tpu.ops.pallas.flash_attention import (
        _interpret,
        flash_attention,
    )

    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert _interpret()
    # on the CPU backend, without the variable: not interpreted, and a
    # kernel asked for raises instead of running other math
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET")
    assert not _interpret()
    q = jnp.zeros((1, 1, 128, 64))
    with pytest.raises(RuntimeError, match="'flash_attention'.*'cpu'"):
        flash_attention(q, q, q)
    x = jnp.zeros((1024, 128))
    with pytest.raises(RuntimeError, match="'ln_bwd'.*'cpu'"):
        layer_norm.ln_bwd(x, x, jnp.zeros(1024), jnp.ones(1024),
                          jnp.ones(128))


@pytest.mark.parametrize("norm,kernel,k", [("layer_norm", "ln_bwd", 128),
                                           ("rms_norm", "rms_bwd", 1024)])
def test_a_norms_kernel_on_a_mesh_runs_per_shard_of_the_batch_or_not_at_all(
        norm, kernel, k, monkeypatch):
    """The rule attention follows: GSPMD cannot partition a Pallas custom
    call, so on a mesh of several devices layer_norm_grad and
    rms_norm_grad pick the kernel only where each chip's rows are a whole
    problem of its own: the mesh shards the batch alone, and a shard holds
    rows enough."""
    import jax
    import numpy as np

    from paddle_tpu.ops.pallas import layer_norm
    from paddle_tpu.parallel.mesh import build_mesh

    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    used = []
    real = getattr(layer_norm, kernel)

    def spy(*a, **kw):
        used.append(a[0].shape)
        return real(*a, **kw)

    monkeypatch.setattr(layer_norm, kernel, spy)
    x = fluid.layers.data("x", [2048, k], append_batch_size=False)
    loss = fluid.layers.mean(getattr(fluid.layers, norm)(
        fluid.layers.fc(x, k), begin_norm_axis=1))
    fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = {"x": np.random.RandomState(0).randn(2048, k).astype("float32")}
    main = fluid.default_main_program()

    def step(places=None, mesh=None):
        del used[:]
        program = main
        if places or mesh:
            program = fluid.CompiledProgram(main).with_data_parallel(
                loss_name=loss.name, places=places)
            program._mesh = mesh
        (value,) = exe.run(program, feed=feed, fetch_list=[loss])
        assert np.isfinite(value).all()
        return list(used)

    assert step() == [(2048, k)]
    # two shards of 1,024 rows: the kernel, called on the global arrays
    assert step(places=2) == [(2048, k)]
    # eight shards of 256 rows are under the kernel's 1,024: XLA's
    assert step(places=8) == []
    # tensor parallelism beside the batch axis: XLA's
    assert step(mesh=build_mesh(batch=2, model=2,
                                devices=jax.devices()[:4])) == []


# ---------------------------------------------------- imports and the cache

_IMPORT_PROBE = """
import json
import jax
import paddle_tpu
import paddle_tpu.distributed.launch
import paddle_tpu.inference.fleet
import paddle_tpu.inference.server
import paddle_tpu.models.bert
from jax._src import xla_bridge
from paddle_tpu.jit_compile import COMPILE_CACHE_DIR
print(json.dumps({
    "backends_initialized": xla_bridge.backends_are_initialized(),
    "ours": COMPILE_CACHE_DIR,
    "jax": jax.config.jax_compilation_cache_dir,
}))
"""


def test_imports_touch_no_device_and_cache_defaults_to_the_checkout():
    p = _run(["-c", _IMPORT_PROBE], env={"JAX_COMPILATION_CACHE_DIR": None})
    assert p.returncode == 0, p.stderr
    got = json.loads(p.stdout.strip().splitlines()[-1])
    # a parent may import all of these and stay off the chip
    assert got["backends_initialized"] is False
    assert got["ours"] == got["jax"] == os.path.join(REPO, ".jax_cache")


def test_cache_directory_from_the_environment_is_left_alone(tmp_path):
    want = str(tmp_path / "elsewhere")
    p = _run(["-c", _IMPORT_PROBE], env={"JAX_COMPILATION_CACHE_DIR": want})
    assert p.returncode == 0, p.stderr
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["ours"] == got["jax"] == want


def test_only_one_place_in_the_repo_sets_the_cache_directory():
    """No cache path from a temporary name, a pid or a time: the one
    `jax_compilation_cache_dir` update is the fixed default, and it is
    skipped when the environment names a directory."""
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith((".", "_"))
                   and d != "tests"]
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(root, f)
                with open(path, encoding="utf-8") as fh:
                    if "jax_compilation_cache_dir\"," in fh.read():
                        hits.append(os.path.relpath(path, REPO))
    assert hits == [os.path.join("paddle_tpu", "jit_compile.py")]


# ------------------------------------------------------- the entry points


def test_server_device_tpu_fails_without_a_tpu(tmp_path):
    p = _run(["-m", "paddle_tpu.inference.server", "--model-dir",
              str(tmp_path), "--device", "tpu"])
    assert p.returncode != 0
    assert "default JAX backend is 'cpu'" in p.stderr


def test_benchmark_run_fails_without_a_tpu():
    p = _run(["benchmark/run.py", "--workload", "bert_base_s128",
              "--seed", "1", "--seconds", "1"])
    assert p.returncode != 0
    assert "there is no CPU fallback" in p.stderr
    assert not p.stdout.strip().splitlines()[-1].startswith("{")


def test_chip_smoke_fails_without_a_chip_and_prints_no_result():
    p = _run(["chip_smoke.py"])
    assert p.returncode != 0
    assert "platform is 'cpu', not 'tpu'" in p.stderr
    assert '"ok"' not in p.stdout


def test_chip_smoke_rehearsal_passes_and_fills_the_named_cache(tmp_path):
    cache = tmp_path / "cache"
    p = _run(["chip_smoke.py", "--rehearse"], timeout=600, env={
        "JAX_COMPILATION_CACHE_DIR": str(cache),
        "JAX_ENABLE_COMPILATION_CACHE": "true",
    })
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    assert lines[0].startswith("REHEARSAL")
    assert json.loads(lines[-1]) == {
        "ok": True, "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 4},
    }
    for phase in ("[train]", "[kernels]", "[export]", "[serve]", "[dp4]"):
        assert any(ln.startswith(phase) for ln in lines), phase
    # entries land where the environment said, not in the checkout
    assert len(os.listdir(cache)) > 0


# ------------------------------------------------------------ the switches


def test_readme_table_names_every_paddle_tpu_variable_of_the_package():
    """A `PADDLE_TPU_*` name under `paddle_tpu/` is a switch someone can
    flip: README's table lists each one, so a new one shows in review."""
    import re

    found = set()
    for dirpath, _, files in os.walk(os.path.join(REPO, "paddle_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(dirpath, f)) as fh:
                    found.update(re.findall(r"PADDLE_TPU_[A-Z0-9_]+",
                                            fh.read()))
    with open(os.path.join(REPO, "README.md")) as fh:
        listed = re.findall(r"^\| `(PADDLE_TPU_[A-Z0-9_]+)` \|", fh.read(),
                            re.M)
    assert len(listed) == len(set(listed))
    assert set(listed) == found
