"""Test environment: force the XLA CPU backend with a virtual 8-device mesh
so sharding paths are testable without TPU hardware (the analog of the
reference's localhost-multiprocess distributed tests, SURVEY.md §4)."""

import os

# The environment is set before the first JAX import; that alone holds
# the whole suite on the CPU backend.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# The persistent compile cache stays off under the suite, in this process
# and in every child it starts. Re-tried on jaxlib 0.9.0, reading cached
# CPU executables no longer crashed (cold and warm runs of test_resilience,
# test_executor and test_data_parallel with every entry cached), but each
# load makes XLA's CPU loader write a two-kilobyte "could lead to SIGILL"
# error to stderr in the middle of pytest's progress line, which breaks
# the tier-1 count of passed dots. The chip is where the cache matters.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
# The IR verifier (paddle_tpu/analysis) runs between every pass-manager
# pass under the suite (PADDLE_TPU_VERIFY, round-15): a pass that breaks
# def-before-use / dtype / write-rule invariants fails loudly with an
# op/var-precise message instead of an opaque tracer error. Exported
# values win (set PADDLE_TPU_VERIFY=0 to profile the suite without it).
os.environ.setdefault("PADDLE_TPU_VERIFY", "1")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# the plain modules beside the test files hold cases and assertions too
pytest.register_assert_rewrite("decoder_suite", "kernel_cases")


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'` (ROADMAP.md); slow-marked tests (the
    # resilience kill/resume + transformer bitwise-resume gates) run in
    # tools/ci.sh instead
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 budget; run via ci.sh"
    )


@pytest.fixture(autouse=True, scope="session")
def no_step_store():
    """The store of compiled steps (`paddle_tpu/step_store.py`) has no
    directory for the session: the suite patches lowerings and kernels in
    place, which no key can see, so a step found on disk could be another
    test's. `tests/test_step_store.py` gives it a temporary one."""
    from paddle_tpu import step_store

    step_store.DIR = None
    yield


@pytest.fixture(autouse=True)
def fresh_programs():
    """Each test gets fresh default programs + scope (the reference resets
    Program state per unit test via new Program() guards)."""
    import paddle_tpu.framework as framework
    import paddle_tpu.scope as scope_mod

    old_main = framework.switch_main_program(framework.Program())
    old_startup = framework.switch_startup_program(framework.Program())
    framework.unique_name.switch()
    old_scope = scope_mod._global_scope
    scope_mod._global_scope = scope_mod.Scope()
    scope_mod._scope_stack[:] = [scope_mod._global_scope]
    yield
    framework.switch_main_program(old_main)
    framework.switch_startup_program(old_startup)
    scope_mod._global_scope = old_scope
    scope_mod._scope_stack[:] = [old_scope]


@pytest.fixture
def rng():
    return np.random.RandomState(42)


@pytest.fixture
def attn_path(monkeypatch):
    """`attn_path("flash")`: until the test ends, every attention lowering
    takes the named path whatever `fused_ops.attention_path` would choose
    from the call. How a test reaches a kernel below its threshold; a
    kernel this backend cannot run still raises where it is called."""
    from paddle_tpu.ops import fused_ops

    def force(path):
        monkeypatch.setattr(fused_ops, "attention_path",
                            lambda *shapes, **call: path)

    return force
