"""Test environment: force the XLA CPU backend with a virtual 8-device mesh
so sharding paths are testable without TPU hardware (the analog of the
reference's localhost-multiprocess distributed tests, SURVEY.md §4)."""

import os

# The environment is set before the first JAX import; that alone holds
# the whole suite on the CPU backend.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=8"
os.environ["XLA_FLAGS"] = flags.strip()
# Four threads a virtual device in the CPU client's pools, not one. The
# client sizes them by the machine's cores or the devices, whichever is
# more: eight for eight devices here. A step that holds two collectives
# with no order between them (the causal ring's permutes and a gather:
# `tests/test_ring_attention.py::test_forward_matches_full[8-True]`) parks
# a thread a device in each, and when the devices' threads choose
# differently every thread of the pool waits in a rendezvous whose other
# participants are queued behind it. XLA then ends the process ("This
# thread has been waiting for `all gather ...` ... seven threads; one in
# `collective permute ...`", then "Termination timeout ... exceeded",
# SIGABRT): under xdist a crashed worker, one whole run in two or three,
# in a case no PR touched. `PJRT_NPROC` is the client's own name for the size.
os.environ["PJRT_NPROC"] = "32"
# The persistent compile cache stays off under the suite, in this process
# and in every child it starts. Weighed once more in PR 70, now that the
# driver counts from the JUnit file and XLA:CPU's loader may write to
# stderr as it likes: one whole run of the driver's command with a cache
# directory made anew under the run's temporary directory and shared by
# the six workers, against one without, on one tree. The sum of JUnit
# `time` fell by 6.0% (4,918 to 4,622 s; the entries 435 MB in 474 files),
# short of the tenth that would have paid for it, and sixteen cases that
# count compiles went red (`tests/test_step_store.py`, which gives itself
# a cache of its own, and `tests/test_tracing.py`'s bare jit). With the
# cache on every compile is also keyed and looked up, which the files
# that compile op by op paid for with a quarter more time
# (`tests/test_flash_attention.py` and three others, 725 to 902 s).
# What repeats across the workers is kept once a run by the tests that
# own it (`run_dir` below, `decoder_suite.kept`). The chip is where the
# cache matters.
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
    # the driver's tier-1 command runs `-m 'not slow'`; slow-marked tests
    # (the resilience kill/resume and transformer bitwise-resume gates)
    # run in tools/ci.sh instead
    config.addinivalue_line(
        "markers", "slow: excluded from tier-1; run via ci.sh"
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


@pytest.fixture(scope="session")
def run_dir(tmp_path_factory):
    """The directory of this run of the tests, the same for every xdist
    worker of the run (a worker's own is `<the run's>/popen-gwN`): where a
    result that more than one worker needs is kept (`decoder_suite.kept`)."""
    base = tmp_path_factory.getbasetemp()
    return base.parent if os.environ.get("PYTEST_XDIST_WORKER") else base


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
