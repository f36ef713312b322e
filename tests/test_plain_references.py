"""The decoders' plain references stay independent of the code under test:
starting from an adapter's `reference` (what the benchmark's `correct`
reads, `benchmark/models/<adapter>.py`), the functions it reaches in its
module, by the names their code objects use, import and name nothing of
`paddle_tpu`. The adapters import `paddle_tpu` only inside the functions
that build the Program (`config`, `build`)."""

import importlib
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ADAPTERS = ("kimi_linear", "trinity", "mellum", "joyai_flash", "phi4_flash",
            "lfm2", "qwen3_next", "nemotron_h", "keye_vl2", "olmo_hybrid",
            "sdar", "granite_hybrid")


def names_used(code):
    """Every global, attribute and imported module a code object names,
    its nested functions' included."""
    used = set(code.co_names)
    for const in code.co_consts:
        if isinstance(const, types.CodeType):
            used |= names_used(const)
    return used


def reached(module, start):
    """{name: function} of the module's own functions that `start` reaches
    through the globals their code names, and every name they use."""
    found, used, todo = {}, set(), [start]
    while todo:
        fn = todo.pop()
        if fn.__name__ in found:
            continue
        found[fn.__name__] = fn
        names = names_used(fn.__code__)
        used |= names
        todo += [v for v in (vars(module).get(n) for n in names)
                 if isinstance(v, types.FunctionType)
                 and v.__module__ == module.__name__]
    return found, used


@pytest.mark.parametrize("adapter", ADAPTERS)
def test_the_reference_reaches_nothing_of_the_code_under_test(adapter):
    module = importlib.import_module("benchmark.models." + adapter)
    found, used = reached(module, module.reference)
    assert len(found) >= 5, sorted(found)  # the mixers and what they share
    assert not {"config", "build"} & set(found)
    assert not {"paddle_tpu", "fluid", "layers"} & used, sorted(used)
    for name in used & set(vars(module)):
        value = vars(module)[name]
        origin = getattr(value, "__module__", None) or getattr(
            value, "__name__", "")
        assert not origin.startswith("paddle_tpu"), (name, origin)
    # and the walk sees an import where there is one
    _, builds = reached(module, module.build)
    assert "paddle_tpu" in builds
