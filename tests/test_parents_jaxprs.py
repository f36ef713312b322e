"""What PR 51 added to shared ops is off by default, and with the default
the Programs of the configurations that were there trace what they traced:
Kimi's delta-rule mixer (a decay a channel, a key head a value head),
the attention of Trinity, Mellum and LFM2 (positions over the whole head
or none), and the five expert cells' `expert_ffn` (no gate on the shared
expert). Each case lowers a small Program and its backward as the
Executor's step does and compares the sha256 of the jaxpr's text with the
one taken by running this file against a copy of PR 51's parent commit
(`PYTHONPATH=<parent> python tests/test_parents_jaxprs.py`), under jax
0.9.0. One case is pinned later: `kda-kernels` at PR 52, which restated
the kernels' body (the solve in lockstep over the chunks of a grid
step: `ops/pallas/kda_chunk.py`), and again at PR 54 (a grid step's row
arithmetic once over its stacked rows, four chunks a step); each re-took
that digest on its finished tree; the results are the parent's bit for
bit (`tests/test_kda_kernel.py`), the jaxpr is not. PR 65 re-took it
once more, on purpose: the kernels now take q, k and beta's logits as the
projections wrote them and make the L2 norms and beta in VMEM
(`_gate_rows`; the channel's decay stays XLA's `kda_gate`), so the call's
operands and body moved; the results are the plain path's at the
distances `tests/test_kda_kernel.py` holds. `kda-chunked` stands
untouched by it (the op, `_prologue` and the plain path did not change),
and it and the ten others are PR 51's parent's.

PR 53 gave `moe_experts` a second input and an expert form, `attention`
a switch for the QK-norm, `proj` a deviation of its own and
`ssm_ops.py` a second op, all off by default: the six cases from
`experts-gmm` on are taken against PR 53's parent (commit 55ba533) the
same way: the expert layer through the grouped kernels, with the
renormalisation's epsilon (LFM2's) and with the gated shared expert
(Qwen3-Next's), Phi-4's Mamba-1 mixer on both of its paths, and the
convolution with bias and SiLU as the Mamba mixers call it.

The four `-flash` cases are PR 60's own tree's. That PR gave the flash
kernels an optional admission operand and made the call return its
log-sum-exp rows beside the output (`ops/pallas/flash_attention.py`): a
call without an admission carries one more static (`admit_keys` 0), its
kernels take their optional operands by a tuple of switches, and the
custom-vjp returns a pair, so the jaxpr's text moved. What the chip runs
did not: compiled for a described v5e at Trinity's and Mellum's shape
(32 heads on 4 of 128 at 8,192 tokens, full causal), the Mosaic modules
of `flash_fwd` and `flash_bwd_dkv_dq` with their locations stripped and
the declared costs are the parent's to the byte (PERF.md section 6,
PR 60, has the digests). The `-xla` cases stand.

The five `experts-` cases are PR 67's own tree's. That PR restated the
expert layer's bookkeeping (`parallel/moe.py`: the k selected scores by a
compare over the lanes, one sort that carries the weights, the load
counted off the keys), so `moe_experts`' jaxpr moved by design in every
caller; what the pieces compute is held bit for bit, value and
gradients, against the forms these digests had pinned
(`tests/test_moe_experts.py`). Up to then `experts-shared` and
`experts-alone` were PR 51's parent's and the three others PR 53's
parent's; the two Mamba-1 cases and the convolution's still are.

`experts-gmm` is PR 73's own tree's, and was PR 69's before. With the
kernels a block's rows are summed onto their tokens by grouped 0/1
products (`parallel/moe.py::_onto_tokens`) and not by `scatter-add`
(PR 69), and the backward's `dx` is `moe_gmm` on the weights read
transposed and not `ragged_dot` on the last group stretched (PR 73:
`ops/pallas/grouped_matmul.py`), so that path's jaxpr moved by design each
time; the four cases on the plain path stand, what the sums compute is
held against the scatter's in `tests/test_moe_experts.py`, and `dx`
against `ragged_dot`'s in `tests/test_grouped_matmul.py`."""

import os
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASE = dict(hidden_size=256, initializer_range=0.02, rms_norm_eps=1e-6)


def _loss(out):
    from paddle_tpu import layers

    return layers.mean(layers.cast(out, "float32"))


def _data(s, hidden=256):
    from paddle_tpu import layers

    return layers.data("u", [1, s, hidden], dtype="float32",
                       append_batch_size=False)


def kda_case():
    """Kimi's mixer at two heads of 128: the kernels under the
    interpreter, `kda_chunked` without."""
    from paddle_tpu.models.kimi_linear import KimiLinearConfig, _kda_mixer

    cfg = KimiLinearConfig(hidden_size=256, num_heads=2, kda_head_dim=128)
    return _loss(_kda_mixer(_data(192), cfg, "m"))


def attention_case(which, s):
    """`decoder_parts.attention` as the three models call it; at 2,048
    tokens under the interpreter the flash path and `qk_prep`, at 64 the
    XLA path and `rotate_half`."""
    from paddle_tpu.models.decoder_parts import attention

    cfg = SimpleNamespace(num_attention_heads=4, num_key_value_heads=2,
                          head_dim=128, **BASE)
    kw = {
        "trinity_full": dict(window=0, rope_theta=0.0, gated=True),
        "trinity_window": dict(window=32, rope_theta=1e4, gated=True),
        "mellum": dict(window=0, rope_theta=5e5,
                       rope_scaling={"rope_type": "yarn", "factor": 8.0,
                                     "original_max_position_embeddings":
                                     4096}),
        "lfm2": dict(rope_theta=1e6),
    }[which]
    return _loss(attention(_data(s), cfg, "a", **kw))


def expert_case(shared):
    from paddle_tpu.models.decoder_parts import expert_ffn

    cfg = SimpleNamespace(
        num_experts=8, experts_held=4, held_from=2, moe_intermediate_size=32,
        num_experts_per_token=2, routed_scaling_factor=2.0,
        moe_renormalize=True, router_bias_scale=0.0,
        score_func="softmax" if shared else "sigmoid",
        num_shared_experts=shared, **BASE)
    return _loss(expert_ffn(_data(24), cfg, "e")[0])


def expert_case_53(which):
    """`expert_ffn` as the six expert cells call it beyond the two cases
    above: at widths the grouped kernels take, with an epsilon in the
    renormalisation, and with the token's gate on the shared expert."""
    from paddle_tpu.models.decoder_parts import expert_ffn

    cfg = SimpleNamespace(
        num_experts=8, experts_held=4, held_from=2,
        moe_intermediate_size=128 if which == "gmm" else 32,
        num_experts_per_token=2, routed_scaling_factor=2.0,
        moe_renormalize=True, router_bias_scale=0.1,
        score_func="softmax" if which == "shared_gate" else "sigmoid",
        num_shared_experts=1, shared_expert_gate=which == "shared_gate",
        **BASE)
    return _loss(expert_ffn(_data(24), cfg, "e",
                            norm_eps=1e-20 if which == "norm_eps" else 0.0)[0])


def mamba1_case():
    """Phi-4's Mamba-1 mixer (`selective_scan`, the convolution with its
    bias): 128 channels, the kernels under the interpreter."""
    from paddle_tpu.models.phi4_flash import Phi4FlashConfig, mamba

    cfg = Phi4FlashConfig(hidden_size=64, mamba_expand=2, mamba_d_state=16,
                          mamba_d_conv=4)
    return _loss(mamba(_data(96, 64), cfg, "m")[0])


def conv_case():
    from paddle_tpu import ParamAttr, layers

    return _loss(layers.short_conv1d(_data(96, 128), 4,
                                     bias_attr=ParamAttr(name="c.b_0")))


CASES = {
    "kda-chunked": (kda_case, False),
    "kda-kernels": (kda_case, True),
    "trinity_full-xla": (lambda: attention_case("trinity_full", 64), False),
    "trinity_window-xla": (lambda: attention_case("trinity_window", 64),
                           False),
    "mellum-xla": (lambda: attention_case("mellum", 64), False),
    "lfm2-xla": (lambda: attention_case("lfm2", 64), False),
    "trinity_full-flash": (lambda: attention_case("trinity_full", 2048),
                           True),
    "trinity_window-flash": (lambda: attention_case("trinity_window", 2048),
                             True),
    "mellum-flash": (lambda: attention_case("mellum", 2048), True),
    "lfm2-flash": (lambda: attention_case("lfm2", 2048), True),
    "experts-shared": (lambda: expert_case(1), False),
    "experts-alone": (lambda: expert_case(0), False),
    "experts-gmm": (lambda: expert_case_53("gmm"), True),
    "experts-norm_eps": (lambda: expert_case_53("norm_eps"), False),
    "experts-shared_gate": (lambda: expert_case_53("shared_gate"), False),
    "mamba1-chunked": (mamba1_case, False),
    "mamba1-kernels": (mamba1_case, True),
    "short_conv-bias": (conv_case, False),
}

# as PR 51's parent (commit 6f8ecfe) traces them
PARENTS_JAXPRS = {
    "kda-chunked": "ef5c1d772c23b254",
    "kda-kernels": "6b405d73f7deeba2",  # PR 65's tree: see the docstring
    "trinity_full-xla": "1160994003f6c88b",
    "trinity_window-xla": "9e6c7e0895897a9a",
    "mellum-xla": "9e7d571745830a91",
    "lfm2-xla": "9f0068051ad85522",
    # PR 60's tree: see the docstring
    "trinity_full-flash": "056160d2639a489a",
    "trinity_window-flash": "e01967eee0b60f69",
    "mellum-flash": "beab6dae51f8d3b7",
    "lfm2-flash": "0847f7ec19b5593b",
    # the expert cases: PR 67's tree (see the docstring)
    "experts-shared": "53549bc5f876910a",
    "experts-alone": "8e9175990e4374b6",
    "experts-gmm": "c67d05977d1000f2",  # PR 73's tree: see the docstring
    "experts-norm_eps": "2b3d5cd41d2da9df",
    "experts-shared_gate": "a5b4baca8bfe7d27",
    # as PR 53's parent (commit 55ba533) traces them
    "mamba1-chunked": "95497628e7a473b7",
    "mamba1-kernels": "9013a4410a37b2fa",
    "short_conv-bias": "60ee9c916a5b65eb",
}


def digest(case):
    from pallas_costs import program_digest

    build, interpret = CASES[case]
    if interpret:
        os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
    else:
        os.environ.pop("PADDLE_TPU_PALLAS_INTERPRET", None)
    return program_digest(build, amp=True)


@pytest.mark.parametrize("case", list(CASES))
def test_the_defaults_jaxpr_is_the_parents(case, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "")  # restored after
    assert digest(case) == PARENTS_JAXPRS[case]


if __name__ == "__main__":
    for name in CASES:
        print(f'    "{name}": "{digest(name)}",', flush=True)
