"""One table of Program pins: a row a cell of `BENCHMARK.json`, the train
Program at the rehearsal size as `train_loop.build_programs` builds it.
A row holds the count of ops; the sha256 (16 hex digits) of the op types in
order, each with every attribute that is not bookkeeping (`op_role` and
the gradient ops' copy of their forward op, `fwd_*`); and, so that a reader
need not decode the digest, how many ops of the types in `OPS` the Program
has and which of the attributes in `ATTRS` any op of it carries (each is
one model's: a cell that does not list it has none). Nothing is compiled
and no step runs; what a cell's step must and must not count is in that
cell's own counters case (`tests/test_<model>_reference.py`).

The table is taken by running this file as a script on the parent commit
(`PYTHONPATH=<parent> python tests/test_program_pins.py`, as
`tests/test_parents_jaxprs.py` is): a PR that adds a cell adds its row, and
re-takes only the rows it means to change. These are PR 55's parent's
(commit 006eebc), but for the nine cells that lower `rms_norm`: PR 59 gave
the op a gradient op of its own (`rms_norm_grad` where `__auto_grad__`
stood, one a norm), and their rows are that PR's own tree's.
PR 60 added `keye_vl2_ep16_s8192` and the three op types at `OPS`' end,
which no other cell has. Its row and `ouro_2p6b_vp8_s4096`'s are PR 61's
own tree's: that PR took the positions of both and the QK-norm of Keye's
into `fused_multihead_attention`; the thirteen other rows stood. PR 63
added `olmo_hybrid_7b_vp8_longdoc` (its `kda_attention` ops carry
`beta_scale`, which no other cell's do: the fifteen other rows stood).
PR 68 added `sdar_30b_a3b_ep8_s4096` (its `fused_multihead_attention` ops
carry `diffusion_block`, the attribute at `ATTRS`' end, which no other
cell's do: the sixteen other rows stood). PR 72 added
`granite4_h_micro_vp8_longdoc` and gave `decoder_parts.attention` a
`scale` argument whose default is what the function did: the seventeen
other rows stood, which is the test that the default changes no
Program."""

import hashlib
import json
import os
import sys

import pytest

# after PYTHONPATH: run as a script against a parent, the parent's is found
sys.path.append(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OPS = ("fused_multihead_attention", "rotary_embedding", "short_conv1d",
       "kda_attention", "selective_scan", "ssd_scan", "moe_experts",
       "rms_norm_grad", "sparse_index", "sparse_select", "index_kl")
ATTRS = ("rope_scaling", "interleaved", "q_lora_rank", "activation",
         "norm_eps", "rotary_dim", "expert_form", "diffusion_block")

PINS = {
    "bert_base_s128": (
        304, "5123d1989e7f49f4",
        {"fused_multihead_attention": 2},
        ()),
    "bert_base_s128_dp4": (
        304, "7ab026a688fe9daa",
        {"fused_multihead_attention": 2},
        ()),
    "bert_base_s512": (
        304, "5f2e9a39729d70cd",
        {"fused_multihead_attention": 2},
        ()),
    "transformer_base_s64": (
        628, "7c09445cc15c5358",
        {"fused_multihead_attention": 6},
        ()),
    "resnet50_b128": (
        346, "efb26064e718b288",
        {},
        ()),
    "kimi_linear_ep32_s4096": (
        658, "f22970285e8a493a",
        {"fused_multihead_attention": 1, "short_conv1d": 12,
         "kda_attention": 4, "moe_experts": 4, "rms_norm_grad": 16},
        ()),
    "trinity_mini_ep16_s8192": (
        571, "86fb5cbdd7c57727",
        {"fused_multihead_attention": 5, "moe_experts": 4,
         "rms_norm_grad": 21},
        ()),
    "mellum2_ep4_s8192": (
        291, "9679eedb8fe261f8",
        {"fused_multihead_attention": 4, "moe_experts": 4,
         "rms_norm_grad": 9},
        ("rope_scaling",)),
    "joyai_flash_ep32_s4096": (
        827, "ce76ffcc8dc6405a",
        {"fused_multihead_attention": 6, "rotary_embedding": 12,
         "moe_experts": 5, "rms_norm_grad": 28},
        ("interleaved", "q_lora_rank",)),
    "phi4_mini_flash_vp8_longdoc": (
        733, "ad6c61b955fb9d55",
        {"fused_multihead_attention": 6, "short_conv1d": 2,
         "selective_scan": 2, "rms_norm_grad": 3},
        ()),
    "lfm2_24b_ep8_longdoc": (
        313, "4a78acef1d49edd3",
        {"fused_multihead_attention": 1, "short_conv1d": 4,
         "moe_experts": 4, "rms_norm_grad": 11},
        ("activation", "norm_eps",)),
    "qwen3_next_ep16_s4096": (
        496, "1e149720f8325246",
        {"fused_multihead_attention": 1, "short_conv1d": 3,
         "kda_attention": 3, "moe_experts": 4, "rms_norm_grad": 12},
        ("rotary_dim",)),
    "nemotron3_super_ep64_s4096": (
        296, "1027849616ac328c",
        {"fused_multihead_attention": 1, "short_conv1d": 2,
         "ssd_scan": 2, "moe_experts": 2, "rms_norm_grad": 10},
        ("norm_eps", "expert_form",)),
    # PR 61's own tree: the two cells whose q and k it moved into the
    # attention op (Ouro's 16 `rotary_embedding` ops and their gradient
    # ops gone; of Keye's 8 the indexer's 4 left, its 4 QK-norms gone)
    "ouro_2p6b_vp8_s4096": (
        712, "4f1654fba37bd39b",
        {"fused_multihead_attention": 8, "rms_norm_grad": 36}, ()),
    "keye_vl2_ep16_s8192": (
        258, "5708863fa152acb3",
        {"fused_multihead_attention": 2, "rotary_embedding": 4,
         "moe_experts": 2, "rms_norm_grad": 5, "sparse_index": 2,
         "sparse_select": 2, "index_kl": 2}, ()),
    # PR 63's own tree: the cell it added
    "olmo_hybrid_7b_vp8_longdoc": (
        391, "b77c60bd17d83405",
        {"fused_multihead_attention": 1, "short_conv1d": 3,
         "kda_attention": 3, "rms_norm_grad": 14}, ()),
    # PR 68's own tree: the cell it added
    "sdar_30b_a3b_ep8_s4096": (
        172, "dbc029f9df8d353d",
        {"fused_multihead_attention": 2, "moe_experts": 2,
         "rms_norm_grad": 5}, ("diffusion_block",)),
    # PR 72's own tree: the cell it added (the rehearsal's three layers)
    "granite4_h_micro_vp8_longdoc": (
        283, "101ac4c56cd2b498",
        {"fused_multihead_attention": 1, "short_conv1d": 2, "ssd_scan": 2,
         "rms_norm_grad": 9}, ()),
}


def cells():
    from benchmark.harness import spec

    with open(os.path.join(os.path.dirname(spec.BENCH_DIR),
                           "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def row(cell_name):
    import paddle_tpu as fluid
    from benchmark.harness import spec
    from benchmark.runners import train_loop

    c = spec.cell(cell_name, rehearse=True)
    adapter = spec.plugin("models", c["config"]["adapter"])
    with fluid.program_guard(fluid.Program(), fluid.Program()), \
            fluid.unique_name.guard():
        main = train_loop.build_programs(fluid, adapter, c["config"],
                                         c["traffic"], 3)[0]
    ops = main.global_block().ops
    lines = [op.type + " " + json.dumps(
        {k: v for k, v in op.attrs.items()
         if not k.startswith(("op_", "fwd_"))}, sort_keys=True, default=str)
        for op in ops]
    types = [op.type for op in ops]
    return (len(ops), hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16],
            {t: types.count(t) for t in OPS if t in types},
            tuple(a for a in ATTRS if any(a in op.attrs for op in ops)))


def test_every_cell_has_its_row():
    assert sorted(PINS) == sorted(cells())


@pytest.mark.parametrize("cell_name", list(PINS))
def test_the_cells_train_program_is_op_for_op_what_it_was(cell_name):
    assert row(cell_name) == PINS[cell_name]


if __name__ == "__main__":
    for name in cells():
        print(f'    "{name}": {row(name)!r},', flush=True)
