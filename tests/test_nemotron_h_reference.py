"""Nemotron-H against its plain reference (`benchmark/models/nemotron_h.py`)
at the rehearsal size of the cell `nemotron3_super_ep64_s4096`: what every
decoder suite holds (`tests/decoder_suite.py`: every kind of block alone,
the whole model in float32 and under bf16 AMP, one train step's gradients
for every kind of parameter, that each wrong model is caught) on this
model's data, and its own: the published pattern, the Mamba-2 mixer with
one group on a ragged chunk, the expert layer through the grouped kernels,
the shares against the uncut layers (eight head shares of a Mamba-2 mixer
and of an attention layer, sixty-four expert shares with the shared expert
counted once, the vocabulary's slices), the gauges and counters, and the
cell's arithmetic.

Run as a script on the attached TPU, outside any timed window
(`tests/decoder_suite.py` has the arguments):

    python3 tests/test_nemotron_h_reference.py readings 1 2   # program, wrong models and fp8 reference against the reference
    python3 tests/test_nemotron_h_reference.py loads@3e-6 1 2   # held share by expert layer and the loss over the window's steps at a rate
    python3 tests/test_nemotron_h_reference.py gradients      # at the published widths on one 512-token row
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np
import pytest

from decoder_suite import *  # noqa: F401,F403 — the shared cases, on SUITE
from decoder_suite import guards, highest, main, rel, routed_shares

from benchmark.models import nemotron_h as adapter  # noqa: E402

CELL = "nemotron3_super_ep64_s4096"

# At 64 wide, seeded as the cell is (matrices Normal(0, 0.02), a block's
# last product 13 times less), a block adds next to nothing to the residual
# stream, so a wrong model does not show in the logits. With the matrices
# at 0.1 and the last products at the same, the blocks weigh in the stream
# as at the published width or more.
AS_AT_WIDTH = {"initializer_range": 0.1, "rescale_prenorm_residual": False}



def _mixer_program(which, model, batch, seq):
    """A mixer or an expert layer alone in a Program: `u` in, `y` out."""
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_parts

    cfg = adapter.config(model)
    u = fluid.layers.data("u", [batch, seq, cfg.hidden_size],
                          append_batch_size=False)
    if which == "mamba2":
        return decoder_parts.mamba2_mixer(u, cfg, "m")
    if which == "attention":
        return decoder_parts.attention(u, cfg, "m", qk_norm=False)
    return decoder_parts.expert_ffn(u, cfg, "m", norm_eps=1e-20)[0]


def _want_mixer(which, p, feeds, model, wrong=()):
    fn = {"mamba2": adapter.mamba_mixer, "attention": adapter.attention_mixer,
          "experts": adapter.expert_layer}[which]
    return highest(fn, p, feeds["u"], "m", model, wrong)


WRONG_BY_MIXER = {
    "mamba2": ("no_d_skip", "norm_whole", "gate_after_norm"),
    "attention": ("positions",),
    "experts": ("gated_expert", "router_reads_latent", "no_scaling"),
}


KINDS = {
    "embedding": ("nemotron.embed",),
    "head": ("nemotron.head.w_0",),
    "rms_norm": (".norm.w_0", "final_norm.w_0"),
    "W_in": (".mamba.in_proj.w_0",),
    "conv_filter": (".mamba.conv.w_0",),
    "conv_bias": (".mamba.conv.b_0",),
    "A_log": (".mamba.A_log",),
    "dt_bias": (".mamba.dt_bias",),
    "D": (".mamba.D",),
    "gated_norm": (".norm.group0.w_0", ".norm.group1.w_0"),
    "W_out": (".mamba.out_proj.w_0",),
    "attention": (".attn.q.w_0", ".attn.k.w_0", ".attn.v.w_0", ".attn.o.w_0"),
    "router": (".moe.gate",),
    "latent": (".latent_in.w_0", ".latent_out.w_0"),
    "shared_expert": (".shared.up.w_0", ".shared.down.w_0"),
    "experts": (".moe.w_up", ".moe.w_down"),
}


def _ungated(step):
    assert not any(n.endswith("w_gate") for n in step.want)


# on the chip a rank holds one group's norm, and the latent's two
# projections see the loss through the routed experts alone, so where
# rounding flips a selection their gradients change by a whole token's
# worth, as the experts' do: held to the routed limit
CHIP_KINDS = dict(KINDS, gated_norm=(".norm.group0.w_0",))
CHIP_KINDS["experts"] += CHIP_KINDS.pop("latent")

SUITE = Suite(  # noqa: F405
    CELL, adapter, kinds=KINDS, as_at_width=AS_AT_WIDTH,
    # the norms' weights off their seeded 1 and the skip's `D` off its, so
    # that a norm left out or a weight shared where it is a channel's own
    # shows
    moved=lambda n: "norm" in n or n.endswith(".D"),
    # 80 tokens: five chunks of the rehearsal's 16; four Mamba-2 heads in
    # two groups, so the norm by groups and the group a head reads show
    mixers=("mamba2", "attention", "experts"), mixer_program=_mixer_program,
    want_mixer=_want_mixer, wrong_by_mixer=WRONG_BY_MIXER,
    # the reference with its last block left out or with one departure of
    # `WRONG`: against the float32 program each reads hundreds of times
    # its limit, and against the program in the cell's precision each is
    # refused by the cell's logits' limit
    wrong={"drop_layers": caught(100, 1, drop_layers=1),  # noqa: F405
           **{w: caught(100, 1, wrong=(w,))  # noqa: F405
              for w in adapter.WRONG}},
    on_gradients=_ungated, seed=53001, gradient_row=512,
    checkpointed="ssm_recurrence", chip_kinds=CHIP_KINDS,
    step_counters=("ssd_dispatch_chunked", "short_conv_dispatch_pallas",
                   "short_conv_dispatch_xla", "attn_dispatch_flash",
                   "attn_qk_prep_fused", "flash_bwd_fused_calls",
                   "moe_dispatch_grouped", "moe_dispatch_gmm",
                   "moe_assignments", "moe_experts_ungated"),
    gauges=("mamba2_layers", "attention_layers", "expert_layers",
            "ssd_chunk_len", "ssd_heads", "ssd_groups", "ssd_state_size",
            "attn_kv_group", "moe_block_rows", "moe_experts_held",
            "moe_experts_total", "moe_latent_width", "flash_blocks_visited",
            "flash_blocks_total"))


def test_every_wrong_model_belongs_to_a_mixer():
    assert sorted(sum(WRONG_BY_MIXER.values(), ())) == sorted(adapter.WRONG)


def test_block_kinds_follow_the_published_pattern():
    from paddle_tpu.models.nemotron_h import NemotronHConfig

    model, _ = SUITE.cell(rehearse=False)
    kinds = [k for _, k in adapter.held_layers(model)]
    assert "".join(k[0] for k in kinds) == "memememaeme"
    assert (kinds.count("mamba2"), kinds.count("experts"),
            kinds.count("attention")) == (5, 5, 1)
    whole = dict(model, hybrid_override_pattern=model[
        "hybrid_override_pattern_published"])
    kinds = [k for _, k in adapter.held_layers(whole)]
    assert (len(kinds), kinds.count("mamba2"), kinds.count("experts"),
            kinds.count("attention")) == (88, 40, 40, 8)
    # the blocks held are the published ones from 0 on
    assert adapter.held_layers(whole)[:11] == adapter.held_layers(model)
    cfg = adapter.config(model)
    assert cfg.layer_kinds() == adapter.held_layers(model)
    assert (cfg.mamba_num_heads, cfg.mamba_n_groups, cfg.mamba_head_dim,
            cfg.ssm_state_size, cfg.mamba_chunk_size,
            cfg.mamba_conv_kernel) == (16, 1, 64, 128, 128, 4)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim) == (4, 1, 128)
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_token,
            cfg.num_shared_experts, cfg.score_func, cfg.expert_form,
            cfg.moe_latent_size, cfg.routed_scaling_factor) == (
        512, 8, 22, 2, "sigmoid", "relu2", 1024, 5)
    assert abs(cfg.out_std - 0.02 / 176 ** 0.5) < 1e-12
    assert NemotronHConfig(rescale_prenorm_residual=False).out_std is None
    with pytest.raises(ValueError, match="one of M, E"):
        NemotronHConfig(hybrid_override_pattern="ME-")
    with pytest.raises(ValueError, match="multiple"):
        NemotronHConfig(moe_shared_expert_intermediate_size=3000)
    with pytest.raises(ValueError, match="groups do not divide"):
        NemotronHConfig(mamba_num_heads=12, mamba_n_groups=8)


# ------------------------------------------ the blocks' own


def test_mamba_mixer_with_the_cells_one_group_and_a_ragged_chunk():
    """One group, as the cell holds it (no split, one norm), on rows of
    37 tokens: two chunks of 16 and a ragged third."""
    m = SUITE.mixer("mamba2", batch=1, seq=37, seed=2,
                    config=dict(AS_AT_WIDTH, n_groups=1))
    assert sum(".norm.group" in n for n in m.names) == 1
    assert rel(m.got, m.want()) < 2e-5


def test_expert_layer_through_the_grouped_kernels(monkeypatch):
    """A latent of 128 and experts of 128: the widths `moe_gmm` takes,
    under the interpreter, ungated."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    m = SUITE.mixer("experts", batch=1, seq=48, seed=2, config=dict(
        AS_AT_WIDTH, moe_latent_size=128, moe_intermediate_size=128,
        moe_shared_expert_intermediate_size=128))
    assert not any(n.endswith("w_gate") for n in m.names)
    assert m.bumped("moe_dispatch_gmm") == 1
    assert m.counters["moe_latent_width"] == 128
    assert rel(m.got, m.want()) < 2e-5


# ------------------------------------------------ the shares add up


def _set(values):
    import paddle_tpu as fluid

    scope = fluid.global_scope()
    for n, v in values.items():
        assert np.shape(scope.get(n)) == v.shape, (n, v.shape)
        scope.set(n, np.ascontiguousarray(v, np.float32))


def _cfg(**kw):
    return SimpleNamespace(hidden_size=32, initializer_range=0.1,
                           rms_norm_eps=1e-5, **kw)


def test_eight_head_shares_add_up_to_the_whole_mamba_mixer():
    """The published split at a small size: 16 heads in 8 groups, a rank
    holding one group's 2 heads, its B and C and its group of the norm.
    The eight shares' outputs (each through the Program's mixer with
    `mamba_num_heads` 2 and one group) add up to the reference's mixer
    with all 16 heads; a share that read its neighbour's B and C does
    not."""
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_parts

    heads, groups, hp, n, hidden, shares = 16, 8, 4, 8, 32, 8
    inner, r = heads * hp, np.random.RandomState(7)
    whole = {"mamba_num_heads": heads, "mamba_head_dim": hp,
             "n_groups": groups, "ssm_state_size": n,
             "layer_norm_epsilon": 1e-5}
    p = {"m.in_proj.w_0": r.randn(hidden, 2 * inner + 2 * groups * n + heads)
         * 0.2,
         "m.conv.w_0": r.uniform(-0.5, 0.5, (inner + 2 * groups * n, 4)),
         "m.conv.b_0": r.uniform(-0.5, 0.5, inner + 2 * groups * n),
         "m.A_log": r.uniform(0, 2.7, heads),
         "m.dt_bias": r.uniform(-4, -1, heads),
         "m.D": r.uniform(0.5, 1.5, heads),
         "m.out_proj.w_0": r.randn(inner, hidden) * 0.2}
    for i in range(groups):
        p[f"m.norm.group{i}.w_0"] = r.uniform(0.5, 1.5, inner // groups)
    p = {k: v.astype(np.float32) for k, v in p.items()}
    u = r.randn(2, 40, hidden).astype(np.float32)
    cfg = _cfg(mamba_num_heads=heads // shares, mamba_head_dim=hp,
               mamba_n_groups=1, ssm_state_size=n, mamba_conv_kernel=4,
               mamba_chunk_size=16)
    x = fluid.layers.data("u", list(u.shape), append_batch_size=False)
    outs = [decoder_parts.mamba2_mixer(x, cfg, f"share{i}")
            for i in range(shares)]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    per = inner // shares  # a share's channels

    def columns(i, of_b=None):
        """A share's columns of [z ; x ; B ; C ; dt] (`of_b`: whose B and
        C it reads), and of the convolution's channels [x ; B ; C]."""
        g = i if of_b is None else of_b
        z = np.arange(i * per, (i + 1) * per)
        bm = inner + np.arange(g * n, (g + 1) * n)
        cm = bm + groups * n
        conv = np.concatenate([z, bm, cm])
        dt = 2 * inner + 2 * groups * n + np.arange(
            i * heads // shares, (i + 1) * heads // shares)
        return np.concatenate([z, inner + conv, dt]), conv

    def share(i, of_b=None):
        cols, conv = columns(i, of_b)
        hs = slice(i * heads // shares, (i + 1) * heads // shares)
        return {f"share{i}.in_proj.w_0": p["m.in_proj.w_0"][:, cols],
                f"share{i}.conv.w_0": p["m.conv.w_0"][conv],
                f"share{i}.conv.b_0": p["m.conv.b_0"][conv],
                f"share{i}.A_log": p["m.A_log"][hs],
                f"share{i}.dt_bias": p["m.dt_bias"][hs],
                f"share{i}.D": p["m.D"][hs],
                f"share{i}.norm.group0.w_0": p[f"m.norm.group{i}.w_0"],
                f"share{i}.out_proj.w_0":
                    p["m.out_proj.w_0"][i * per:(i + 1) * per]}

    for i in range(shares):
        _set(share(i))
    got = exe.run(feed={"u": u}, fetch_list=outs)
    uncut = highest(adapter.mamba_mixer, p, u, "m", whole)
    assert np.abs(uncut).max() > 1e-2
    assert rel(sum(got), uncut) < 1e-5
    # one share alone is the reference's mixer with that share's heads
    one = highest(adapter.mamba_mixer,
                  {k.replace("share3", "m"): v for k, v in share(3).items()},
                  u, "m", dict(whole, mamba_num_heads=2, n_groups=1))
    assert rel(got[3], one) < 1e-5
    _set(share(3, of_b=4))  # the neighbour's B and C
    (other,) = exe.run(feed={"u": u}, fetch_list=[outs[3]])
    assert rel(other, got[3]) > 0.03


def test_eight_head_shares_add_up_to_the_whole_attention_layer():
    """32 query heads over 2 key/value heads, a rank holding 4 query
    heads and the key/value head they read (each key/value head lives on
    four ranks): the eight shares' outputs add up to the reference's
    layer with all the heads."""
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_parts

    h, g, d, hidden, shares = 32, 2, 8, 32, 8
    r = np.random.RandomState(8)
    p = {"m.q.w_0": r.randn(hidden, h * d) * 0.3,
         "m.k.w_0": r.randn(hidden, g * d) * 0.3,
         "m.v.w_0": r.randn(hidden, g * d) * 0.3,
         "m.o.w_0": r.randn(h * d, hidden) * 0.2}
    p = {k: v.astype(np.float32) for k, v in p.items()}
    u = r.randn(2, 24, hidden).astype(np.float32)
    cfg = _cfg(num_attention_heads=h // shares, num_key_value_heads=1,
               head_dim=d)
    x = fluid.layers.data("u", list(u.shape), append_batch_size=False)
    outs = [decoder_parts.attention(x, cfg, f"share{i}", qk_norm=False)
            for i in range(shares)]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    per = h // shares * d
    for i in range(shares):
        kv = i // (shares // g)  # query head n reads n // (h / g)
        _set({f"share{i}.q.w_0": p["m.q.w_0"][:, i * per:(i + 1) * per],
              f"share{i}.k.w_0": p["m.k.w_0"][:, kv * d:(kv + 1) * d],
              f"share{i}.v.w_0": p["m.v.w_0"][:, kv * d:(kv + 1) * d],
              f"share{i}.o.w_0": p["m.o.w_0"][i * per:(i + 1) * per]})
    got = exe.run(feed={"u": u}, fetch_list=outs)
    uncut = highest(adapter.attention_mixer, p, u, "m", {
        "num_attention_heads": h, "num_key_value_heads": g, "head_dim": d})
    assert np.abs(uncut).max() > 1e-2
    assert rel(sum(got), uncut) < 1e-5
    assert rel(sum(got[:7]), uncut) > 0.05


@pytest.mark.parametrize("total,held,k", [(128, 2, 5), (512, 8, 22)])
def test_the_64_expert_shares_add_up_to_the_uncut_layer(total, held, k):
    """Sixty-four shares' routed parts, each back through its copy of
    `W_lat_out`, and the shared expert counted once, equal the
    reference's layer with all the experts held: the published 512
    experts 8 a share and 22 a token with the scaling of 5, and a small
    layer. The router reads the token, the experts its latent."""
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_parts

    r = np.random.RandomState(total)
    hidden, latent, width, shares = 32, 16, 8, 64
    assert total == shares * held
    p = {"m.moe.gate": r.randn(hidden, total) * 0.5,
         "m.moe.bias": r.randn(total) * 0.1,
         "m.moe.w_up": r.randn(total, latent, width) * 0.3,
         "m.moe.w_down": r.randn(total, width, latent) * 0.3,
         "m.latent_in.w_0": r.randn(hidden, latent) * 0.3,
         "m.latent_out.w_0": r.randn(latent, hidden) * 0.3,
         "m.shared.up.w_0": r.randn(hidden, 2 * width) * 0.3,
         "m.shared.down.w_0": r.randn(2 * width, hidden) * 0.3}
    p = {n: v.astype(np.float32) for n, v in p.items()}
    u = r.randn(2, 24, hidden).astype(np.float32)
    x = fluid.layers.data("u", list(u.shape), append_batch_size=False)

    def share(i):
        cfg = _cfg(num_experts=total, experts_held=held, held_from=i * held,
                   moe_intermediate_size=width, num_experts_per_token=k,
                   routed_scaling_factor=5.0, moe_renormalize=True,
                   router_bias_scale=0.1, score_func="sigmoid",
                   num_shared_experts=0, moe_latent_size=latent,
                   expert_form="relu2")
        return decoder_parts.expert_ffn(x, cfg, f"share{i}", norm_eps=1e-20)

    def hold(i, order, lo):
        # layer `i` holds `held` experts from `lo` of the experts in `order`
        mine = order[lo:lo + held]
        _set({f"share{i}.moe.gate": p["m.moe.gate"][:, order],
              f"share{i}.moe.bias": p["m.moe.bias"][order],
              f"share{i}.moe.w_up": p["m.moe.w_up"][mine],
              f"share{i}.moe.w_down": p["m.moe.w_down"][mine],
              f"share{i}.latent_in.w_0": p["m.latent_in.w_0"],
              f"share{i}.latent_out.w_0": p["m.latent_out.w_0"]})

    routed, loads = routed_shares(share, hold, {"u": u}, shares, total, held)
    assert len(routed) == shares
    assert int(np.sum(loads)) == u.shape[0] * u.shape[1] * k
    layer = {"num_experts_per_tok": k, "n_routed_experts": total,
             "held_from": 0, "norm_topk_prob": True,
             "routed_scaling_factor": 5.0}
    uncut = highest(adapter.expert_layer, p, u, "m", layer)
    routed_only = highest(adapter.expert_layer, p, u, "m",
                          dict(layer, shared_expert=False))
    shared = uncut - routed_only  # what every chip computes alike
    assert np.abs(shared).max() > 1e-3
    assert rel(sum(routed), routed_only) < 1e-5
    assert rel(sum(routed) + shared, uncut) < 1e-5
    # sixty-four shares that each added their shared expert would count
    # it sixty-four times
    assert rel(sum(routed) + shares * shared, uncut) > 0.5
    # and one share alone is the reference's share
    p_share = dict(p, **{f"m.moe.{w}": p[f"m.moe.{w}"][held:2 * held]
                         for w in ("w_up", "w_down")})
    one = highest(adapter.expert_layer, p_share, u, "m",
                  dict(layer, n_routed_experts=held, held_from=held,
                       shared_expert=False))
    assert rel(routed[1], one) < 1e-5


def test_the_vocabularys_slices_give_the_whole_logits_columns():
    """The final norm and the untied head on one residual stream: eight
    slices' logits side by side are the whole head's."""
    import paddle_tpu as fluid
    from paddle_tpu.models import decoder_parts

    hidden, vocab, slices = 32, 64, 8
    r = np.random.RandomState(9)
    w = r.uniform(0.5, 1.5, hidden).astype(np.float32)
    head = (r.randn(hidden, vocab) * 0.3).astype(np.float32)
    xs = r.randn(2, 12, hidden).astype(np.float32)
    cfg = _cfg()
    x = fluid.layers.data("x", list(xs.shape), append_batch_size=False)
    outs = [decoder_parts.proj(
        decoder_parts.norm(x, f"slice{i}.final_norm", cfg),
        vocab // slices, f"slice{i}.head", cfg) for i in range(slices)]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    per = vocab // slices
    for i in range(slices):
        _set({f"slice{i}.final_norm.w_0": w,
              f"slice{i}.head.w_0": head[:, i * per:(i + 1) * per]})
    got = np.concatenate(exe.run(feed={"x": xs}, fetch_list=outs), -1)
    whole = highest(lambda xs, w, head: adapter._rms(xs, w, 1e-5) @ head,
                    xs, w, head)
    assert rel(got, whole) < 1e-5


# ------------------------------------------- gauges, counters, the cell


def test_gauges_and_counters_at_the_rehearsal_size(monkeypatch):
    from paddle_tpu import profiler

    # no interpreter, whatever a test file imported before this one set
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    model, traffic = SUITE.cell()
    before = profiler.counters()
    with guards():
        main, eval_prog, built, exe, names = SUITE.built_model(model, traffic)
        batch = SUITE.batch_for(model, traffic)
        loads = exe.run(main, feed=batch, fetch_list=built["loads"])
    after = profiler.counters()
    assert {n: after[n] for n in (
        "mamba2_layers", "attention_layers", "expert_layers",
        "moe_experts_held", "moe_experts_total", "moe_latent_width",
        "attn_kv_group", "ssd_chunk_len", "ssd_heads", "ssd_groups",
        "ssd_state_size")} == {
        "mamba2_layers": 2, "attention_layers": 1, "expert_layers": 2,
        "moe_experts_held": 2, "moe_experts_total": 8,
        "moe_latent_width": 32, "attn_kv_group": 2, "ssd_chunk_len": 16,
        "ssd_heads": 4, "ssd_groups": 2, "ssd_state_size": 16}

    def bumped(name):
        return after.get(name, 0) - before.get(name, 0)

    # two Mamba-2 blocks: the forward op's lowering only (the gradient op
    # reads Starts); two expert layers, the forward op's lowering and the
    # gradient op's replay
    assert bumped("ssd_dispatch_chunked") == 2
    assert bumped("short_conv_dispatch_xla") == 2
    assert bumped("moe_dispatch_grouped") == 4
    assert bumped("moe_experts_ungated") == 2  # once a layer built
    tokens = traffic["batch"] * traffic["seq_len"]
    assert bumped("moe_assignments") == 4 * tokens * model[
        "num_experts_per_tok"]
    assert bumped("moe_route_softmax") == 0
    assert bumped("attn_dispatch_xla") == 2
    assert bumped("attn_qk_prep_fused") == 0  # no norm, no positions
    ops = main.global_block().ops
    types = [op.type for op in ops]
    assert types.count("short_conv1d") == 2
    assert types.count("ssd_scan") == types.count("ssd_scan_grad") == 2
    assert types.count("selective_scan") == 0
    assert types.count("fused_multihead_attention") == 1
    assert types.count("moe_experts") == 2
    assert types.count("rotary_embedding") == 0
    scans = [op for op in ops if op.type == "ssd_scan"]
    assert all(op.attr("n_groups") == 2 and op.attr("chunk_size") == 16
               for op in scans)
    experts = [op for op in ops if op.type == "moe_experts"]
    assert all(op.attr("score_func") == "sigmoid" and op.attr("renormalize")
               and op.attr("expert_form") == "relu2"
               and op.attr("scaling") == 5.0 and op.input("XExperts")
               and not op.input("WGate") for op in experts)
    (attn,) = [op for op in ops if op.type == "fused_multihead_attention"]
    assert not attn.attr("rope_theta") and not attn.attr("window")
    assert not attn.input("QNorm")
    assert len(loads) == 2 and all(x.shape == (2,) for x in loads)


def test_parameters_and_flops_of_the_cell():
    from benchmark.runners import train_loop

    model, traffic = SUITE.cell(rehearse=False)
    assert (traffic["batch"], traffic["seq_len"]) == (1, 4096)
    # ISSUE 53's arithmetic, redone
    mamba = 4096 * (1024 + 1280 + 16) + 1024 * 4096
    attn = 4096 * (512 + 256) + 512 * 4096
    router, latent, shared = 4096 * 512, 2 * 4096 * 1024, 2 * 4096 * 5376
    expert = 2 * 1024 * 2688
    assert [adapter.block_matrix_params(model, k) for k in (
        "mamba2", "attention")] == [mamba, attn]
    # a balanced router sends a token's 22 assignments to the 8 of 512
    # held 0.34 of an expert's worth
    assert adapter.block_matrix_params(model, "experts") == (
        router + latent + shared + expert * 22 * 8 / 512)
    per_token = adapter.matrix_params_per_token(model)
    assert per_token == (5 * mamba + attn + 5 * (
        router + latent + shared + expert * 22 * 8 / 512) + 4096 * 16384)
    assert abs(per_token / 1e6 - 422.9) < 0.05
    held = (5 * (mamba + 1280 * 5 + 3 * 16 + 1024) + attn
            + 5 * (router + 512 + latent + shared + 8 * expert)
            + 12 * 4096 + 2 * 4096 * 16384)
    assert abs(held / 1e6 - 700.9) < 0.05  # the parameters held
    pairs = 4096 * 4097 // 2
    flops = adapter.flops_per_example(model, traffic)
    assert flops == 3.0 * (2 * 4096 * per_token + pairs * 4 * 4 * 128
                           + 5 * 4096 * 16 * 4 * 64 * 128)
    assert 10.4e12 < flops < 10.5e12
    # the assignments a layer and a step
    assert 4096 * model["num_experts_per_tok"] == 90112

    # the count the program reports
    import paddle_tpu as fluid

    for rehearse, want in ((True, None), (False, held)):
        m, t = SUITE.cell(rehearse=rehearse)
        blocks = [k for _, k in adapter.held_layers(m)]
        with fluid.program_guard(fluid.Program(), fluid.Program()), \
                fluid.unique_name.guard():
            main, _, built, _ = train_loop.build_programs(
                fluid, adapter, m, t, 3)
            params = main.global_block().all_parameters()
        names = [p.name for p in params]
        assert len(names) == len(set(names)) == (
            2 + len(blocks) + 1
            + blocks.count("mamba2") * (7 + m["n_groups"])
            + blocks.count("attention") * 4 + blocks.count("experts") * 8)
        if want:
            assert sum(int(np.prod(p.shape)) for p in params) == want
        assert built["feeds"] == ["tokens", "labels"]
        assert len(built["loads"]) == blocks.count("experts")


if __name__ == "__main__":
    main(SUITE)
