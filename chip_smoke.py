"""Smoke test on the chip: the quickest proof that the system still starts.

    python chip_smoke.py              # needs a TPU; fails without one
    python chip_smoke.py --rehearse   # CPU rehearsal at BertConfig.tiny()

Drives the main path once through the entry points a user calls, at the
full width of BERT-base (12 layers, hidden 768, vocabulary 30,522; random
weights from a seed), one phase after another, each in a child process
that is the only holder of the chip while it runs. This parent only
orchestrates: it never initialises a JAX backend.

  train   fluid.Executor(fluid.TPUPlace()) -> startup program -> five
          exe.run steps on one fixed batch (b=256, s=128, Adam under
          mixed precision) -> one run_repeated(steps=5). Losses finite
          and falling; the first step's seconds are set-up time.
  kernels (same child) the lowered step holds the Mosaic custom call for
          ln_bwd; flash_attention forward and backward compile at a shape
          its dispatch picks by default and agree with _xla_attention.
  export  (same child) the encoder is saved with save_inference_model and
          its own AnalysisPredictor answers one seeded batch.
  serve   ServingFleet(replicas=1, worker_device="tpu") answers that batch
          eight times through the router, within 1e-2 of the predictor;
          ready file and /healthz name the device the worker initialised.
  dp4     with four chips or more: the same program through
          CompiledProgram.with_data_parallel on a batch=4 mesh, global
          batch 1,024, three steps; state and feeds on four devices.

Every phase prints the device and the JAX, jaxlib and libtpu versions and
fails unless the platform is `tpu`. The last line of standard output is
one JSON object, {"ok": true, "device": {...}}, printed only when every
phase passed; any failure exits non-zero without it.

Set-up seconds and cache entry counts are printed per run; run it twice
in one command to see the compile cache cold and warm. It reports no
rate and no utilization.
"""

from __future__ import annotations

import argparse
import io
import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

PHASE_TIMEOUT_S = 900  # one child; the whole run has 1200 s

# (config, batch, seq, masked positions, served batch, learning rate,
#  flash check shape [b, h, s, d], data-parallel global batch)
REAL = dict(cfg="base", b=256, s=128, preds=20, serve_b=8, lr=1e-4,
            flash=(2, 12, 2048, 64), dp_b=1024)
# the rehearsal's learning rate is larger because 32 masked positions of a
# 2-layer model do not show ten 1e-4 steps above their dropout noise
REHEARSAL = dict(cfg="tiny", b=8, s=16, preds=4, serve_b=2, lr=1e-2,
                 flash=(1, 2, 256, 64), dp_b=16)


def say(*a):
    print(*a, flush=True)


# ------------------------------------------------------------- children


def device_report(phase, rehearse):
    """Print what JAX initialised here; fail unless it is a TPU."""
    from importlib import metadata

    import jax
    import jaxlib

    dev = jax.devices()[0]
    report = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say(f"[{phase}] device: platform={dev.platform} "
        f"device_kind={dev.device_kind!r} count={report['count']} | "
        f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {metadata.version('libtpu')}")
    if dev.platform != "tpu" and not rehearse:
        raise SystemExit(f"[{phase}] FAIL: platform is {dev.platform!r}, "
                         "not 'tpu'")
    return report


def cache_entries():
    from paddle_tpu.jit_compile import COMPILE_CACHE_DIR

    try:
        return len(os.listdir(COMPILE_CACHE_DIR))
    except FileNotFoundError:
        return 0


def build_train_program(size, batch):
    """BERT pretraining as the benchmark's bert_base cells build it."""
    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp
    from paddle_tpu.models.bert import BertConfig, build_bert_pretrain

    cfg = getattr(BertConfig, size["cfg"])()
    handles = build_bert_pretrain(cfg, batch, size["s"], mlm_only=True,
                                  max_preds=size["preds"])
    mp.decorate(fluid.optimizer.Adam(size["lr"])).minimize(handles["loss"])
    return cfg, handles["loss"].name


def check_losses(phase, losses, vocab):
    import numpy as np

    losses = [float(x) for x in losses]
    say(f"[{phase}] losses: {[round(x, 4) for x in losses]} "
        f"(ln vocab = {math.log(vocab):.3f})")
    if not np.isfinite(losses).all():
        raise SystemExit(f"[{phase}] FAIL: non-finite loss")
    if abs(losses[0] - math.log(vocab)) > 1.0:
        raise SystemExit(f"[{phase}] FAIL: first loss {losses[0]:.3f} is not "
                         f"near ln {vocab} on fresh weights")
    return losses


def phase_train(size, workdir, rehearse):
    import numpy as np

    report = device_report("train", rehearse)
    import paddle_tpu as fluid
    from __graft_entry__ import _bert_feed
    from paddle_tpu.jit_compile import COMPILE_CACHE_DIR

    entries0 = cache_entries()
    say(f"[train] compile cache: {COMPILE_CACHE_DIR} ({entries0} entries)")
    b, s = size["b"], size["s"]
    cfg, loss_name = build_train_program(size, b)
    main = fluid.default_main_program()
    place = fluid.CPUPlace() if rehearse else fluid.TPUPlace()
    exe = fluid.Executor(place)
    exe.run(fluid.default_startup_program())
    feed = _bert_feed(np.random.RandomState(0), cfg, b, s,
                      max_preds=size["preds"])

    t0 = time.perf_counter()
    (first,) = exe.run(feed=feed, fetch_list=[loss_name])
    first_step_s = time.perf_counter() - t0
    losses = [first[0]]
    for _ in range(4):
        (lv,) = exe.run(feed=feed, fetch_list=[loss_name])
        losses.append(lv[0])
    t0 = time.perf_counter()
    (stacked,) = exe.run_repeated(feed=feed, fetch_list=[loss_name], steps=5)
    repeated_s = time.perf_counter() - t0
    losses = check_losses(
        "train", losses + list(np.asarray(stacked).reshape(-1)),
        cfg.vocab_size)
    if not losses[-1] < losses[0]:
        raise SystemExit("[train] FAIL: loss did not fall over ten steps")
    say(f"[train] {cfg.num_layers} layers x hidden {cfg.hidden_size}, "
        f"b={b} s={s}: 5 exe.run steps + run_repeated(5) OK; set-up time: "
        f"first step {first_step_s:.1f} s, run_repeated's first call "
        f"{repeated_s:.1f} s (trace + compile + run)")

    phase_kernels(exe, main, feed, loss_name, size, rehearse)
    phase_export(exe, cfg, size, workdir, rehearse)

    entries1 = cache_entries()
    say(f"[train] compile cache entries: {entries0} -> {entries1}")
    report.update(first_loss=losses[0], first_step_s=round(first_step_s, 1),
                  cache_entries=[entries0, entries1])
    return report


def phase_kernels(exe, main, feed, loss_name, size, rehearse):
    """The Pallas kernels on BERT's default path were compiled by Mosaic."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.ops import fused_ops
    from paddle_tpu.ops.pallas.flash_attention import (
        _xla_attention,
        flash_attention,
    )

    # the step that just ran, lowered again (same idiom as
    # tools/bench_passes.py): its text names every Mosaic custom call
    scope = fluid.global_scope()
    compiled, feeds, _ = exe._prepare_run(main, feed, [loss_name], scope)
    state = exe._assemble_state(compiled, scope)
    text = compiled.jit_fn.lower(state, feeds, jax.random.key(0)).as_text()
    n_ln = text.count('kernel_name = "ln_bwd"')
    say(f"[kernels] train step: {text.count('@tpu_custom_call')} Mosaic "
        f"custom calls, {n_ln} of them ln_bwd")
    if rehearse:
        say("[kernels] rehearsal: kernels run in the Pallas interpreter and "
            "hidden 64 is below ln_bwd's size; nothing is asserted here")
    elif n_ln == 0 or "@tpu_custom_call" not in text:
        raise SystemExit("[kernels] FAIL: the train step holds no Mosaic "
                         "custom call for ln_bwd")

    b, h, s, d = size["flash"]
    rng = np.random.RandomState(1)
    q, k, v, w = (jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
                  for _ in range(4))
    bias = jnp.asarray(np.where(rng.rand(b, s) < 0.9, 0.0, -1e4), jnp.float32)
    if not rehearse and fused_ops.attention_path(
            q.shape, k.shape, v.shape, layout="bhsd", causal=False, window=0,
            group=1, mesh=None) != "flash":
        raise SystemExit(f"[kernels] FAIL: dispatch does not pick the flash "
                         f"kernel at s={s}")
    scale = 1.0 / math.sqrt(d)

    def run(attend):
        def loss(q, k, v):
            out = attend(q, k, v)
            return jnp.sum((out * w).astype(jnp.float32)), out

        (_, out), grads = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
        )(q, k, v)
        return [np.asarray(x, np.float32) for x in (out, *grads)]

    got = run(lambda q, k, v: flash_attention(q, k, v, bias=bias))
    want = run(lambda q, k, v: _xla_attention(
        q, k, v, bias, False, scale, 0.0, None))
    for name, g, r in zip(("out", "dq", "dk", "dv"), got, want):
        err = float(np.max(np.abs(g - r)))
        bound = 2e-2 * max(1.0, float(np.max(np.abs(r))))
        say(f"[kernels] flash_attention {name} [{b},{h},{s},{d}] bf16 + key "
            f"bias vs _xla_attention: max abs err {err:.2e} "
            f"(bound {bound:.2e})")
        if not (np.isfinite(g).all() and err <= bound):
            raise SystemExit(f"[kernels] FAIL: flash_attention {name} "
                             "disagrees with _xla_attention")


def phase_export(exe, cfg, size, workdir, rehearse):
    """Save the trained encoder's inference program; record what this
    process's own predictor answers for one seeded batch."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor
    from paddle_tpu.models.bert import bert_encoder

    bs, s = size["serve_b"], size["s"]
    infer = fluid.Program()
    # the usual Fluid pairing: a second program at the serving batch whose
    # parameters are, by name, the ones the train program just updated
    with fluid.program_guard(infer, fluid.Program()), \
            fluid.unique_name.guard():
        ids = [layers.data(n, [bs, s], dtype="int64", append_batch_size=False)
               for n in ("src_ids", "sent_ids", "pos_ids")]
        mask = layers.data("input_mask", [bs, s], dtype="float32",
                           append_batch_size=False)
        hidden = bert_encoder(*ids, mask, cfg, is_test=True)
    feed_names = ["src_ids", "sent_ids", "pos_ids", "input_mask"]
    model_dir = os.path.join(workdir, "bert_encoder")
    fluid.io.save_inference_model(model_dir, feed_names, [hidden], exe,
                                  main_program=infer)

    rng = np.random.RandomState(2)
    batch = {
        "src_ids": rng.randint(0, cfg.vocab_size, (bs, s)).astype("int64"),
        "sent_ids": rng.randint(0, 2, (bs, s)).astype("int64"),
        "pos_ids": np.tile(np.arange(s), (bs, 1)).astype("int64"),
        "input_mask": np.ones((bs, s), "float32"),
    }
    config = AnalysisConfig(model_dir)
    if not rehearse:
        config.enable_use_gpu()
    (out,) = create_paddle_predictor(config).run(batch)
    if out.shape != (bs, s, cfg.hidden_size) or not np.isfinite(out).all():
        raise SystemExit(f"[export] FAIL: predictor output {out.shape}")
    np.savez(os.path.join(workdir, "serve_batch.npz"), **batch)
    np.save(os.path.join(workdir, "serve_expected.npy"), out)
    say(f"[export] saved {model_dir}; AnalysisPredictor output {out.shape} "
        "finite")


def phase_dp4(size, first_loss_one_chip, rehearse):
    import numpy as np

    report = device_report("dp4", rehearse)
    import paddle_tpu as fluid
    from __graft_entry__ import _bert_feed

    b, s = size["dp_b"], size["s"]
    cfg, loss_name = build_train_program(size, b)
    main = fluid.default_main_program()
    exe = fluid.Executor(fluid.CPUPlace() if rehearse else fluid.TPUPlace())
    exe.run(fluid.default_startup_program())
    # four devices whatever the host has more of: a batch=4 mesh
    cp = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss_name, places=4)
    feed = _bert_feed(np.random.RandomState(0), cfg, b, s,
                      max_preds=size["preds"])
    t0 = time.perf_counter()
    (first,) = exe.run(cp, feed=feed, fetch_list=[loss_name])
    first_step_s = time.perf_counter() - t0
    losses = [first.reshape(-1)[0]]
    for _ in range(2):
        (lv,) = exe.run(cp, feed=feed, fetch_list=[loss_name])
        losses.append(lv.reshape(-1)[0])
    losses = check_losses("dp4", losses, cfg.vocab_size)
    if abs(losses[0] - first_loss_one_chip) > 1.0:
        raise SystemExit(f"[dp4] FAIL: first loss {losses[0]:.3f} is far "
                         f"from the one-chip phase's "
                         f"{first_loss_one_chip:.3f}")

    # where the step's arguments live: the state as the scope now holds
    # it, the feeds as the step was jitted to take them
    scope = fluid.global_scope()
    compiled, _, _ = exe._prepare_run(main, feed, [loss_name], scope, cp)
    state = exe._assemble_state(compiled, scope)
    mesh = compiled.mesh
    say(f"[dp4] mesh {dict(mesh.shape)} over "
        f"{[d.id for d in mesh.devices.flat]}")
    on_one = [n for n, v in state.items()
              if len({sh.device for sh in v.addressable_shards}) != 4]
    feed_sh = compiled.feed_shardings
    unsharded = [n for n, sh in feed_sh.items()
                 if len(sh.device_set) != 4 or sh.is_fully_replicated]
    n_params = len(main.global_block().all_parameters())
    say(f"[dp4] {len(state)} state arrays ({n_params} parameters, the rest "
        f"optimizer state): {len(state) - len(on_one)} have shards on 4 "
        f"distinct devices; {len(feed_sh) - len(unsharded)} of "
        f"{len(feed_sh)} feeds are split over 4 devices")
    if on_one or unsharded:
        raise SystemExit(f"[dp4] FAIL: not on four devices: state "
                         f"{on_one[:5]} feeds {unsharded}")
    say(f"[dp4] global batch {b}: three steps OK; set-up time: first step "
        f"{first_step_s:.1f} s")
    return report


# --------------------------------------------------------------- parent


def run_child(phase, workdir, rehearse, extra=()):
    """One phase in its own process, the only holder of the chip while
    it runs. Its result comes back through a file; a child that fails or
    outlives its time limit fails the run."""
    result = os.path.join(workdir, f"{phase}.json")
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--workdir", workdir, *extra]
    if rehearse:
        cmd.append("--rehearse")
    try:
        rc = subprocess.run(cmd, cwd=REPO,
                            timeout=PHASE_TIMEOUT_S).returncode
    except subprocess.TimeoutExpired:  # run() has killed the child
        raise SystemExit(f"[{phase}] FAIL: no result in {PHASE_TIMEOUT_S} s")
    if rc != 0 or not os.path.exists(result):
        raise SystemExit(f"[{phase}] FAIL: child exited {rc}")
    with open(result) as f:
        return json.load(f)


def phase_serve(workdir, device):
    """One served replica on the chip, driven through the fleet's router.
    The train child has exited, so the worker is the chip's only holder."""
    import numpy as np

    from paddle_tpu.inference.fleet import ServingFleet

    expected = np.load(os.path.join(workdir, "serve_expected.npy"))
    with open(os.path.join(workdir, "serve_batch.npz"), "rb") as f:
        body = f.read()
    fleet = ServingFleet(
        os.path.join(workdir, "bert_encoder"), replicas=1,
        worker_device=device["platform"], ready_timeout_s=600.0)
    t0 = time.perf_counter()
    try:
        fleet.start()
        rep = fleet.supervisor.replicas[0]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{rep.port}/healthz", timeout=30) as r:
            health = json.load(r)
        say(f"[serve] worker pid {rep.pid} ready in "
            f"{time.perf_counter() - t0:.1f} s (set-up time, warm-up "
            f"{rep.warmup_ms} ms): ready file platform={rep.platform} "
            f"device_kind={rep.device_kind!r}; /healthz "
            f"platform={health.get('platform')} "
            f"device_kind={health.get('device_kind')!r}")
        for src in (vars(rep), health):
            if (src.get("platform"), src.get("device_kind")) != (
                    device["platform"], device["kind"]):
                raise SystemExit(
                    "[serve] FAIL: the worker is not on the device the "
                    f"train phase used ({device['platform']}, "
                    f"{device['kind']!r})")
        worst = 0.0
        for i in range(8):
            req = urllib.request.Request(
                fleet.base_url + "/predict", data=body, method="POST")
            with urllib.request.urlopen(req, timeout=120) as r:
                reply = np.load(io.BytesIO(r.read()))
            (got,) = (reply[n] for n in reply.files)
            if got.shape != expected.shape:
                raise SystemExit(f"[serve] FAIL: request {i} returned "
                                 f"{got.shape}, expected {expected.shape}")
            worst = max(worst, float(np.max(np.abs(got - expected))))
        say(f"[serve] 8 /predict requests through the router: output "
            f"{expected.shape}, max abs difference from the in-process "
            f"predictor {worst:.2e} (bound 1e-2)")
        if not worst <= 1e-2:
            raise SystemExit("[serve] FAIL: served outputs differ from the "
                             "in-process predictor")
    finally:
        fleet.stop()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at BertConfig.tiny(), Pallas "
                    "interpreted; proves the script, not the chip")
    ap.add_argument("--phase", choices=["train", "dp4"],
                    help=argparse.SUPPRESS)  # the parent's call to a child
    ap.add_argument("--workdir", help=argparse.SUPPRESS)
    ap.add_argument("--first-loss", type=float, help=argparse.SUPPRESS)
    args = ap.parse_args()
    size = REHEARSAL if args.rehearse else REAL

    if args.phase:  # a child: holds the device, writes its result, exits
        if args.phase == "train":
            result = phase_train(size, args.workdir, args.rehearse)
        else:
            result = phase_dp4(size, args.first_loss, args.rehearse)
        with open(os.path.join(args.workdir, f"{args.phase}.json"), "w") as f:
            json.dump(result, f)
        return

    if args.rehearse:
        say("REHEARSAL: BertConfig.tiny() on the CPU with Pallas "
            "interpreted. This proves the script runs; it says nothing "
            "about the chip.")
        # every process this one starts inherits the CPU environment
        os.environ.update(
            JAX_PLATFORMS="cpu", PADDLE_TPU_PALLAS_INTERPRET="1",
            XLA_FLAGS="--xla_force_host_platform_device_count=4")
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    t0 = time.perf_counter()
    try:
        train = run_child("train", workdir, args.rehearse)
        device = {k: train[k] for k in ("platform", "kind", "count")}
        phase_serve(workdir, device)
        if device["count"] >= 4:
            run_child("dp4", workdir, args.rehearse,
                      ["--first-loss", str(train["first_loss"])])
        else:
            say(f"[dp4] not run ({device['count']} chips)")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    say(f"all phases passed in {time.perf_counter() - t0:.0f} s")
    final = {"ok": True, "device": device}
    if args.rehearse:
        final["rehearsal"] = True
    say(json.dumps(final))


if __name__ == "__main__":
    main()
