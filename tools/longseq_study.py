"""Long-sequence scaling study (VERDICT round-4 #5 / SURVEY M6 exit):

1. On the chip: BERT-base-width encoder train step at s=512..4096
   (tokens/batch held at 32k), flash (Pallas blocked) vs XLA attention
   FORCED per run — the cutover measured, not assumed.
2. On the virtual CPU mesh (no chip needed): the same trunk under
   sp=1/2/4 ring attention, per-device bytes of the sharded
   sequence-axis tensors recorded — the memory story that makes long
   context feasible at all.

Each (s, path) runs in a subprocess because the flash cutover constant
and the backend are fixed at import/init time.

Usage:
  python tools/longseq_study.py chip         # the 8 chip configs
  python tools/longseq_study.py mesh         # the sp memory table (CPU)
  python tools/longseq_study.py one S MODE   # inner: one chip config
  python tools/longseq_study.py table STUDY.jsonl [MORE.jsonl ...] [OUT.json]
      # fold chip-sweep JSONL(s) into the dispatch table consumed by
      # ops/fused_ops.py (default OUT: the checked-in
      # paddle_tpu/ops/pallas/attn_dispatch_table.json). Inputs may be
      # partial and/or concatenated across chip sessions: unmatched
      # (s, mode) halves wait for a later session, already-measured s
      # values persist, and the regeneration is recorded through the
      # keyed artifacts accessor (round 20)
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

TOKENS_PER_BATCH = 32768
SEQS = [512, 1024, 2048, 4096]


def run_one(s: int, mode: str) -> None:
    """One (seq, attention-path) measurement on the current backend."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.models.bert import (
        BertConfig,
        bert_flops_per_token,
        build_bert_pretrain,
    )
    from __graft_entry__ import _bert_feed, _fresh_programs

    b = max(TOKENS_PER_BATCH // s, 1)
    cfg = BertConfig(
        vocab_size=30522, hidden_size=768, num_layers=4, num_heads=12,
        intermediate_size=3072, max_position=max(SEQS),
        hidden_dropout=0.0, attention_dropout=0.0,
    )
    max_preds = max(1, s * 20 // 128)
    _fresh_programs()
    handles = build_bert_pretrain(cfg, b, s, mlm_only=True,
                                  max_preds=max_preds)
    from paddle_tpu.contrib import mixed_precision as mp

    opt = mp.decorate(fluid.optimizer.Adam(1e-4))
    opt.minimize(handles["loss"])
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = _bert_feed(rng, cfg, b, s, max_preds=max_preds)
    feed = {k: jax.device_put(jnp.asarray(v)) for k, v in feed.items()}
    loss_name = handles["loss"].name
    t0 = time.time()
    (lv,) = exe.run(feed=feed, fetch_list=[loss_name])
    compile_s = time.time() - t0
    for _ in range(3):
        exe.run(feed=feed, fetch_list=[loss_name], return_numpy=False)
    steps = 10
    dts = []
    for _ in range(3):
        t0 = time.time()
        for _ in range(steps):
            out = exe.run(feed=feed, fetch_list=[loss_name],
                          return_numpy=False)
        np.asarray(out[0])
        dts.append(time.time() - t0)
    dt = min(dts)
    tok_s = b * s * steps / dt
    import jax

    from paddle_tpu.place import peak_bf16_flops

    flops_tok = bert_flops_per_token(cfg, seq_len=s, max_preds=max_preds)
    mfu = tok_s * flops_tok / peak_bf16_flops(jax.devices()[0].device_kind)
    print(json.dumps({
        "s": s, "b": b, "mode": mode,
        "ms_step": round(dt / steps * 1e3, 1),
        "tok_s": round(tok_s, 0), "mfu": round(mfu, 4),
        "compile_s": round(compile_s, 1),
        "loss": round(float(np.asarray(lv).reshape(-1)[0]), 3),
    }), flush=True)


def chip_sweep() -> None:
    for s in SEQS:
        for mode in ("xla", "flash"):
            env = dict(os.environ)
            # force the path: cutover by score bytes -> 0 = always flash,
            # huge = never flash
            env["PADDLE_TPU_FLASH_SCORE_BYTES"] = (
                "0" if mode == "flash" else str(1 << 62))
            env["PYTHONPATH"] = ROOT
            p = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "one",
                 str(s), mode],
                env=env, cwd=ROOT, capture_output=True, text=True,
                timeout=1500,
            )
            emitted = False
            for line in p.stdout.splitlines():
                if line.startswith("{"):
                    print(line, flush=True)
                    emitted = True
            if not emitted:
                print(json.dumps({
                    "s": s, "mode": mode, "rc": p.returncode,
                    "error": p.stderr[-300:],
                }), flush=True)


def mesh_memory() -> None:
    """sp=1/2/4 ring attention on the virtual CPU mesh: per-device bytes
    of the sequence-sharded activations (the long-context enabler)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "").replace(
            "--xla_force_host_platform_device_count=8", "")
        + " --xla_force_host_platform_device_count=8").strip()
    env["PYTHONPATH"] = ROOT
    env["_LONGSEQ_MESH_INNER"] = "1"
    p = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "mesh_inner"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=1800,
    )
    sys.stdout.write(p.stdout)
    if p.returncode != 0:
        sys.stderr.write(p.stderr[-2000:])
        sys.exit(p.returncode)


def mesh_inner() -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.ops.pallas.ring_attention import ring_attention
    from paddle_tpu.parallel import make_mesh

    b, h, d = 2, 4, 64
    s = 4096
    rng = np.random.RandomState(0)
    qkv = [jnp.asarray(rng.randn(b, h, s, d).astype("float32") * 0.1)
           for _ in range(3)]
    for sp in (1, 2, 4):
        if sp == 1:
            q, k, v = qkv
            out = jnp.einsum(
                "bhqd,bhkd->bhqk", q, k)  # score tensor materializes
            per_dev_score = out.size * out.dtype.itemsize
            per_dev_act = sum(x.size * x.dtype.itemsize for x in qkv)
            del out
        else:
            mesh = make_mesh({"sp": sp}, devices=jax.devices()[:sp])
            sh = NamedSharding(mesh, P(None, None, "model", None))
            q, k, v = [jax.device_put(x, sh) for x in qkv]

            # GSPMD-native: ring_attention takes the GLOBAL arrays; the
            # sequence dim rides the unified mesh's 'model' axis
            out = jax.jit(lambda q, k, v: ring_attention(
                q, k, v, "model", axis_size=sp
            ))(q, k, v)
            out.block_until_ready()
            per_dev_act = sum(
                max(sh_.data.size * x.dtype.itemsize
                    for sh_ in x.addressable_shards)
                for x in (q, k, v))
            # ring attention never materializes the [s, s] scores; the
            # per-device working set is one [s/sp, s/sp] chunk pair
            per_dev_score = (s // sp) * (s // sp) * 4 * b * h
        print(json.dumps({
            "sp": sp, "s": s,
            "per_device_qkv_mb": round(per_dev_act / 1e6, 2),
            "per_device_score_working_mb": round(per_dev_score / 1e6, 2),
        }), flush=True)


def emit_table(study_paths, out_path: str | None = None) -> None:
    """Fold chip-sweep JSONL(s) into the dispatch table ops/fused_ops.py
    loads: the flash_min_seq threshold is the smallest measured s where
    the flash path beats XLA, and every (s, xla_ms, flash_ms) pair is
    recorded as a `measured` row with its winner. Thresholds not
    derivable from the study (score-bytes knee, ring floor) keep their
    existing values.

    Round 20: the input may be PARTIAL or MERGED — several chip sessions
    concatenated into one JSONL, or passed as multiple files (a session
    cut short costs the missing configs, not the table). Within
    one (s, mode) the LAST row wins (later sessions supersede earlier
    retries); s values absent from the input keep their previously
    measured rows, so a resumed sweep accretes instead of clobbering.
    The existing table is read through the keyed analysis/artifacts.py
    accessor, so regeneration provenance (which sweep files fed which
    table content) lands in the artifact registry and the table's own
    `provenance` block."""
    if isinstance(study_paths, str):
        study_paths = [study_paths]
    out_path = out_path or os.path.join(
        ROOT, "paddle_tpu", "ops", "pallas", "attn_dispatch_table.json")
    by_s: dict = {}
    for study_path in study_paths:
        with open(study_path) as f:
            for line in f:
                line = line.strip()
                if not line.startswith("{"):
                    continue
                row = json.loads(line)
                if "ms_step" not in row:
                    continue
                row["_src"] = os.path.basename(study_path)
                by_s.setdefault(int(row["s"]), {})[row["mode"]] = row

    sources = sorted({os.path.basename(p) for p in study_paths})
    signature = "regen:" + "+".join(sources)
    from paddle_tpu.analysis.artifacts import load_artifact

    table = load_artifact(
        out_path,
        backend=os.environ.get("JAX_PLATFORMS", "").strip() or "tools",
        signature=signature,
        default={"thresholds": {}},
    )

    merged = {int(r["s"]): r for r in table.get("measured", [])}
    new_rows = 0
    for s in sorted(by_s):
        pair = by_s[s]
        if "xla" not in pair or "flash" not in pair:
            continue  # partial sweep: this s waits for its other half
        winner = ("flash" if pair["flash"]["ms_step"] < pair["xla"]["ms_step"]
                  else "xla")
        merged[s] = {
            "s": s,
            "b": pair["xla"].get("b"),
            "xla_ms_step": pair["xla"]["ms_step"],
            "flash_ms_step": pair["flash"]["ms_step"],
            "winner": winner,
            "source": "+".join(sorted({pair["xla"]["_src"],
                                       pair["flash"]["_src"]})),
        }
        new_rows += 1
    measured = [merged[s] for s in sorted(merged)]
    flash_min_seq = next(
        (r["s"] for r in measured if r["winner"] == "flash"), None)
    if measured:
        table["measured"] = measured
    if flash_min_seq is not None:
        table.setdefault("thresholds", {})["flash_min_seq"] = flash_min_seq
    table["tokens_per_batch"] = TOKENS_PER_BATCH
    prov = table.setdefault("provenance", {})
    prov["sources"] = sorted(set(prov.get("sources", [])) | set(sources))
    prov["last_regen"] = signature
    with open(out_path, "w") as f:
        json.dump(table, f, indent=2)
        f.write("\n")
    print(json.dumps({
        "table": out_path,
        "rows": len(measured),
        "new_rows": new_rows,
        "sources": sources,
        "flash_min_seq": table.get("thresholds", {}).get("flash_min_seq"),
    }), flush=True)


def main() -> None:
    cmd = sys.argv[1] if len(sys.argv) > 1 else "chip"
    if cmd == "one":
        run_one(int(sys.argv[2]), sys.argv[3])
    elif cmd == "chip":
        chip_sweep()
    elif cmd == "mesh":
        mesh_memory()
    elif cmd == "mesh_inner":
        mesh_inner()
    elif cmd == "table":
        # table A.jsonl [B.jsonl ...] [OUT.json] — every .jsonl arg is a
        # sweep input (sessions merge), an optional trailing non-.jsonl
        # arg is the output table path
        rest = list(sys.argv[2:])
        if not rest:
            raise SystemExit("table needs at least one sweep JSONL")
        out = None
        if len(rest) > 1 and not rest[-1].endswith(".jsonl"):
            out = rest.pop()
        emit_table(rest, out)
    else:
        raise SystemExit(f"unknown command {cmd!r}")


if __name__ == "__main__":
    main()
