"""Transformer-base WMT16 train throughput on the chip. Thin delegate:
the canonical workload body lives in bench.py (bench_transformer); the
FLOPs accounting lives in
paddle_tpu.models.transformer.transformer_flops_per_trg_token.

Prints the transformer metric as ONE stdout JSON line (this tool's own
contract — bench.py's stdout headline stays BERT).

Env knobs: TF_BATCH, TF_SEQ, TF_STEPS, TF_AMP, TF_NO_FLASH.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu.models.transformer import (  # noqa: F401,E402 (back-compat)
    transformer_flops_per_trg_token as flops_per_trg_token,
)


def main():
    import bench

    bench.require_tpu()
    bench.bench_transformer()
    payload = bench._EXTRA["transformer_base_wmt16_tokens_per_sec_per_chip"]
    print(json.dumps({
        "metric": "transformer_base_wmt16_tokens_per_sec_per_chip",
        **payload,
    }))


if __name__ == "__main__":
    main()
