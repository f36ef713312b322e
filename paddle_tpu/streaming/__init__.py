"""Streaming CTR subsystem (round 17): online train-while-serve over
the sharded sparse table, a hot-row cache with async write-behind, and
int8 quantize-on-export serving.

The last scenario class the ROADMAP names: one process streams clicks
through the executor into the sharded embedding table (write-behind
cache bounds and measures staleness) while serving replicas answer
lookups against the same shards, and the dense tower deploys as an int8
predictor bundle.

  WriteBehindRowCache  — LRU/LFU hot-row cache + async write-behind
                         (streaming/row_cache.py)
  OnlineTrainer        — the click-stream device-worker loop with the
                         stream.click chaos site (online_trainer.py)
  zipf_ids/click_stream— THE seeded Zipf id/click generators every
                         streaming drill shares
  export_int8_model    — QAT/PTQ/plain program -> int8 predictor
                         bundle, self-verifying (export_int8.py)
"""

from .export_int8 import (  # noqa: F401
    ExportToleranceError,
    export_int8_model,
    quantize_weight,
)
from .online_trainer import OnlineTrainer, click_stream, zipf_ids  # noqa: F401
from .row_cache import WriteBehindRowCache  # noqa: F401

__all__ = [
    "WriteBehindRowCache",
    "OnlineTrainer",
    "click_stream",
    "zipf_ids",
    "ExportToleranceError",
    "export_int8_model",
    "quantize_weight",
]
