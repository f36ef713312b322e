"""Static analysis over the Program IR: verifier, shape/dtype
inference, sharding consistency.

The substrate for cost-model-driven placement (ROADMAP
shard_propagation): per-op output shapes/dtypes over the IR without
tracing, plus the correctness tooling (IR verifier between passes,
sharding checker, repo lints in tools/provlint.py) that keeps the six
rewrite passes honest. Analysis never mutates programs — compile-cache
fingerprints are unaffected.

Entry points:
  verify_program / check_program  — structural IR invariants
                                    (analysis/verifier.py)
  infer_program / infer_block     — static VarMeta environment
                                    (analysis/shape_infer.py)
  check_sharding                  — PartitionSpec consistency
                                    (analysis/sharding_check.py)
"""

from .meta import InferError, Unknown, VarMeta, lowered_dtype  # noqa: F401
from .shape_infer import (  # noqa: F401
    InferContext,
    InferResult,
    infer_block,
    infer_program,
)
from .sharding_check import check_sharding, check_spec_axes  # noqa: F401
from .verifier import (  # noqa: F401
    Finding,
    VerifierError,
    check_program,
    verify_program,
)

__all__ = [
    "VarMeta",
    "InferError",
    "Unknown",
    "lowered_dtype",
    "InferContext",
    "InferResult",
    "infer_block",
    "infer_program",
    "check_sharding",
    "check_spec_axes",
    "Finding",
    "VerifierError",
    "check_program",
    "verify_program",
]
