"""Distribution: ONE named device mesh + sharding annotations.

TPU-native replacement for the reference's distribution stacks (SURVEY.md
§2.8): every parallelism flavor is a PartitionSpec assignment over the
unified mesh (axes ('batch', 'model', 'pipe') — parallel/mesh.py), and
the train/eval step compiles with plain `jax.jit(..., in_shardings=...,
out_shardings=..., donate_argnums=...)`. There are no NCCL rings, gRPC
parameter servers, or hand-written per-device programs to manage — XLA
emits and overlaps the collectives (psum/all-gather/reduce-scatter/
collective-permute) from the shardings.
"""

from .mesh import (  # noqa: F401
    AXES,
    build_mesh,
    canonical_axis,
    canonicalize_spec,
    current_mesh,
    mesh_signature,
)
from .api import (  # noqa: F401
    DistributedStrategy,
    compile_distributed,
    get_mesh,
    make_mesh,
    shard_parameter,
    sharding_specs,
)
from .pipeline import (  # noqa: F401
    PipelineOptimizer,
    gpipe,
    stack_stage_params,
)
from .moe import (  # noqa: F401
    init_moe_params,
    moe_ffn,
    moe_shardings,
)
