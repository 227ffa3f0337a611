"""Public distributed API.

Everything ``train/`` and ``launch/`` need from the distributed layer is
re-exported here with types — sharding rules and mesh context, per-parameter
partition specs, the freeze-aware explicit gradient reduce, and the int8
error-feedback compressor.  Deep imports of the submodules keep working but
new call sites should use this surface.
"""
from repro.distributed.compression import (  # noqa: F401
    compress_with_feedback,
    dequantize_int8,
    n_compressible,
    quantize_int8,
)
from repro.distributed.reduce import (  # noqa: F401
    DP_AXES,
    explicit_reduce_axes,
    reduce_gradients,
    reduce_plan_bytes,
)
from repro.distributed.sharding import (  # noqa: F401
    DEFAULT_RULES,
    ShardingRules,
    active_mesh,
    active_rules,
    logical_constraint,
    logical_to_spec,
    make_mesh,
    named_sharding,
    param_partition_specs,
    suspend_mesh,
    use_mesh,
)

__all__ = [
    "DEFAULT_RULES",
    "DP_AXES",
    "ShardingRules",
    "active_mesh",
    "active_rules",
    "compress_with_feedback",
    "dequantize_int8",
    "explicit_reduce_axes",
    "logical_constraint",
    "logical_to_spec",
    "n_compressible",
    "named_sharding",
    "param_partition_specs",
    "quantize_int8",
    "reduce_gradients",
    "reduce_plan_bytes",
    "suspend_mesh",
    "use_mesh",
]
