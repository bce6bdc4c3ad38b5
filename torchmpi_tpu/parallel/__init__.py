from .deltanet import gated_delta_rule
from .ep import (
    biased_sigmoid_route_weights,
    moe_dispatch_combine,
    moe_load_stats,
    moe_local_experts,
    sigmoid_route_weights,
    softmax_route_weights,
)
from .mesh import make_parallel_mesh
from .pp import (
    pipeline_1f1b_value_and_grad,
    pipeline_forward,
    pipeline_loss_fn,
)
from .retention import power_retention, symmetric_square
from .ring_attention import (
    blocked_self_attention,
    full_self_attention,
    ring_self_attention,
)
from .selected_attention import selected_self_attention
from .ssm import (
    causal_conv1d,
    causal_conv1d_silu,
    gated_group_norm,
    gated_short_conv,
    ssd_chunked_scan,
)
from .tp import MPLinear, MPLinearOutputSplit, shard_input_features

__all__ = [
    "make_parallel_mesh",
    "moe_dispatch_combine",
    "moe_load_stats",
    "moe_local_experts",
    "sigmoid_route_weights",
    "biased_sigmoid_route_weights",
    "softmax_route_weights",
    "pipeline_1f1b_value_and_grad",
    "pipeline_forward",
    "pipeline_loss_fn",
    "ring_self_attention",
    "full_self_attention",
    "blocked_self_attention",
    "selected_self_attention",
    "causal_conv1d",
    "causal_conv1d_silu",
    "gated_short_conv",
    "ssd_chunked_scan",
    "gated_group_norm",
    "power_retention",
    "gated_delta_rule",
    "symmetric_square",
    "MPLinear",
    "MPLinearOutputSplit",
    "shard_input_features",
]
