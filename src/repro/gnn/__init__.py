from repro.gnn.models import (
    GNNConfig, directed_edges, forward, init_params, loss_fn, predict,
    reference_forward, segment_sum,
)
from repro.gnn.distributed import (
    PlanBSR, PlanCaps, PlanDelta, ShardPlan, build_plan_bsr, compile_plan,
    gather_outputs, make_bsp_forward, patch_plan, plan_caps, plans_equal,
    recompile_like, scatter_features, scatter_ints, scatter_replica_halo,
    set_replication, simulate_bsp_forward,
)
from repro.gnn.serving import (
    EgoBatch, FeatureCache, GNNServeEngine, ServeStats, ego_tables,
    extract_ego, extract_ego_batch, link_traffic, make_ego_forward,
    replicate_for_stream, request_traffic, serving_cost, zipf_requests,
)

__all__ = [
    "GNNConfig", "directed_edges", "forward", "init_params", "loss_fn",
    "predict", "reference_forward", "segment_sum",
    "PlanBSR", "PlanCaps", "PlanDelta", "ShardPlan", "build_plan_bsr",
    "compile_plan", "gather_outputs", "make_bsp_forward", "patch_plan",
    "plan_caps", "plans_equal", "recompile_like", "scatter_features",
    "scatter_ints", "scatter_replica_halo", "set_replication",
    "simulate_bsp_forward",
    "EgoBatch", "FeatureCache", "GNNServeEngine", "ServeStats", "ego_tables",
    "extract_ego", "extract_ego_batch", "link_traffic", "make_ego_forward",
    "replicate_for_stream", "request_traffic", "serving_cost",
    "zipf_requests",
]
