"""Compiled execution plans: one operator-generic IR for all executors.

`compile_plan` lowers a `NetworkMapping` once — executor choice per
layer (conv executors plus the ``"matmul"`` MXU path for op="matmul"
layers), super-step schedule (steps==cycles checked at compile time),
inter-layer glue (inferred chain/concat for CNNs, or the mapping's
explicit `GlueSpec` tuple for transformer lowerings), sharding decisions
— and `execute_plan` runs the whole forward as a single jitted program
with cross-layer overlap.  See DESIGN.md §8/§11 and the module
docstrings of exec/plan.py / exec/run.py.

    from repro.exec import compile_plan, execute_plan
    plan = compile_plan(net_mapping, executor_policy="auto",
                        mesh=mesh, batch=8)
    y = execute_plan(plan, kernels, x, mesh=mesh)
"""
from .constants import (PlanConstants, constant_counts, fixed_constants,
                        prepared_stats)
from .glue import (ACTIVATIONS, GLUE_KINDS, GlueSpec, attention_stage,
                   center_crop, fit_spatial, layernorm, resolve_chain)
from .memory import LayerMemory, network_memory, peak_bytes, total_bytes
from .plan import (EXECUTORS, PASSES, LayerPlan, NetworkPlan, PlanDraft,
                   PolicyLike, compile_counts, compile_plan)
from .remat import allowed_cuts, canonical_remat, plan_segments
from .run import (REASSOC_RTOL, apply_layer, donation_supported,
                  execute_layerwise, execute_looped, execute_oracle,
                  execute_plan, reassoc_error)

__all__ = [
    "ACTIVATIONS", "GLUE_KINDS", "GlueSpec", "EXECUTORS", "LayerMemory",
    "LayerPlan", "NetworkPlan", "PASSES", "PlanConstants", "PlanDraft",
    "PolicyLike", "allowed_cuts", "apply_layer", "attention_stage",
    "canonical_remat", "center_crop", "compile_counts", "compile_plan",
    "constant_counts", "donation_supported", "execute_layerwise",
    "execute_looped", "execute_oracle", "execute_plan", "fit_spatial",
    "fixed_constants", "layernorm", "network_memory", "peak_bytes", "plan_segments",
    "prepared_stats", "REASSOC_RTOL", "reassoc_error",
    "resolve_chain", "total_bytes",
]
