"""Kernel-customization autotuner (paper §3.3-3.4 as a subsystem).

Per-layer method/tile selection, measurement-driven with an analytical
roofline fallback, persisted to a JSON plan cache:

  space    -- candidate enumeration (method x (tm, te) x pad_to x fuse
              x pipeline x permute x BCSR (block_m, block_n)) from
              geometry; spatial tiles come from the kernels' halo'd-block
              VMEM feasibility models (pipelined tilings reserve the
              second halo buffer), the fuse axis from the conv's lowered
              epilogue (bias/ReLU/shortcut in-kernel)
  measure  -- wall-clock timing + roofline scoring of candidates (the
              roofline credits the fused epilogue's saved output passes,
              the pipelined schedule's overlapped staging bytes, and the
              balanced bank's equalised channel tiles, and prices the MXU
              systolic peak against the VPU FMA rate — the crossover that
              sends moderately-sparse layers to the BCSR ``bsr`` method)
  cache    -- versioned JSON plan cache keyed on geometry/epilogue/sparsity/
              dtype/backend
  planner  -- plans the engine's lowered program (one ConvOp at a time)
              into executable {layer: PlanEntry} tables
"""
from repro.tuning.cache import PlanCache, PlanEntry, layer_key, sparsity_bucket
from repro.tuning.measure import (epilogue_bytes, measurable,
                                  measure_candidate, permute_bytes,
                                  roofline_estimate, staged_input_bytes,
                                  staging_stall_s, time_fn)
from repro.tuning.planner import (apply_plan_to_params, format_plan,
                                  geometry_for, geometry_of_op, plan_layer,
                                  plan_network, plan_program)
from repro.tuning.space import (Candidate, ConvGeometry, bsr_feasible,
                                enumerate_candidates, METHODS,
                                PAD_TO_BUCKETS, pallas_feasible)

__all__ = [
    "Candidate", "ConvGeometry", "METHODS", "PAD_TO_BUCKETS", "PlanCache",
    "PlanEntry", "apply_plan_to_params", "bsr_feasible",
    "enumerate_candidates",
    "epilogue_bytes", "format_plan", "geometry_for", "geometry_of_op",
    "layer_key", "measurable", "measure_candidate", "pallas_feasible",
    "permute_bytes", "plan_layer", "plan_network", "plan_program",
    "roofline_estimate", "sparsity_bucket", "staged_input_bytes",
    "staging_stall_s", "time_fn",
]
