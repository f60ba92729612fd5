"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel package: <name>.py (pl.pallas_call + BlockSpec VMEM tiling),
ops.py (jit'd public wrapper, CPU auto-interpret), ref.py (pure-jnp oracle).

  flash_attention — blockwise fused attention (causal/window/softcap/GQA);
                    kills the O(S²) HBM scores traffic the §Roofline table
                    shows dominating the jnp baseline
  ssd_scan        — Mamba-2 SSD chunk scan (intra-chunk attention-like +
                    carried inter-chunk state)
  segment_reduce  — sorted segmented reduction (reduceByKey hot path
                    of the dataflow layer — the paper's TeraSort/K-Means side)
                    + segment_totals, the shuffle-stage ABI entry; the one
                    kernel the shuffle engine runs (lanes.py: its lane-dense
                    layout and in-tile scan)
  moe_route       — fused softmax + top-k + capacity positions for MoE
                    dispatch (phi3.5 / mixtral / jamba)

registry.py is the capability/selection/autotune layer the shuffle engine
(core/shuffle_plan.py) consults per reduceByKey node — docs/kernels.md.
"""
