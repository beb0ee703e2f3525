"""noc_cycle: the fused `pallas` cycle engine (DESIGN.md §13), the TPU path.

* `engine` — `pallas_engine`, built and called like the dense `ref`
  engine (`repro.core.noc.engine`); the one module that knows the lane
  layout's per-epoch rows, pack and unpack as the seam sees them;
* `kernel` — the `pallas_call` launch of `fused_cycle_kernel`, one per
  chunk of `cycles_per_launch` simulated cycles;
* `fused`  — the lane layout, the stage twins of the dense engine, and
  pack/unpack;
* `ops`    — `fused_cycles`, the kernel launch with interpret-mode
  fallback off-TPU.
"""
