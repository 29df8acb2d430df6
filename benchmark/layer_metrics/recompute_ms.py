"""Step compiler: device time a step of the RECOMPUTED forward - the ops
`jax.checkpoint` runs again inside the backward pass for the blocks that
carry `Block.recompute`'s mark (their `op_name` path holds
`rematted_computation`; `backward_ms` counts them, as everything under
`transpose(`).  What recomputation costs in time for the memory it frees
(harness/scope_time.py)."""
from benchmark.harness import scope_time


def read(run):
    return scope_time.ms(run, "recompute")
