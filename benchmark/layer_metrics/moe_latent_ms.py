"""Kernels: device time a step under the scope `moe/latent` (the latent
expert layers' two projections, model width -> latent -> model width,
shared by a layer's experts), forward, recomputed forward and backward
(harness/scope_time_ssm.py)."""
from benchmark.harness import scope_time_ssm


def read(run):
    return scope_time_ssm.ms(run, "moe/latent")
