"""Parallelism over TPU meshes.

Reference role: the kvstore/comm layer (src/kvstore/, SURVEY.md §2.3/§5.8)
plus the parallelism strategies the reference lacks (TP/SP design slots).
TPU-native: `jax.sharding.Mesh` + NamedSharding + jit — XLA inserts the
collectives (psum/all-gather/reduce-scatter) and rides ICI within a slice,
DCN across slices.

Two mixture-of-experts routers live in ``moe.py``: ``token_choice_moe``
(with ``topk_choice``, ``topk_route`` and ``held_expert_ffn``: top-k over all
experts, no dropped token, computed for the experts one chip HOLDS - one
grouped product a projection, ``ops/grouped.py``'s Pallas kernels on a TPU
and ``lax.ragged_dot`` elsewhere) is the
one a Gluon model reaches, through ``gluon.nn.TokenChoiceMoE``;
``moe_parallel`` / ``moe_apply`` / ``top1_dispatch`` (top-1 with capacity
and two ``all_to_all``s over an ``ep`` axis) is called directly and by no
block.
"""
from .mesh import (make_mesh, replicated, batch_sharded, shard_params_tp,
                   TrainStep, init_process_group)
from .speclayout import (SpecLayout, shard_params, tp_alternation_specs,
                         layout_from_env, mesh_from_env, mesh_for_world)
from .ring import (ring_attention, ulysses_attention,
                   context_parallel_attention)
from .pipeline import pipeline_apply, pipeline_parallel
from .moe import (moe_apply, moe_parallel, top1_dispatch, topk_choice,
                  topk_route, held_expert_ffn, token_choice_moe)

__all__ = ["make_mesh", "replicated", "batch_sharded", "shard_params_tp",
           "SpecLayout", "shard_params", "tp_alternation_specs",
           "layout_from_env", "mesh_from_env", "mesh_for_world",
           "TrainStep", "init_process_group", "ring_attention",
           "ulysses_attention", "context_parallel_attention",
           "pipeline_apply", "pipeline_parallel", "moe_apply",
           "moe_parallel", "top1_dispatch", "topk_choice", "topk_route",
           "held_expert_ffn", "token_choice_moe"]
