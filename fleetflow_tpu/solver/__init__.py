"""JAX placement solver: the device-resident heart of the framework.

Replaces the reference's sequential placement path (engine.rs
order_by_dependencies + per-service Docker loop) with greedy seeding +
mesh-sharded simulated annealing over dense constraint tensors.
"""

from .anneal import chain_states_from_assignment, prerepair_state
from .buckets import (BucketConfig, BucketInfo, bucket_config, bucket_size,
                      pad_problem_tiers, soft_score_host,
                      stage_problem_tiers, staging_arena_stats,
                      subsolve_tier)
from .resident import ProblemDelta, ResidentProblem, transfer_guard_ctx
from .subsolve import (ActiveIndex, ActivePlan, SubsolveConfig, plan_active,
                       subsolve_config)
from .sharded import SVC_AXIS, anneal_sharded, pad_problem, shard_problem
from .api import CHAIN_AXIS, SolveResult, make_chain_inits, solve
from .greedy import greedy_place, greedy_place_batched, placement_order
from .kernels import (node_loads, soft_score, total_cost, total_violations,
                      violation_stats)
from .problem import DeviceProblem, prepare_problem
from .repair import RepairResult, repair, verify
