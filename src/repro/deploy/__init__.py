"""Deployment-plan subsystem: persistent schedule cache + shape bucketing +
batch planner, turning one-shot autotuning into a reusable serving pipeline.

    from repro.deploy import Planner, PlanCache
    from repro.models.workload import gemm_workload

    planner = Planner(hw, cache=PlanCache("results/plan_cache"))
    planner.batch_tune(gemm_workload(cfg, batch=8, seq=4096))    # cold, once
    plan = planner.plan(shape)                                   # warm: O(1)
"""
from repro.deploy.batcher import (BATCH_MODES, Batch, BatchPolicy,
                                  ContinuousBatcher, Request, bucket_pool,
                                  decode_m)
from repro.deploy.bucketing import (BucketingPolicy, adapt, bucket_of,
                                    distance, nearest_tuned, next_pow2,
                                    transfer_candidates)
from repro.deploy.cache import CacheStats, PlanCache, plan_key
from repro.deploy.plan import (DeploymentPlan, PLAN_SCHEMA_VERSION,
                               SOURCE_BUCKETED, SOURCE_TUNED, hw_fingerprint,
                               plan_from_tuning, schedule_from_dict,
                               schedule_to_dict, search_variant)
from repro.deploy.planner import Planner

__all__ = [
    "BATCH_MODES", "Batch", "BatchPolicy", "BucketingPolicy", "CacheStats",
    "ContinuousBatcher", "DeploymentPlan", "PLAN_SCHEMA_VERSION",
    "PlanCache", "Planner", "Request", "SOURCE_BUCKETED", "SOURCE_TUNED",
    "adapt", "bucket_of", "bucket_pool", "decode_m", "distance",
    "hw_fingerprint", "nearest_tuned", "next_pow2", "plan_from_tuning",
    "plan_key", "schedule_from_dict", "schedule_to_dict", "search_variant",
    "transfer_candidates",
]
