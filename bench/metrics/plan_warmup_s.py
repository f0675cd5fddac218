"""Host seconds of the planner's warm-up (`launch.serve.warm_plan_cache` on
a fresh plan cache, and installing the routing context) in set-up."""


def read(run):
    return run["setup"]["plan_warmup_s"]
