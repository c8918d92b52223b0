"""Host seconds the plan cache spent building every bucket's plan
(``PlanCache.stats()["build_s"]``: Alg-2 tables, autotune, pruning)."""


def read(ctx):
    return ctx["plan_build_s"]
