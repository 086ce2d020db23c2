"""Seconds to fetch the plan for the root from the in-memory ``PlanServer``
(``CompiledModel.plan``), in set-up."""


def read(run):
    return run.setup.get("plan_s")
