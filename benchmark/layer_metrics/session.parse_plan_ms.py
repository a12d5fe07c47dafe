"""Median per request of the planner's and the executor builder's spans
(``session.plan_query`` + ``executor.build``).  Parsing has no span of
its own: it falls under ``wire.outside_worker_ms``."""

from benchmark.harness.observe import total_s


def read(obs):
    def plan_s(root):
        plan = total_s(root, "session.plan_query")
        return None if plan is None else \
            plan + (total_s(root, "executor.build") or 0.0)
    return obs.median_span_ms(plan_s)
