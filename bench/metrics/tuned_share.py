"""Share of the served plan's entries resolved to a tuned schedule
(exact or transfer tier), in %, from ``ExecutionPlan.tier_counts()``."""


def read(rec):
    t = rec["plan_tiers"]
    n = sum(t.values())
    return 100.0 * (t["exact"] + t["transfer"]) / n if n else None
