"""Share of the served plan's kernel instances that run a tuned schedule
(exact or transfer tier), in %, from ``ExecutionPlan.tier_counts()``, in
the cells whose end-to-end tail is the TTFT: there the prefill kernels do
most of the work.  The same reading as ``tuned_share``, which moves the
inter-token tail."""


def read(rec):
    t = rec["plan_tiers"]
    n = sum(t.values())
    return 100.0 * (t["exact"] + t["transfer"]) / n if n else None
