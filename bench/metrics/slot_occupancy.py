"""Mean share of decode slots active at each decode step of the window,
in %."""


def read(rec):
    steps = rec["steps"]
    return 100.0 * rec["active_sum"] / (steps * rec["slots"]) if steps else None
