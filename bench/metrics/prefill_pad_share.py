"""Share of prefill tokens computed that were padding, in %, over the
window: (padded - true) / padded from the engine's counters."""


def read(rec):
    padded = rec["prefill_padded"]
    return 100.0 * (padded - rec["prefill_true"]) / padded if padded else None
