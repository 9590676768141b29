"""Host seconds of donor tuning, plan resolution and the drain of the
transfer-tuning jobs (harness span)."""


def read(rec):
    return rec["spans"]["tune_s"]
