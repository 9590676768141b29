"""99th percentile of the gaps between consecutive output tokens, in ms,
over every request in the window, each gap read on the host clock when the
token is read."""
import harness


def read(rec):
    v = harness.percentile(rec["gaps"], 99)
    return None if v is None else v * 1e3
