"""90th percentile of time to first token, in ms, over every request due
in the window: from its due time to the host read of its first token; a
request still waiting when the window closes counts at its wait so far."""
import harness


def read(rec):
    v = harness.percentile(harness.ttfts(rec), 90)
    return None if v is None else v * 1e3
