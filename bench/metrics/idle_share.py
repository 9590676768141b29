"""Share of the traced window in which no operation ran on the device, in
%: 1 - (union of the device op intervals) / (traced window)."""


def read(rec):
    tr = rec["trace"]
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
