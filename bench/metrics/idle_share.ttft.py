"""Share of the traced window in which no operation ran on the device, in
%, in the cells whose end-to-end tail is the TTFT: there an idle device is
a prefill that could have started and did not.  The same reading as
``idle_share``, which moves the inter-token tail."""


def read(rec):
    tr = rec["trace"]
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
