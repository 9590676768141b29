"""Decode step's share of its roofline, in %: the least time the decode
steps that ran while the trace ran could take on the chip -- the larger of
their operations over the bf16 peak and their least bytes (weights once,
each active slot's live cache rows and new rows; the architecture module's
``dims``) over the HBM bandwidth -- over the device time of the
``jit_decode_fn`` program in the trace.  At these batch sizes the bytes
bound the step."""


def read(rec):
    tr = rec["trace"]
    t = tr and tr["module_s"].get("jit_decode_fn", 0.0)
    traced, peaks = rec["traced"], rec["peaks"]
    if not t or not traced["decode_bytes"]:
        return None
    least = max(traced["decode_flops"] / peaks["bf16_flops_per_s"],
                traced["decode_bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
