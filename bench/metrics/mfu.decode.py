"""Decode share of the chip's bf16 peak, in %: operations of the active
slots in the decode steps that ran while the trace ran (the module's
``dims``) over the device time of the ``jit_decode_fn`` program in the
trace."""


def read(rec):
    tr = rec["trace"]
    t = tr and tr["module_s"].get("jit_decode_fn", 0.0)
    flops = rec["traced"]["decode_flops"]
    if not t or not flops:
        return None
    return 100.0 * flops / (t * rec["peaks"]["bf16_flops_per_s"])
