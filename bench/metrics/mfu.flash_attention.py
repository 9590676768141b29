"""Causal flash attention's share of the chip's bf16 peak in prefill, in %:
the attention operations (scores and weighted values, ``attention_flops`` of
the architecture module's ``dims`` for each layer over the causal triangle
of the true prompt tokens, as ``prefill_flops`` counts them) of the prefills
that ran while the trace ran, over the self time, inside ``jit_prefill_fn``,
of the ops the trace names as a flash-attention kernel class
(``op_module_s`` of ``engine_trace.py``).  The pad rows the kernel also
computes count in its time, not in its operations.  None where the trace
names no such class."""
import engine_trace

ATTENTION = "flash_attention"


def read(rec):
    r = engine_trace.of(rec)
    ops = r and r["op_module_s"].get("jit_prefill_fn")
    n = rec["traced"]["prefills"]
    if not ops or not n:
        return None
    t = sum(v for k, v in ops.items() if k.startswith(ATTENTION))
    if not t:
        return None
    d = rec["dims"]
    flops = sum(d.layers * d.attention_flops(1) * (m * (m + 1) // 2)
                for m in (len(s.prompt) for s in rec["admitted"][-n:]))
    return 100.0 * flops / (t * rec["peaks"]["bf16_flops_per_s"])
