"""The expert GEMMs' share of their roofline in prefill, in %: the least
time the expert GEMMs of the prompts prefilled while the trace ran need
(``expert_least_s`` of the architecture module's ``dims`` for each true
prompt length: the larger of the routed operations over the bf16 peak and
the least bytes, each expert weight touched once plus the routed rows in
and out, over the HBM bandwidth), over the self time, inside
``jit_prefill_fn``, of the ops the trace names as an expert GEMM class
(``moe_gemm``, ``moe_gemm_silu_glu``; ``op_module_s`` of
``engine_trace.py``).  None where the trace names no such class or the
module's counts have no expert GEMMs."""
import engine_trace

EXPERT = "moe_gemm"


def read(rec):
    r = engine_trace.of(rec)
    ops = r and r["op_module_s"].get("jit_prefill_fn")
    n = rec["traced"]["prefills"]
    d = rec["dims"]
    if not ops or not n or not hasattr(d, "expert_least_s"):
        return None
    t = sum(v for k, v in ops.items() if k.startswith(EXPERT))
    if not t:
        return None
    least = sum(d.expert_least_s(len(s.prompt), rec["peaks"])
                for s in rec["admitted"][-n:])
    return 100.0 * least / t
