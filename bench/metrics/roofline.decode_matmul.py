"""The decode step's Pallas matmuls' share of their byte roofline, in %:
the least bytes those matmuls read -- each kernel class's weights once per
decode step traced, from the configuration's shapes -- over the HBM
bandwidth, over the self time of those classes' ops inside
``jit_decode_fn`` in the trace (``op_module_s`` of ``engine_trace.py``).
A class counts only where the trace names it and its weights are known,
so the bytes and the time always come from the same ops; a class this
file cannot map to a weight is left out of both, never guessed.  At decode batch sizes (16 rows
or fewer, against the chip's 240 operations a byte) the bytes, not the
operations, bound these matmuls.  None where the trace names no such
class."""
import engine_trace


def class_weights(d) -> dict[str, int]:
    """Weight elements each matmul class reads in one decode step of a dense
    GQA decoder with a GELU MLP (``Dims`` of ``bench/models/dense_gqa.py``),
    as the program assigns classes: the q, k, v and output projections run
    as ``matmul``, the MLP's up projection as ``matmul_bias_gelu``, its down
    projection as ``matmul_bias`` with a bias and as ``matmul`` without, the
    head as ``matmul_lmhead``."""
    proj = (d.d_model * (d.heads + 2 * d.kv_heads) * d.head_dim
            + d.heads * d.head_dim * d.d_model)
    up = d.d_model * d.d_ff + (d.d_ff if d.mlp_bias else 0)
    down = d.d_ff * d.d_model + (d.d_model if d.mlp_bias else 0)
    out = {"matmul": d.layers * proj, "matmul_bias_gelu": d.layers * up,
           "matmul_lmhead": d.d_model * d.vocab}
    if d.mlp_bias:
        out["matmul_bias"] = d.layers * down
    else:
        out["matmul"] += d.layers * down
    return out


def read(rec):
    r = engine_trace.of(rec)
    ops = r and r["op_module_s"].get("jit_decode_fn")
    steps = rec["traced"]["steps"]
    if not ops or not steps:
        return None
    d = rec["dims"]
    weights = {c: n for c, n in class_weights(d).items() if ops.get(c)}
    if not weights:
        return None
    least = steps * sum(weights.values()) * d.dtype_bytes
    t = sum(ops[c] for c in weights)
    return 100.0 * least / (t * rec["peaks"]["hbm_bytes_per_s"])
