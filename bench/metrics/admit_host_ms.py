"""Host time of an admission, in ms: the mean, over the engine's
``engine.prefill`` spans in the traced window, of the span less its
``engine.prefill.read`` child (``engine_trace.py``).  What is left is the
padded prompt and its upload, the dispatch of the jitted prefill and of
the cache splice; the read is the wait for the device and the transfer of
the first token.  None where the trace holds no such spans."""
import engine_trace


def read(rec):
    r = engine_trace.of(rec)
    return r and r["host_ms"]["prefill"]
