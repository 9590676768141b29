"""Host time of a decode step, in ms: the mean, over the engine's
``engine.decode_step`` spans in the traced window, of the span less its
``engine.decode_step.read`` child (``engine_trace.py``).  What is left is
the token vector and its upload, the dispatch of the jitted step and the
bookkeeping of its tokens: host time in which the device has no step
queued.  None where the trace holds no such spans."""
import engine_trace


def read(rec):
    r = engine_trace.of(rec)
    return r and r["host_ms"]["decode_step"]
