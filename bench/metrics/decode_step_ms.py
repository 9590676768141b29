"""Mean host time of ``engine.step()`` in the window, in ms: the span
ends with the host read of the argmax, so it covers the device step."""


def read(rec):
    return 1e3 * rec["step_s"] / rec["steps"] if rec["steps"] else None
