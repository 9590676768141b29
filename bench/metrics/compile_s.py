"""Host seconds of the warm-up that runs every shape the window uses once
(harness span): compiles, or loads from the persistent cache."""


def read(rec):
    return rec["spans"]["compile_s"]
