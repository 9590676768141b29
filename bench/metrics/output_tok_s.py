"""Output tokens read in the window over the window's seconds."""


def read(rec):
    return rec["tokens"] / rec["window_s"]
