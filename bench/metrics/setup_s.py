"""Seconds from process start to the window's start: weights, tuning,
plan resolution and warm-up (compiles or cache loads)."""


def read(rec):
    return rec["setup_s"]
