"""Seconds from process start to the first timed step or request:
loading, weight making, compiling or loading programs, warm-up, and
the correctness readings of a training cell's first steps."""


def read(rec):
    return rec["setup_s"]
