"""Seconds from the process's start to the window's: loading, data,
warm-up and, in a checkout's first run, compiling."""


def read(rec):
    return rec.get("setup_s")
