"""Set-up: process start to the window's start (host clock): weights,
compile-cache loads, warm-up and pre-roll."""


def read(rec):
    return rec["setup_s"]
