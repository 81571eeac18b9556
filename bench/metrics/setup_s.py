"""Set-up: process start to the first instant of the measured window
(loading, weights, compiles or cache reads, warm-up, set-up traffic)."""


def read(rec):
    return rec["setup_s"]
