"""Set-up: process start to the first measured operation (host clock)."""


def read(rec):
    return rec["setup_s"]
