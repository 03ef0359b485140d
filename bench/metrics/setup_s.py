"""Set-up seconds: process start to the first panel of the window."""


def read(record, trace):
    return record["setup_s"]
