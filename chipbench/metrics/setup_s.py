"""Process start to window start: weights, warm-up, compiles or cache loads."""


def read(rec):
    return rec["setup_s"]
