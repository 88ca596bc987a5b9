"""setup_s: process start to the first timed dispatch (host clock):
imports, data, compiling or loading every program the cell uses, and
the warm-up dispatches."""


def compute(records, trace):
    return records["setup_s"]
