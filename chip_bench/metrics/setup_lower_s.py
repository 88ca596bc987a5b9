"""setup_lower_s (entry): the seconds the program spent tracing and
lowering its programs (``.lower()``, which the persistent compile cache
does not serve), from its ``compile.lower_s`` counter.  Silent where
the program keeps no such counter."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import steps  # noqa: E402


def compute(records, trace):
    return steps.counter("compile.lower_s")
