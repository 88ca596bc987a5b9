"""setup_compile_s (entry): the seconds the program spent compiling its
programs or loading them from the persistent compile cache
(``.compile()``), from its ``compile.backend_s`` counter.  Silent where
the program keeps no such counter."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import steps  # noqa: E402


def compute(records, trace):
    return steps.counter("compile.backend_s")
