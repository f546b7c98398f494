"""Seconds JAX spent lowering and in the backend compile, cache retrieval included (compile log), before the window's start."""
from benchmarks.lib import stage_gaps

NAME, UNIT = "setup.jit_compile_s", "s"
LAYER, MOVES = "serve programs", "setup_s"


def read(record, trace):
    return stage_gaps.setup_seconds(record, ("lower", "compile"))
