"""kernels.pallas_fallbacks summed over reasons. The counter moves when a
program is TRACED, not when it runs: this is a flag of the compiled
programs (how many gate decisions fell back since process start), not a
rate, and it cannot move with the window. It reads 0 once a kernel takes
the shape."""
NAME, UNIT = "kernel.paged_fallbacks.closed", "count"
LAYER, MOVES = "paged kernels", "serve_tokens_per_s"


def read(record, trace):
    fb = record.get("fallbacks")
    return None if fb is None else float(sum(fb.values()))
