"""Milliseconds of the window the routing path spent compiling a match program
(``matcher.compile`` stage: a jit seam met a never-seen shape key and compiled
where it stood). Reads 0 once never-compiled shapes are answered by the host
mirror and compiled on a thread of their own (``ops/hybrid.py``); a compile
off the path counts under ``matcher.compiles_in_window``, not here. Absent
where the broker has no such stage."""

from _stages import delta

SPEC = {"layer": "device matcher ops/partitioned.py", "unit": "ms",
        "source": "program_span", "moves": "puback_p99_ms"}


def read(run: dict):
    return delta(run, "stage_matcher_compile_busy_ms_total")
