"""Device seconds per closure of the engine's solve program (staged solve
layer): the runs of the module ``jit_apsp_solve`` in the traced window
(``bench/program_trace.py``), over the closures traced.  None where the
program names no such module."""
from bench import program_trace


def read(r):
    p = program_trace.read(r)
    return None if p is None else p.solve_device_s()
