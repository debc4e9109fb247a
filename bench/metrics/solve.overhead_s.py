"""Seconds per closure that the engine adds around its solve program
(engine layer): the time of the host spans ``apsp.solve`` less the device
time of ``jit_apsp_solve`` inside them (``bench/program_trace.py``), over
the closures traced: the eager batch axis, the unpadding, dispatch and the
negative-cycle check's wait on the host.  None where the program opens no
such span or names no such module."""
from bench import program_trace


def read(r):
    p = program_trace.read(r)
    return None if p is None else p.solve_overhead_s()
