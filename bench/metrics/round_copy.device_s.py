"""Device seconds per closure of the per-round table copies (staged solve
layer): the ``copy`` ops that run inside a ``while`` op inside the module
``jit_apsp_solve`` (``bench/program_trace.py``), over the closures traced.
Copies made once per solve, inside the program or by the engine's eager
ops, are not counted.  None where the program names no such module."""
from bench import program_trace


def read(r):
    p = program_trace.read(r)
    return None if p is None else p.round_copy_device_s()
