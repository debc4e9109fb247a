"""Device seconds per closure of the edge-list packer (pack layer): the
runs of the module ``jit_apsp_pack`` that ``ApspEngine.pack_edges`` runs,
in the traced window (``bench/module_trace.py``), over the closures
traced.  None where the program runs no such module."""
from bench import module_trace

MODULE = "jit_apsp_pack"


def read(r):
    return module_trace.device_s(r, MODULE)
