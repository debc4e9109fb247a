"""The whole closure's share of the chip's min-plus peak, in percent
(device layer): the least time one closure can take
(``bench/roofline.py``) over the traced window per closure traced.  It
counts every op and every idle gap, so it bounds the kernels' roofline
shares whichever kernels the closure runs."""
from bench.roofline import closure_roofline_s


def read(r):
    if r.trace is None or not r.trace.ops or r.trace.window_s() <= 0:
        return None
    closures = len(r.trace.span_list("bench.closure"))
    if not closures:
        return None
    word = 8 if r.cell.traffic.get("successors", False) else 4
    best = closure_roofline_s(int(r.cell.config["n"]), r.peaks, word)
    return 100.0 * best * closures / r.trace.window_s()
