"""The fused successor round's share of its roofline, in percent: the least
time one closure with next hops can take (``bench/roofline.py``: the
2 n**3 min-plus operations at the VPU's peak; the selects that keep next
hops are not counted) over the kernel's device time per closure
(``succ_round.device_s``)."""
from bench.harness import load_metric
from bench.roofline import closure_roofline_s


def read(r):
    t = load_metric("succ_round.device_s").read(r)
    if not t:
        return None
    n = int(r.cell.config["n"])
    return 100.0 * closure_roofline_s(n, r.peaks, word=8) / t
