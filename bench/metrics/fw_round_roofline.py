"""The fused round kernel's share of its roofline, in percent: the least
time one closure can take (``bench/roofline.py``: 2 n**3 min-plus
operations at the VPU's peak, far above the HBM bound at n=16384) over
the kernel's device time per closure (``round.device_s``)."""
from bench.harness import load_metric
from bench.roofline import closure_roofline_s


def read(r):
    t = load_metric("round.device_s").read(r)
    if not t:
        return None
    return 100.0 * closure_roofline_s(int(r.cell.config["n"]), r.peaks) / t
