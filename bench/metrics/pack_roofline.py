"""The edge-list packer's share of its roofline, in percent (pack layer):
the least time one pack can take (``bench/reach_roofline.py``: one write
of the word table and one read of the edges, at HBM's peak) over the
packer's device time per closure (``pack.device_s``)."""
from bench.harness import load_metric
from bench.reach_roofline import pack_roofline_s


def read(r):
    t = load_metric("pack.device_s").read(r)
    if not t:
        return None
    cfg = r.cell.config
    n = int(cfg["n"])
    best = pack_roofline_s(n, int(cfg["graphs"]),
                           int(cfg["edgefactor"]) * n, r.peaks)
    return 100.0 * best / t
