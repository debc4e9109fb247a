"""Bytes of the edge-list packer, counted from the configuration and not
from the implementation, so they read the same work whatever packs.

Whatever it does inside, a packer that turns B edge lists of E edges each
into the packed table must write the table once, ceil(B / 32) int32 words
of n * n, and read each edge once, two int32 endpoints.  It needs no
arithmetic worth counting, so HBM bounds it.
"""
from bench.reach_reference import LANES


def pack_bytes(n: int, graphs: int, edges: int) -> float:
    """The least bytes one pack moves: edges per graph ``edges``."""
    words = -(-graphs // LANES)
    return 4.0 * n * n * words + 8.0 * edges * graphs


def pack_roofline_s(n: int, graphs: int, edges: int, peaks: dict) -> float:
    """The least time one pack can take on a chip with these peaks."""
    return pack_bytes(n, graphs, edges) / float(peaks["hbm_bytes_per_s"])
