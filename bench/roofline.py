"""Operations and bytes of a min-plus closure, counted from the problem and
not from tiles or block size, so they read the same work whatever kernel
implements it.

A closure of n vertices relaxes every entry once per pivot: at least an
add and a min (or a compare) each, 2 n**3 operations.  Next-hop tracking
adds selects that a kernel may fuse away, so they are not counted.  The
operations cannot use the MXU: their peak is the VPU's min-plus rate.  The
bytes are one read and one write of the tables (``word`` bytes an entry:
4 for distances, 8 with next hops).
"""


def closure_ops(n: int) -> float:
    return 2.0 * n ** 3


def closure_bytes(n: int, word: int = 4) -> float:
    return 2.0 * word * n * n


def closure_roofline_s(n: int, peaks: dict, word: int = 4) -> float:
    """The least time one closure can take on a chip with these peaks."""
    return max(closure_ops(n) / float(peaks["vpu_minplus_ops_per_s"]),
               closure_bytes(n, word) / float(peaks["hbm_bytes_per_s"]))
