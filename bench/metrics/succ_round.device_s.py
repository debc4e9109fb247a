"""Device seconds per closure of the fused successor round kernel (kernels
layer), the round that also keeps next hops.

One ``pallas_call`` per pivot round; on a TPU v5e its trace events are the
HLO ops named ``%fw_round_with_successors.<i> = ... custom-call(...)``
(checked by hand in chip traces).  The sum of their durations in the
traced window over the closures traced.
"""
KERNELS = ("fw_round_with_successors",)


def read(r):
    if r.trace is None:
        return None
    closures = len(r.trace.span_list("bench.closure"))
    if not closures or not r.trace.kernel_count(KERNELS):
        return None
    return r.trace.kernel_s(KERNELS) / closures
