"""Device seconds per closure of the fused round kernel (kernels layer).

The round is one ``pallas_call`` per pivot round; on a TPU v5e its trace
events are the HLO ops named ``%fw_round.<i> = ... custom-call(...)``
(checked by hand in a chip trace).  The sum of their durations in the
traced window over the closures traced.
"""
KERNELS = ("fw_round",)


def read(r):
    if r.trace is None:
        return None
    closures = len(r.trace.span_list("bench.closure"))
    if not closures or not r.trace.kernel_count(KERNELS):
        return None
    return r.trace.kernel_s(KERNELS) / closures
