"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read:
device busy time, per-kernel device time, the host spans the harness
annotated, the top device ops and the longest idle gaps.

The layout, as a TPU v5e writes it with jax 0.9:

    plane "/device:TPU:<i>"   line "XLA Ops": one event per HLO op run, named
                              by its HLO text ("%fw_round.7 = f32[...]
                              custom-call(...)"); a ``while`` op spans the
                              ops of its body
    plane "/host:CPU"         the ``TraceAnnotation`` spans, on the thread
                              that opened them

Device and host events share one clock (nanoseconds from the session's
start).  Busy time is the union of the "XLA Ops" intervals inside the
traced window, averaged over the chips traced.
"""
from __future__ import annotations

import dataclasses
import glob
import shutil
from pathlib import Path

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
TRACED_SPAN = "bench.traced"  # the traced part of the window
# HLO ops that only hold other ops; they count for busy time (their body
# runs inside them) but not as ops of their own.
CONTAINERS = ("while", "conditional", "call")


def op_family(event_name: str) -> str:
    """"%fw_round.7 = f32[...] custom-call(...)" -> "fw_round"."""
    name = event_name.split(" = ", 1)[0].lstrip("%").strip()
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def merge(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def overlap(merged, lo: float, hi: float) -> float:
    """Length of the merged intervals inside [lo, hi]."""
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in merged)


@dataclasses.dataclass
class Summary:
    """One traced window, reduced.  Times are nanoseconds unless named _s."""

    window: tuple[float, float]
    ops: list          # per chip: [(family, start, end)] inside the window
    spans: list        # [(name, start, end)] host annotations, bench.*

    @property
    def chips(self) -> int:
        return max(1, len(self.ops))

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _merged(self, chip_ops):
        return merge((a, b) for _, a, b in chip_ops)

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips."""
        lo, hi = self.window
        return sum(overlap(self._merged(c), lo, hi)
                   for c in self.ops) / self.chips * 1e-9

    def kernel_s(self, families) -> float:
        """Device seconds of the ops of these families, per chip."""
        fam = set(families)
        return sum(b - a for c in self.ops for f, a, b in c
                   if f in fam) / self.chips * 1e-9

    def kernel_count(self, families) -> int:
        fam = set(families)
        return sum(1 for c in self.ops for f, _, _ in c if f in fam)

    def span_list(self, name: str):
        return [(a, b) for n, a, b in self.spans if n == name]

    def top_ops(self, k: int = 10):
        tot: dict[str, float] = {}
        for c in self.ops:
            for f, a, b in c:
                if f not in CONTAINERS:
                    tot[f] = tot.get(f, 0.0) + (b - a)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[f, t / self.chips * 1e-9] for f, t in top]

    def idle_gaps(self, k: int = 10):
        """The k longest idle gaps of chip 0, each named by the innermost
        harness span that covers most of it ("none" if no span does)."""
        lo, hi = self.window
        busy = self._merged(self.ops[0]) if self.ops else []
        gaps, t = [], lo
        for a, b in busy:
            if a > t:
                gaps.append((t, min(a, hi)))
            t = max(t, b)
        if t < hi:
            gaps.append((t, hi))
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
        spans = [(n, a, b) for n, a, b in self.spans if n != TRACED_SPAN]
        out = []
        for a, b in gaps:
            best, best_key = "none", (0.0, 0.0)
            for n, sa, sb in spans:
                cover = min(b, sb) - max(a, sa)
                key = (cover, -(sb - sa))
                if cover > 0 and key > best_key:
                    best, best_key = n, key
            out.append([best, (b - a) * 1e-9])
        return out

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def load(path) -> Summary:
    """Read one ``.xplane.pb`` into a Summary of its ``bench.traced`` span
    (the whole trace when the span is missing)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((op_family(e.name), e.start_ns,
                                e.start_ns + e.duration_ns)
                               for e in line.events)
            devices.append((plane.name, ops))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    devices.sort()
    traced = [(a, b) for n, a, b in spans if n == TRACED_SPAN]
    if traced:
        window = (min(a for a, _ in traced), max(b for _, b in traced))
    else:
        every = [x for _, ops in devices for _, a, b in ops for x in (a, b)]
        window = (min(every), max(every)) if every else (0.0, 0.0)
    lo, hi = window
    ops = [[(f, max(a, lo), min(b, hi)) for f, a, b in chip if b > lo and a < hi]
           for _, chip in devices]
    return Summary(window=window, ops=ops,
                   spans=[s for s in spans if s[2] > lo and s[1] < hi])


class Tracer:
    """Profiles a driver's whole window into ``directory``.

    The driver calls ``start()`` as its window opens and ``stop()`` once it
    has closed; the traced part is annotated as ``bench.traced``.  Only one
    trace is kept on disk per cell.
    """

    def __init__(self, directory: Path, spans):
        self.directory = Path(directory)
        self.spans = spans
        self._ann = None
        self._path = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.directory), profiler_options=opts)
        self.spans.annotate = jax.profiler.TraceAnnotation
        self._ann = jax.profiler.TraceAnnotation(TRACED_SPAN)
        self._ann.__enter__()

    def stop(self) -> None:
        if self._ann is None:
            return
        import jax

        self._ann.__exit__(None, None, None)
        self._ann = None
        self.spans.annotate = None
        jax.profiler.stop_trace()
        found = glob.glob(str(self.directory / "**" / "*.xplane.pb"),
                          recursive=True)
        self._path = found[0] if found else None

    def summary(self) -> Summary | None:
        return load(self._path) if self._path else None
