"""Read what the program names itself in a traced run: the solve program's
module, the ``copy`` ops of its round loop and the engine's host span.

``xplane.Summary`` keeps op families and the harness's ``bench.*`` spans.
The ``solve.*`` and ``round_copy.*`` metrics need three more things from
the same ``.xplane.pb``, as a TPU v5e writes it with jax 0.9:

    plane "/device:TPU:<i>"  line "XLA Modules": one event per program run,
                             named "<module>(<fingerprint>)"; the engine's
                             solve runner is the module ``jit_apsp_solve``
                             line "XLA Ops": a ``while`` op spans the ops of
                             its body, so a ``copy`` inside it is a copy
                             made once per loop iteration (per round)
    plane "/host:CPU"        ``apsp.solve``, the span ``ApspEngine.solve``
                             opens around everything it does

The tracer keeps one trace per cell, under ``harness.TRACE_DIR / <cell>``.
It is read only when its ``bench.traced`` window is the one the harness
reduced into ``Readings.trace``, so a trace that another run left there is
never read.  Where the program has no such module or span (a program that
predates them), every reading is None, never 0.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

from bench import harness
from bench.xplane import (
    DEVICE_PLANE,
    HOST_PLANE,
    OPS_LINE,
    TRACED_SPAN,
    merge,
    op_family,
    overlap,
)

MODULES_LINE = "XLA Modules"
SOLVE_MODULE = "jit_apsp_solve"
SOLVE_SPAN = "apsp.solve"
CLOSURE_SPAN = "bench.closure"
LOOP_OP = "while"
COPY_OP = "copy"  # the family of "%copy.<i>"; not copy-start / copy-done


def module_name(event_name: str) -> str:
    """"jit_apsp_solve(10001249987622917784)" -> "jit_apsp_solve"."""
    return event_name.split("(", 1)[0].strip()


def _inside(a: float, b: float, intervals) -> bool:
    return any(lo <= a and b <= hi for lo, hi in intervals)


@dataclasses.dataclass
class Program:
    """The program's own events in one traced window, per chip, clipped to
    the window.  Times are nanoseconds on the trace's clock."""

    window: tuple[float, float]
    solves: list     # per chip: [(start, end)] of jit_apsp_solve runs
    loops: list      # per chip: [(start, end)] of while ops
    copies: list     # per chip: [(start, end)] of copy ops
    spans: list      # [(start, end)] of apsp.solve host spans
    closures: int    # bench.closure spans in the window

    @property
    def chips(self) -> int:
        return max(1, len(self.solves))

    def _ready(self) -> bool:
        return self.closures > 0 and any(self.solves)

    def solve_device_s(self) -> float | None:
        """Device seconds per closure of the solve program."""
        if not self._ready():
            return None
        total = sum(b - a for chip in self.solves for a, b in chip)
        return total / self.chips / self.closures * 1e-9

    def round_copy_device_s(self) -> float | None:
        """Device seconds per closure of the ``copy`` ops that run inside a
        ``while`` op inside the solve program: the per-round table copies,
        not the copies the program or the engine make once per solve."""
        if not self._ready():
            return None
        total = 0.0
        for solves, loops, copies in zip(self.solves, self.loops,
                                         self.copies):
            body = [(a, b) for a, b in loops if _inside(a, b, solves)]
            total += sum(b - a for a, b in copies if _inside(a, b, body))
        return total / self.chips / self.closures * 1e-9

    def solve_overhead_s(self) -> float | None:
        """Seconds per closure that ``apsp.solve`` spans hold beyond the
        solve program's device time inside them: what the engine adds
        around its program (eager ops, dispatch, host waits)."""
        if not self._ready() or not self.spans:
            return None
        span_ns = sum(b - a for a, b in self.spans)
        inside = 0.0
        for solves in self.solves:
            merged = merge(solves)
            inside += sum(overlap(merged, a, b) for a, b in self.spans)
        return (span_ns - inside / self.chips) / self.closures * 1e-9


def _clip(events, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in events if b > lo and a < hi]


def load(path) -> Program | None:
    """Read one ``.xplane.pb``; None without a ``bench.traced`` span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    chips, host = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            solves, loops, copies = [], [], []
            for line in plane.lines:
                for e in line.events:
                    iv = (e.start_ns, e.start_ns + e.duration_ns)
                    if line.name == MODULES_LINE:
                        if module_name(e.name) == SOLVE_MODULE:
                            solves.append(iv)
                    elif line.name == OPS_LINE:
                        fam = op_family(e.name)
                        if fam == LOOP_OP:
                            loops.append(iv)
                        elif fam == COPY_OP:
                            copies.append(iv)
            chips.append((plane.name, solves, loops, copies))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name in (TRACED_SPAN, CLOSURE_SPAN,
                                          SOLVE_SPAN))
    traced = [(a, b) for n, a, b in host if n == TRACED_SPAN]
    if not traced:
        return None
    lo, hi = min(a for a, _ in traced), max(b for _, b in traced)
    chips.sort()
    return Program(
        window=(lo, hi),
        solves=[_clip(c[1], lo, hi) for c in chips],
        loops=[_clip(c[2], lo, hi) for c in chips],
        copies=[_clip(c[3], lo, hi) for c in chips],
        spans=_clip([(a, b) for n, a, b in host if n == SOLVE_SPAN], lo, hi),
        closures=len(_clip([(a, b) for n, a, b in host
                            if n == CLOSURE_SPAN], lo, hi)),
    )


def find(cell_name: str) -> Path | None:
    """The trace the tracer keeps for this cell, if there is one."""
    found = sorted((harness.TRACE_DIR / cell_name).glob("**/*.xplane.pb"))
    return found[0] if found else None


def read(r) -> Program | None:
    """The program's events in the run ``r`` reduced, or None where the run
    was not traced or its trace is not on disk."""
    if r.trace is None:
        return None
    path = find(r.cell.name)
    if path is None:
        return None
    try:
        prog = load(path)
    except (OSError, RuntimeError):
        # A file that another process is still writing or removing; the
        # harness has already read this run's own trace without error.
        return None
    if prog is None or prog.window != tuple(r.trace.window):
        return None
    return prog
