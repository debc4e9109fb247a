"""Device seconds of one program module, read by name from a traced run.

``program_trace`` reads the solve program; this reads any module the same
way: the events of line "XLA Modules" on each "/device:TPU:<i>" plane whose
name is "<module>(<fingerprint>)", clipped to the ``bench.traced`` window,
over the ``bench.closure`` spans in it.  The trace is read only when its
window is the one the harness reduced into ``Readings.trace``; a program
that runs no such module reads None, never 0.
"""
from __future__ import annotations

from bench import program_trace
from bench.xplane import DEVICE_PLANE, HOST_PLANE, TRACED_SPAN


def load(path, module: str):
    """(window, per-chip [(start, end)] of ``module``, closures) from one
    ``.xplane.pb``; None without a ``bench.traced`` span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    chips, host = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            runs = [(e.start_ns, e.start_ns + e.duration_ns)
                    for line in plane.lines
                    if line.name == program_trace.MODULES_LINE
                    for e in line.events
                    if program_trace.module_name(e.name) == module]
            chips.append((plane.name, runs))
        elif plane.name == HOST_PLANE:
            host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for line in plane.lines for e in line.events
                        if e.name in (TRACED_SPAN,
                                      program_trace.CLOSURE_SPAN))
    traced = [(a, b) for n, a, b in host if n == TRACED_SPAN]
    if not traced:
        return None
    lo, hi = min(a for a, _ in traced), max(b for _, b in traced)
    closures = len(program_trace._clip(
        [(a, b) for n, a, b in host if n == program_trace.CLOSURE_SPAN],
        lo, hi))
    runs = [program_trace._clip(r, lo, hi) for _, r in sorted(chips)]
    return (lo, hi), runs, closures


def device_s(r, module: str) -> float | None:
    """Device seconds per closure of ``module`` in the run ``r`` reduced,
    averaged over the chips traced; None where there is none to read."""
    if r.trace is None:
        return None
    path = program_trace.find(r.cell.name)
    if path is None:
        return None
    try:
        got = load(path, module)
    except (OSError, RuntimeError):
        return None
    if got is None or got[0] != tuple(r.trace.window):
        return None
    _, runs, closures = got
    if not closures or not any(runs):
        return None
    total = sum(b - a for chip in runs for a, b in chip)
    return total / max(1, len(runs)) / closures * 1e-9
