"""The benchmark's cells cut to a size the CPU tests hold."""
import _paths  # noqa: F401

from bench import harness


def tiny(name: str) -> harness.Cell:
    cell = harness.find_cell(harness.load_spec(), name)
    cell.config = dict(cell.config, n=128)
    cell.traffic = dict(cell.traffic, check_rows=8, max_closures=4)
    return cell
