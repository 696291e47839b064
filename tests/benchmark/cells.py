"""The manifest's cells by their mix's driver, for tests that take their
cells from ``BENCHMARK.json`` so that a later cell is held with no edit."""

import manifest as M


def by_driver(driver: str) -> list:
    man = M.load_manifest()
    return [w["name"] for w in man["workloads"] if M.load_traffic(w["traffic"])["driver"] == driver]


def find(name: str) -> dict:
    """The cell with its files resolved, as ``run.py`` gets it."""
    return M.find_cell(M.load_manifest(), name)


def all_cells() -> list:
    return [w["name"] for w in M.load_manifest()["workloads"]]
