"""What the cells' tests ask of the manifest they load, as rules that a
later PR's additions keep: a later cell is appended to a metric's
``workloads`` and a later metric to ``per_layer``, so no test holds a list
to its length or its end, and none counts cells or metrics as a literal.
"""

from __future__ import annotations

from typing import List


def begins_with(entry: dict, cells: List[str]) -> bool:
    """The metric was brought for ``cells``, in that order: whatever lists
    it now, those come first."""
    return entry.get("workloads", [])[:len(cells)] == list(cells)


def brought_for(manifest: dict, cell: str) -> List[str]:
    """The per-layer metrics that came with ``cell``: it heads their
    ``workloads``."""
    return [x["name"] for x in manifest["per_layer"]
            if begins_with(x, [cell])]


def stand_together(manifest: dict, names) -> bool:
    """``names`` are consecutive entries of ``per_layer``, in that order."""
    have = [x["name"] for x in manifest["per_layer"]]
    at = have.index(names[0])
    return have[at:at + len(names)] == list(names)


def file_holds_entry(spec: dict, entry: dict) -> bool:
    """A metric's file says what its manifest entry says, key for key but
    ``workloads``: a later PR can extend that list in the manifest alone,
    and the harness reads the manifest's and no other
    (``harness.metrics_of``)."""
    return all(spec[k] == v for k, v in entry.items() if k != "workloads")
