"""Sidecar front (device_state.py): ms per cycle of the delta apply's
name-order upkeep: reverse maps, the three sorted row orders and their
permutations, the mirror reorder, the name-to-row maps, running pods by
node, and the snapshot's name lists.

`apply.order` spans, the window's total over its cycle count."""


def read(run: dict):
    cycles = run["cycles"]
    spans = [s.dur_s for s in run["spans"] if s.name == "apply.order"]
    if not cycles or not spans:
        return None
    return sum(spans) * 1e3 / len(cycles)
