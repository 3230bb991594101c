"""Sidecar front (device_state.py): ms per cycle dispatching the delta
apply's device updates: row permutations, the node index column, every
row scatter (host dispatch; the device work runs asynchronously).

`apply.scatter` spans, the window's total over its cycle count."""


def read(run: dict):
    cycles = run["cycles"]
    spans = [s.dur_s for s in run["spans"] if s.name == "apply.scatter"]
    if not cycles or not spans:
        return None
    return sum(spans) * 1e3 / len(cycles)
