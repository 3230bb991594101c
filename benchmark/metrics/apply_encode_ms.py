"""Sidecar front (device_state.py): ms per cycle of the delta apply's
O(churn) encoding: normalizing and interning the churned records,
bucket checks, group and PDB touch sets, and the churned rows and table
rows re-encoded into the host mirror.

`apply.encode` spans, the window's total over its cycle count."""


def read(run: dict):
    cycles = run["cycles"]
    spans = [s.dur_s for s in run["spans"] if s.name == "apply.encode"]
    if not cycles or not spans:
        return None
    return sum(spans) * 1e3 / len(cycles)
