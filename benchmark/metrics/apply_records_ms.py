"""Sidecar front (rpc/server.py `DeviceSession`, device_state.py): ms per
cycle of the delta apply's record-store upkeep: the chain re-pin copies,
proto-to-record conversion, pin restore, validation, the record-store
commit and the group and PDB member maps.

`apply.records` spans, the window's total over its cycle count."""


def read(run: dict):
    cycles = run["cycles"]
    spans = [s.dur_s for s in run["spans"] if s.name == "apply.records"]
    if not cycles or not spans:
        return None
    return sum(spans) * 1e3 / len(cycles)
