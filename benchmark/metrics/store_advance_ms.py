"""Sidecar front (rpc/server.py): ms per cycle advancing the byte store by
the delta: the upsert check, the base's copy, the delta applied, and
the store registered with its replication-log entry.

`store.advance` spans, the window's total over its cycle count."""


def read(run: dict):
    cycles = run["cycles"]
    spans = [s.dur_s for s in run["spans"] if s.name == "store.advance"]
    if not cycles or not spans:
        return None
    return sum(spans) * 1e3 / len(cycles)
