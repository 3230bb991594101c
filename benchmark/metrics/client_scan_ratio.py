"""Client layer (rpc/client.py `DeltaSession`): records the diff walked
per record it shipped.

Over the window's `client.diff` spans, the total of their `visited`
counts (the records `codec.delta_between`'s loops examined: the new
snapshot's, then the base's scanned for removals) over the total of their
`shipped` counts (upserts plus removes)."""


def read(run: dict):
    diffs = [s.attrs for s in run["spans"] if s.name == "client.diff"]
    shipped = sum(a.get("shipped", 0) for a in diffs)
    if not run["cycles"] or not diffs or shipped <= 0:
        return None
    return sum(a.get("visited", 0) for a in diffs) / shipped
