"""Kernels (kernels/assign.py signature rounds): pods the pairwise
validation rolled back per pod placed.

Over the window's `fetch.join` spans, the total of their `reverts`
counts (the commits a round's validate-and-revert fixpoint took back:
DoNotSchedule spread excess plus inter-pod affinity reverts, summed over
the solve's rounds) over the pods the window's cycles placed."""


def read(run: dict):
    reverts = [s.attrs.get("reverts") for s in run["spans"]
               if s.name == "fetch.join"]
    placed = sum(c.placed for c in run["cycles"])
    if not reverts or None in reverts or placed <= 0:
        return None
    return sum(reverts) / placed
