"""Kernels (kernels/assign.py fast mode): commit rounds per solve.

The `rounds` count the sidecar records on each `fetch.join` span (the
solve's commit rounds, as the engine packs them beside its result), the
window's total over its cycle count."""


def read(run: dict):
    rounds = [s.attrs.get("rounds") for s in run["spans"]
              if s.name == "fetch.join"]
    if not run["cycles"] or not rounds or None in rounds:
        return None
    return sum(rounds) / len(run["cycles"])
