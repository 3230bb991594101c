"""Sidecar front (device_state.py): names the delta apply's order, index
and map upkeep examined per record it applied.

Over the window's `delta.apply` spans that carry them, the total of their
`order_visited` counts (`ApplyStats.order_visited`: one per churned name
per pass and one per bisect lookup) over the total of their
`churn_records` (upserts plus removes the apply carried)."""


def read(run: dict):
    applies = [s.attrs for s in run["spans"]
               if s.name == "delta.apply" and "order_visited" in s.attrs]
    churn = sum(a.get("churn_records", 0) for a in applies)
    if not run["cycles"] or not applies or churn <= 0:
        return None
    return sum(a["order_visited"] for a in applies) / churn
