"""Engine side of the sidecar (rpc/server.py): ms per cycle of the
`server.Assign` handler that none of its stage spans covers.

For each `server.Assign` root span, its duration less the union of its
direct children (every span whose `parent_id` is the root's `span_id`)
within it; the window's total over its cycle count. It bounds what the
sidecar's spans still miss."""


def read(run: dict):
    cycles = run["cycles"]
    roots = {s.span_id: s for s in run["spans"] if s.name == "server.Assign"}
    if not cycles or not roots:
        return None
    kids: dict = {}
    for s in run["spans"]:
        if s.parent_id in roots:
            kids.setdefault(s.parent_id, []).append(
                (s.t_wall, s.t_wall + s.dur_s))
    total = 0.0
    for sid, root in roots.items():
        lo, hi = root.t_wall, root.t_wall + root.dur_s
        covered, end = 0.0, lo
        for a, b in sorted(kids.get(sid, ())):
            a, b = max(a, end), min(b, hi)
            if b > a:
                covered += b - a
                end = b
        total += root.dur_s - covered
    return total * 1e3 / len(cycles)
