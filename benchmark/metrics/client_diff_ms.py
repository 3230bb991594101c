"""Client layer (rpc/client.py `DeltaSession`): ms per Assign cycle in the
client's own stages around the send.

`client.check` (`codec.delta_safe` over every record) + `client.diff`
(`codec.delta_between` and the delta's size) + `client.remember` (the
sent records kept as the next base) spans, the window's total over its
cycle count."""

NAMES = ("client.check", "client.diff", "client.remember")


def read(run: dict):
    cycles = run["cycles"]
    spans = [s.dur_s for s in run["spans"] if s.name in NAMES]
    if not cycles or not spans:
        return None
    return sum(spans) * 1e3 / len(cycles)
