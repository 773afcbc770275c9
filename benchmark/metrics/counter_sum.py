"""The sum of the facts the metric's file lists under ``sum``, over the fact
it names as ``den``, times its ``scale``: a share of a round its step
counters cover, or a cost that two counters make together. Where a listed
fact is missing (a program without that counter) or the denominator is 0,
there is nothing to read."""


def read(facts: dict, run: dict):
    spec = run["metric"]
    parts = [facts.get(name) for name in spec["sum"]]
    den = facts.get(spec["den"])
    if any(p is None for p in parts) or not den:
        return None
    return sum(parts) / den * spec["scale"]
