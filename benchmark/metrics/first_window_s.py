"""Seconds of the phase the metric's file names, from flight-recorder events:
start and end edges paired by ``phase_id``, on the recorder's monotonic clock."""


def read(facts: dict, run: dict):
    kind = run["metric"]["event_kind"]
    starts, total, found = {}, 0.0, False
    for ev in run["events"]:
        if ev.get("kind") != kind:
            continue
        if ev.get("edge") == "start":
            starts[ev["phase_id"]] = ev["mono"]
        elif ev.get("edge") == "end" and ev["phase_id"] in starts:
            total += ev["mono"] - starts.pop(ev["phase_id"])
            found = True
    return total if found else None
