#!/usr/bin/env python
"""Validate an exported Chrome trace-event JSON file (docs/observability.md).

Used by the devloop trace-smoke step on the trace bench.py exports
(SKYPLANE_BENCH_TRACE_OUT) and by the unit tests. Checks, in order:

  1. well-formed: a dict with a non-empty ``traceEvents`` list;
  2. event schema: every event has name/ph/pid/tid/ts; complete ("X") events
     carry a non-negative ``dur``; async begin/end ("b"/"e") events balance
     per (pid, id);
  3. nesting: on each (pid, tid) track, "X" spans either nest (child fully
     inside parent, small tolerance for clock granularity) or are disjoint —
     partial overlap means broken span scoping;
  4. correlation: at least one chunk id appears on BOTH a sender-side span
     and a receiver-side span — the cross-wire stitching the TRACED header
     flag exists for. A span's side is its category ("sender", "receiver"):
     the envelopes of a chunk (``wire.frame``, ``decode``) carry one. A step
     of the round (category "device", written into the device profile)
     takes the side of its chunk's envelope at the same gateway.

With ``--multihop`` (the collector-merged fleet timeline of a relayed
transfer, docs/observability.md), additionally:

  5. gateway rows: the merged trace carries >= 3 ``process_name`` metadata
     rows (source, relay, destination get their own Perfetto processes);
  6. full-path stitching: at least one chunk's spans carry >= 3 distinct
     ``args.gateway`` values, with sender-side spans at >= 2 gateways (the
     source AND the forwarding relay) and receiver-side spans at >= 2 (the
     relay AND the destination);
  7. hop indices: sender spans carry ``args.hop`` values 0 and 1 — the
     pre-registration hop propagation regresses silently otherwise.

Exit 0 iff all hold. A trace with zero events fails loudly: an empty export
from a "sampled" run means the sampling/flag plumbing regressed.
"""

from __future__ import annotations

import json
import sys
from collections import defaultdict

NEST_TOLERANCE_US = 5.0  # wall-clock ts vs perf-counter dur granularity skew
SIDES = ("sender", "receiver")
PROFILE_CAT = "device"  # steps of a chunk's round: no side of their own (obs/tracer.py PROFILE_CAT)


def fail(msg: str) -> int:
    print(f"trace-smoke: {msg}", file=sys.stderr)
    return 1


def span_sides(events: list):
    """(event, sides) of every event that names a chunk: a sender- or
    receiver-category span has its own category; a step of the round takes
    the sides its chunk's envelopes have at the same ``args.gateway``."""
    envelope = defaultdict(set)  # (chunk id, gateway) -> sides
    for ev in events:
        args = ev.get("args") or {}
        if args.get("chunk_id") and ev.get("cat") in SIDES:
            envelope[(args["chunk_id"], args.get("gateway"))].add(ev["cat"])
    for ev in events:
        args = ev.get("args") or {}
        cid = args.get("chunk_id")
        if not cid:
            continue
        cat = ev.get("cat")
        if cat in SIDES:
            yield ev, {cat}
        elif cat == PROFILE_CAT:
            yield ev, envelope.get((cid, args.get("gateway")), set())


def validate_multihop(trace: dict) -> int:
    """Checks 5-7: the merged fleet timeline of a >= 2-hop relay transfer."""
    events = trace.get("traceEvents", [])
    process_rows = {
        (e.get("pid"), (e.get("args") or {}).get("name"))
        for e in events
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    if len(process_rows) < 3:
        return fail(
            f"merged trace shows {len(process_rows)} gateway process rows; a 2-hop relay transfer "
            "must produce >= 3 (source, relay, destination) — did the collector merge regroup by args.gateway?"
        )
    per_chunk: dict = {}
    hops = set()
    for ev, sides in span_sides(events):
        args = ev["args"]
        cid, gw = args["chunk_id"], args.get("gateway")
        if "sender" in sides and isinstance(args.get("hop"), int):
            hops.add(args["hop"])
        if not gw:
            continue
        entry = per_chunk.setdefault(cid, {"gateways": set(), "sender": set(), "receiver": set()})
        entry["gateways"].add(gw)
        for side in sides:
            entry[side].add(gw)
    full_path = [
        cid
        for cid, e in per_chunk.items()
        if len(e["gateways"]) >= 3 and len(e["sender"]) >= 2 and len(e["receiver"]) >= 2
    ]
    if not full_path:
        best = max(per_chunk.values(), key=lambda e: len(e["gateways"]), default=None)
        return fail(
            "no chunk's spans stitch across source, relay AND destination gateways "
            f"(best chunk saw gateways {sorted(best['gateways']) if best else []}) — "
            "relay TRACED propagation or gateway span args regressed"
        )
    if not {0, 1} <= hops:
        return fail(
            f"sender spans carry hop indices {sorted(hops)}; a relayed transfer must show hops 0 AND 1 "
            "(chunk pre-registration hop propagation regressed)"
        )
    print(
        f"trace-smoke multihop OK: {len(process_rows)} gateway rows, {len(full_path)} chunk(s) stitched "
        f"across the full source->relay->destination path, sender hops {sorted(hops)}"
    )
    return 0


def validate(trace: dict, multihop: bool = False) -> int:
    if not isinstance(trace, dict) or not isinstance(trace.get("traceEvents"), list):
        return fail("not a Chrome trace: expected a dict with a traceEvents list")
    events = trace["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"]
    if not spans:
        return fail("traceEvents holds no complete ('X') spans — was sampling on?")

    # 2: per-event schema
    async_balance = defaultdict(int)
    for i, ev in enumerate(events):
        for key in ("name", "ph", "pid", "tid"):
            if key not in ev:
                return fail(f"event {i} missing {key!r}: {ev!r}")
        if ev["ph"] not in ("X", "M", "b", "e", "C", "i", "I"):
            return fail(f"event {i} has unknown phase {ev['ph']!r}")
        if ev["ph"] != "M" and "ts" not in ev:
            return fail(f"event {i} missing ts: {ev!r}")
        if ev["ph"] == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                return fail(f"X event {i} has bad dur {dur!r}")
        if ev["ph"] in ("b", "e"):
            if "id" not in ev:
                return fail(f"async event {i} missing id")
            async_balance[(ev["pid"], ev["id"])] += 1 if ev["ph"] == "b" else -1
    unbalanced = {k: v for k, v in async_balance.items() if v != 0}
    if unbalanced:
        return fail(f"unbalanced async begin/end pairs: {list(unbalanced)[:5]}")

    # 3: X-span nesting per (pid, tid) track
    tracks = defaultdict(list)
    for ev in spans:
        tracks[(ev["pid"], ev["tid"])].append((float(ev["ts"]), float(ev["dur"]), ev["name"]))
    for (pid, tid), track in tracks.items():
        track.sort()
        stack = []  # (end_ts, name)
        for ts, dur, name in track:
            end = ts + dur
            while stack and ts >= stack[-1][0] - NEST_TOLERANCE_US:
                stack.pop()
            if stack and end > stack[-1][0] + NEST_TOLERANCE_US:
                return fail(
                    f"span {name!r} on track pid={pid} tid={tid} partially overlaps enclosing "
                    f"{stack[-1][1]!r} (ends {end - stack[-1][0]:.1f}us past it) — broken span scoping"
                )
            stack.append((end, name))

    # 4: sender<->receiver correlation by chunk id
    sides = defaultdict(set)  # chunk_id -> {sides}
    for ev, ev_sides in span_sides(events):
        sides[ev["args"]["chunk_id"]] |= ev_sides
    stitched = [cid for cid, found in sides.items() if "sender" in found and "receiver" in found]
    if not stitched:
        return fail(
            "no chunk id appears on both sender- and receiver-side spans — the TRACED wire-flag "
            "propagation (or receiver force-sampling) regressed"
        )

    print(
        f"trace-smoke OK: {len(events)} events, {len(spans)} spans on {len(tracks)} tracks, "
        f"{len(stitched)} chunk(s) stitched across sender+receiver"
    )
    if multihop:
        return validate_multihop(trace)
    return 0


def main(argv) -> int:
    args = [a for a in argv[1:] if not a.startswith("--")]
    flags = [a for a in argv[1:] if a.startswith("--")]
    # unknown flags are a hard error: a typo'd --multihop must not silently
    # downgrade the gate to single-hop checks and exit green
    unknown = [f for f in flags if f != "--multihop"]
    if len(args) != 1 or unknown:
        if unknown:
            print(f"unknown flag(s): {' '.join(unknown)}", file=sys.stderr)
        print("usage: check_trace_json.py <trace.json> [--multihop]", file=sys.stderr)
        return 2
    multihop = "--multihop" in flags
    try:
        with open(args[0]) as f:
            trace = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return fail(f"cannot load {args[0]}: {e}")
    return validate(trace, multihop=multihop)


if __name__ == "__main__":
    sys.exit(main(sys.argv))
