"""The steps of a chunk's round: a counter that is always on, and a profile
span that exists only while tracing is on.

A gateway moves a chunk through a fixed series of steps: the source reads it,
waits in queues, runs the device path, builds the recipe, seals and sends it,
and waits for the ack; the sink receives, opens, decodes and lands it, hands
it to the write operator, and writes it out. Each step is a :class:`Stage`,
built once where the step runs::

    self._t_read = Stage(counters.add, "io_ns", "chunk.read")
    ...
    with self._t_read(chunk.chunk_id, force=traced):
        data = path.read_bytes()

On exit the stage adds the step's nanoseconds to its counter (two reads of
``perf_counter_ns`` and nothing else when tracing is off: the per-call state
lives in the stage's thread-local slots, so a call allocates no object). On
entry it enters ``get_tracer().span(name, trace_id=..., cat=PROFILE_CAT,
args=..., force=...)``, looked up at each call, which a ``jax.profiler``
trace in progress records as ``host:<name>`` (``obs/tracer.py``).

The profile gets leaves, not envelopes: a step's stage covers the work of
that step alone, so the profile's spans do not nest and a device-idle gap is
given to the step the host was in. The envelopes of a chunk (``wire.frame``
on the sender, ``decode`` on the receiver) keep their own categories.

:class:`StageCounters` is the counter sink the operators share: one dict a
thread, so an ``add`` takes no lock, merged on read.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Iterable, List, Optional

from skyplane_tpu.obs.tracer import PROFILE_CAT, get_tracer

_clock = time.perf_counter_ns


class StageCounters:
    """Nanosecond (or any integer) counters under fixed keys, one dict a
    thread: ``add`` is a plain dict update with no lock, and :meth:`totals`
    sums the shards. A read that races an ``add`` sees each counter either
    before or after it, never torn."""

    def __init__(self, keys: Iterable[str]):
        self.keys = tuple(keys)
        self._tls = threading.local()
        self._lock = threading.Lock()  # guards the shard list only
        self._shards: List[dict] = []

    def _shard(self) -> dict:
        d = getattr(self._tls, "counters", None)
        if d is None:
            d = dict.fromkeys(self.keys, 0)
            with self._lock:
                self._shards.append(d)
            self._tls.counters = d
        return d

    def add(self, key: str, n: int) -> None:
        self._shard()[key] += n

    def totals(self) -> dict:
        with self._lock:
            shards = list(self._shards)
        out = dict.fromkeys(self.keys, 0)
        for d in shards:
            for k in self.keys:
                out[k] += d[k]
        return out


class Stage:
    """One step of a chunk's round. ``add(key, ns)`` is the counter sink
    (``None`` counts nowhere unless a call names ``into``); ``key`` the
    counter; ``name`` the span. Call it with the chunk id, then enter it::

        with stage(chunk_id, force=traced, args=span_args):
            ...

    ``into``, where a call gives it, is a dict the step's nanoseconds are
    added to in place of the sink (a per-chunk record such as
    ``parse_recipe``'s ``ref_stats``). After the block, :attr:`started_ns`,
    :attr:`last_ns` and :attr:`ended_ns` give this thread's last entry
    clock, duration and exit clock, so
    a caller that needs the step's interval reads it from here and takes no
    clock of its own. A stage does not nest within itself on one thread."""

    __slots__ = ("_add", "key", "name", "_tls")

    def __init__(self, add: Optional[Callable[[str, int], None]], key: str, name: str):
        self._add = add
        self.key = key
        self.name = name
        self._tls = threading.local()

    def __call__(self, trace_id: Optional[str] = None, force: bool = False, args=None, into: Optional[dict] = None) -> "Stage":
        tls = self._tls
        tls.trace_id = trace_id
        tls.force = force
        tls.args = args
        tls.into = into
        return self

    def __enter__(self) -> "Stage":
        tls = self._tls
        tls.t0 = _clock()  # the counter's interval holds the span's
        span = get_tracer().span(self.name, trace_id=tls.trace_id, cat=PROFILE_CAT, args=tls.args, force=tls.force)
        span.__enter__()
        tls.span = span
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        tls = self._tls
        tls.span.__exit__(exc_type, exc, tb)
        ns = _clock() - tls.t0
        tls.ns = ns
        into = tls.into
        if into is not None:
            into[self.key] = into.get(self.key, 0) + ns
        elif self._add is not None:
            self._add(self.key, ns)
        return False

    @property
    def started_ns(self) -> int:
        """This thread's entry clock of the stage's last run."""
        return self._tls.t0

    @property
    def last_ns(self) -> int:
        """This thread's duration of the stage's last run."""
        return self._tls.ns

    @property
    def ended_ns(self) -> int:
        """This thread's exit clock of the stage's last run."""
        return self._tls.t0 + self._tls.ns
