"""The plain reference in worker processes, so that it keeps up with the rows.

``lib/reference.py`` takes 1.9 s for a 64 MiB row and a run holds every row
it sent to it. A row is a function of (``content``, ``--seed``, index), and
the generators and the reference import numpy only: each worker builds the
generator itself, makes row *i* again and returns its segment ends and
fingerprints, so no row's bytes cross a pipe.

The workers are started before jax is imported and before the run has a
thread, and then wait on their stdin: they import and build nothing, numpy
included, until the run asks for a row, which it does once the window has
closed. A window's host CPU is the cell's own, as is the set-up's, and no
metric may read differently because the yardstick was busy.
A worker ends when its stdin closes, so none outlives the run however the run
ends; ``close`` and ``kill`` end them sooner.

This file is the worker too: ``python3 lib/refpool.py`` reads one JSON line
(generator file, content, seed, scale, cut) and then one row index a line, and
answers each with a JSON line (index, segments, when it started, seconds) and
the ends (int64) and fingerprints (16 bytes each) as raw bytes.
"""

from __future__ import annotations

import importlib.util
import json
import os
import queue
import subprocess
import sys
import threading
import time
from pathlib import Path
from typing import Dict, Iterable, List, Tuple

FP_BYTES = 16  # numpy is imported where it is used: a waiting worker has loaded nothing


def pool_size() -> int:
    """Half the cores, at most 8: the gateways still finish the chunks in
    flight while the reference runs."""
    return max(1, min(8, (os.cpu_count() or 2) // 2))


class ReferencePool:
    def __init__(self, generator_file: Path, content: dict, seed: int, scale: int, cdc: Iterable[int], workers: int):
        spec = {"generator": str(generator_file), "content": content, "seed": int(seed), "scale": int(scale), "cdc": list(cdc)}
        self.procs: List[subprocess.Popen] = []
        try:
            for _ in range(workers):
                proc = subprocess.Popen([sys.executable, str(Path(__file__).resolve())], stdin=subprocess.PIPE, stdout=subprocess.PIPE)
                self.procs.append(proc)
                proc.stdin.write(json.dumps(spec).encode() + b"\n")
                proc.stdin.flush()
        except BaseException:
            self.kill()
            raise

    def pids(self) -> List[int]:
        return [p.pid for p in self.procs]

    def rows(self, indices: Iterable[int]) -> Tuple[Dict[int, Tuple[np.ndarray, List[bytes]]], dict]:
        """index -> (ends, fingerprints) for every index asked, and when the
        first row was started and the workers' seconds in all. Each worker
        takes the next index when it has answered its last."""
        import numpy as np

        todo: queue.Queue = queue.Queue()
        for i in indices:
            todo.put(int(i))
        out: Dict[int, Tuple[np.ndarray, List[bytes]]] = {}
        stats: List[dict] = []
        errors: List[BaseException] = []

        def drive(proc: subprocess.Popen) -> None:
            try:
                while True:
                    try:
                        index = todo.get_nowait()
                    except queue.Empty:
                        return
                    proc.stdin.write(f"{index}\n".encode())
                    proc.stdin.flush()
                    head = json.loads(proc.stdout.readline() or "null")
                    if head is None or head["index"] != index:
                        raise RuntimeError(f"reference worker {proc.pid} gave no answer for row {index}: {head}")
                    n = head["segments"]
                    ends = np.frombuffer(read_exactly(proc.stdout, 8 * n), dtype="<i8").astype(np.int64)
                    fps = read_exactly(proc.stdout, FP_BYTES * n)
                    out[index] = (ends, [fps[k : k + FP_BYTES] for k in range(0, len(fps), FP_BYTES)])
                    stats.append(head)
            except BaseException as err:  # noqa: BLE001 - handed to the caller below
                errors.append(err)

        threads = [threading.Thread(target=drive, args=(p,), name=f"reference-{p.pid}", daemon=True) for p in self.procs]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        info = {
            "rows": len(out),
            "workers": len(self.procs),
            "first_started_at": min((h["started_at"] for h in stats), default=None),
            "worker_seconds": round(sum(h["seconds"] for h in stats), 3),
        }
        return out, info

    def close(self, timeout: float = 10.0) -> None:
        """Close every worker's stdin, wait for each to end, kill what does not."""
        for p in self.procs:
            try:
                p.stdin.close()
            except OSError:
                pass
        until = time.monotonic() + timeout
        for p in self.procs:
            try:
                p.wait(timeout=max(0.0, until - time.monotonic()))
            except subprocess.TimeoutExpired:
                pass
        self.kill()

    def kill(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.kill()
        for p in self.procs:
            p.wait()
            for pipe in (p.stdin, p.stdout):
                try:
                    pipe.close()
                except OSError:
                    pass


def read_exactly(stream, n: int) -> bytes:
    data = stream.read(n)
    if len(data) != n:
        raise RuntimeError(f"reference worker's answer ended after {len(data)} of {n} bytes")
    return data


def worker() -> None:
    spec = json.loads(sys.stdin.buffer.readline())
    generator = None
    out = sys.stdout.buffer
    for line in sys.stdin.buffer:
        index = int(line)
        started_at, t = time.time(), time.monotonic()
        if generator is None:
            import numpy as np

            import reference  # lib/ is this script's directory: numpy only, nothing of the program

            module_spec = importlib.util.spec_from_file_location("benchmark_generator", spec["generator"])
            module = importlib.util.module_from_spec(module_spec)
            module_spec.loader.exec_module(module)
            generator = module.Generator(spec["content"], spec["seed"], spec["scale"])
        row = generator.chunk(index) if index else generator.setup_chunk()
        ends, fps = reference.cdc_and_fingerprints(row, *spec["cdc"])
        head = {"index": index, "segments": len(fps), "started_at": started_at, "seconds": time.monotonic() - t}
        try:
            out.write(json.dumps(head).encode() + b"\n" + np.asarray(ends, "<i8").tobytes() + b"".join(fps))
            out.flush()
        except BrokenPipeError:
            return  # the run has gone


if __name__ == "__main__":
    worker()
