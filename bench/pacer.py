#!/usr/bin/env python3
"""The open-loop pacer: a process of its own that keeps the arrival clock.

    python3 bench/pacer.py   (started by ``bench/generators/open_loop.py``)

It reads from standard input a count ``n`` (int64), ``n`` arrival
offsets in seconds (float64, sorted), answers ``n`` (int32) once it
holds them, then reads the window's start instant
on ``time.perf_counter``'s clock (float64; the clock is the system's
monotonic one, shared by every process of the machine). At each offset
it writes the index of the request due (int32) to standard output, all
indices due at once in one write. After the last it writes -1 and four
float64: its own 99th-percentile and largest lateness in seconds, the
offset of the request it was latest for, and how many times it woke
more than ``STALL_S`` late (a stall of the whole machine: this process
does nothing else).

It imports neither JAX nor the program, so the arrivals keep their
schedule however busy the serving process is: a stall there delays the
answers, never the offers.
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

STALL_S = 0.02
TRAILER = 4       # float64 after the -1


def read_exact(fd: int, nbytes: int) -> bytes:
    buf = b""
    while len(buf) < nbytes:
        chunk = os.read(fd, nbytes - len(buf))
        if not chunk:
            raise EOFError(f"a pipe closed {nbytes - len(buf)} bytes short")
        buf += chunk
    return buf


def pace(fd_in: int, fd_out: int) -> None:
    n = int(np.frombuffer(read_exact(fd_in, 8), np.int64)[0])
    arrivals = np.frombuffer(read_exact(fd_in, 8 * n), np.float64)
    os.write(fd_out, np.int32(n).tobytes())
    t0 = float(np.frombuffer(read_exact(fd_in, 8), np.float64)[0])
    late = np.zeros(n)
    stalls = 0
    i = 0
    while i < n:
        wait = t0 + arrivals[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        now = time.perf_counter() - t0
        j = int(np.searchsorted(arrivals, now, side="right"))
        j = max(j, i + 1)
        late[i:j] = now - arrivals[i:j]
        stalls += int(late[i] > STALL_S)
        os.write(fd_out, np.arange(i, j, dtype=np.int32).tobytes())
        i = j
    worst = int(np.argmax(late)) if n else 0
    tail = ((float(np.percentile(late, 99)), float(late[worst]),
             float(arrivals[worst]), float(stalls)) if n
            else (0.0, 0.0, 0.0, 0.0))
    os.write(fd_out, np.int32(-1).tobytes()
             + np.asarray(tail, np.float64).tobytes())

if __name__ == "__main__":
    pace(sys.stdin.fileno(), sys.stdout.fileno())
