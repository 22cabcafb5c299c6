"""Load generator: a child process that speaks NDJSON to the server.

It never imports JAX, so it holds no chip and does not share the server's
interpreter lock. It reads one JSON plan line on standard input, runs the
warm-up phase, prints ``{"event": "ready"}``, waits for a ``go`` line, opens the
measured phase's connections, prints ``{"event": "start", "t0": ...}``,
runs the measured phase and
prints one JSON line with a record per request:
``[index, due, woke, sent, received, reply line or null, transport
error or null]``. ``woke`` is when the generator got round to the
request, ``sent`` when a connection was free. Times are on the system's
monotonic clock, which the parent reads too.

A phase is open or closed loop:

* ``open`` — request ``i`` is due at ``t0 + offsets[i]`` and is sent then
  over the first free connection of a pool, whatever happened to earlier
  requests. Its latency runs from when it was due.
* ``closed`` — ``sessions`` connections each send their next request
  when the last reply has come; no request is sent after ``duration_s``.
  Latency runs from the send.

Replies are passed on unparsed: the parent checks them.

    python bench/loadgen.py < plan.json
"""

from __future__ import annotations

import asyncio
import json
import sys
import time

LINE_LIMIT = 1 << 28  # bytes in one reply line (khop replies are long)
REPLY_TIMEOUT_S = 60.0  # a reply later than this is recorded as lost


async def _connect(host: str, port: int):
    return await asyncio.open_connection(host, port, limit=LINE_LIMIT)


async def _ask(conn, rid: int, line: str):
    reader, writer = conn
    writer.write(('{"op": "query", "id": %d, "request": %s}\n'
                  % (rid, line)).encode())
    await writer.drain()
    reply = await asyncio.wait_for(reader.readline(), REPLY_TIMEOUT_S)
    if not reply:
        raise ConnectionError("connection closed by the server")
    return reply.decode()


async def _open_loop(host, port, requests, offsets, n_conn, start, out):
    pool: asyncio.Queue = asyncio.Queue()
    for _ in range(n_conn):
        pool.put_nowait(await _connect(host, port))
    t0 = start()

    async def one(i: int) -> None:
        due = t0 + offsets[i]
        delay = due - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        woke = time.monotonic()
        conn = await pool.get()
        sent = time.monotonic()
        try:
            reply = await _ask(conn, i, requests[i])
            out[i] = [i, due, woke, sent, time.monotonic(), reply, None]
            pool.put_nowait(conn)
        except (OSError, asyncio.TimeoutError, ConnectionError) as e:
            out[i] = [i, due, woke, sent, time.monotonic(), None,
                      f"{type(e).__name__}: {e}"]
            conn[1].close()
            try:
                pool.put_nowait(await _connect(host, port))
            except OSError:
                pass  # the pool shrinks; later requests wait longer

    await asyncio.gather(*(one(i) for i in range(len(requests))))
    while not pool.empty():
        pool.get_nowait()[1].close()


async def _closed_loop(host, port, requests, sessions, duration, start,
                       out):
    cursor = iter(range(len(requests)))
    conns = [await _connect(host, port) for _ in range(sessions)]
    end = start() + duration

    async def session(conn) -> None:
        try:
            while time.monotonic() < end:
                i = next(cursor, None)
                if i is None:
                    return
                sent = time.monotonic()
                try:
                    reply = await _ask(conn, i, requests[i])
                    out[i] = [i, sent, sent, sent, time.monotonic(), reply,
                              None]
                except (OSError, asyncio.TimeoutError, ConnectionError) as e:
                    out[i] = [i, sent, sent, sent, time.monotonic(), None,
                              f"{type(e).__name__}: {e}"]
                    conn[1].close()
                    conn = await _connect(host, port)
        finally:
            conn[1].close()

    await asyncio.gather(*(session(c) for c in conns))


def run_phase(host: str, port: int, phase: dict, start) -> list:
    """Connect, call ``start()`` for the phase's start time, run it."""
    requests = phase["requests"]
    out: list = [None] * len(requests)
    if phase["loop"] == "open":
        coro = _open_loop(host, port, requests, phase["offsets"],
                          phase["connections"], start, out)
    else:
        coro = _closed_loop(host, port, requests, phase["sessions"],
                            phase["duration_s"], start, out)
    asyncio.run(coro)
    return [r for r in out if r is not None]


def _emit(obj) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def main() -> int:
    plan = json.loads(sys.stdin.readline())
    host, port = plan["host"], plan["port"]
    lead = float(plan.get("lead_s", 0.05))
    done = run_phase(host, port, plan["warmup"],
                     lambda: time.monotonic() + lead)
    _emit({"event": "ready", "warmup_done": len(done)})
    if sys.stdin.readline().strip() != "go":
        return 1

    def start() -> float:
        t0 = time.monotonic() + lead
        _emit({"event": "start", "t0": t0})
        return t0

    _emit({"event": "records",
           "records": run_phase(host, port, plan["window"], start)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
