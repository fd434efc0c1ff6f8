"""Closed loop: each client sends its next query when the last one's result
is on the host. Parameters of a mix (``traffic/<mix>.json``):

- ``queries``: the query names every client cycles through. Every seed
  sends the same set; with more than one, each cycle's order is drawn from
  the seed.
- ``clients``: how many such clients run side by side, one thread each.
- ``traced_queries``: how many queries a ``--trace 1`` run sends a client
  (the profiler holds all of them), 3 if absent.

A query is started only while the window is open; the one in flight when it
closes is finished and counted, so a run lasts up to one query longer than
its window. A query that raises is recorded as failed, and the loop goes on.
"""

from __future__ import annotations

import random
import threading
import time
import traceback


def run(call, mix: dict, seed: int, seconds: float, limit=None) -> list:
    """``call(query)`` -> whatever the tier returns; it is kept for the
    comparison after the window. ``limit`` caps the queries a client sends.
    -> one record a query: client, query, start and end on
    ``time.perf_counter()``, result (None where it raised)."""
    records: list = []
    lock = threading.Lock()
    opened = time.perf_counter()

    def client(index: int) -> None:
        rng = random.Random(f"{seed}.{index}")
        sent = 0
        while True:
            cycle = list(mix["queries"])
            rng.shuffle(cycle)
            for query in cycle:
                start = time.perf_counter()
                if start - opened >= seconds or sent == limit:
                    return
                try:
                    result = call(query)
                except Exception:
                    traceback.print_exc()
                    result = None
                record = {"client": index, "query": query, "start": start,
                          "end": time.perf_counter(), "result": result}
                sent += 1
                with lock:
                    records.append(record)

    clients = int(mix.get("clients", 1))
    if clients == 1:
        client(0)  # on the caller's thread, as a script would
    else:
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    return sorted(records, key=lambda r: r["end"])
