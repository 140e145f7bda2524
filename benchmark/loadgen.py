"""The load generator: closed-loop clients in processes of their own, so
that no Python of theirs shares the server's interpreter lock.

``run.py`` starts ``processes`` of ``client_process`` (spawned), each
with ``threads`` closed-loop clients.  A client sends its next request
when the last one is answered, from its own slice of the pool, until the
window closes; the request in flight then is waited for.  All clients
open the window at the same instant of the machine's monotonic clock.
"""

from __future__ import annotations

import os
import pickle
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def _client(client, items, t_start, seconds, out):
    """One closed loop.  Appends (pool index, sent_s, latency_s, ok,
    answer, gap_s): ``gap_s`` is what the generator itself took between
    the last answer and this send."""
    t_end = t_start + seconds
    while time.monotonic() < t_start:
        time.sleep(min(0.001, max(t_start - time.monotonic(), 0)))
    last = time.monotonic()
    k = 0
    while True:
        index, wire = items[k % len(items)]
        t0 = time.monotonic()
        if t0 >= t_end:
            return
        ok, answer = client.call(wire)
        t1 = time.monotonic()
        out.append((index, t0 - t_start, t1 - t0, ok, answer, t0 - last,
                    k >= len(items)))
        last = t1
        k += 1


def client_process(kind_name, address, items, threads, seconds, timeout,
                   ready, go, t_start, out_path):
    sys.path[:0] = [os.path.dirname(HERE), HERE]
    import manifest

    kind = manifest.kind(kind_name)
    shared = getattr(kind, "SHARED_CLIENT", False)
    clients = [kind.Client(address, timeout)
               for _ in range(1 if shared else threads)]
    records = [[] for _ in range(threads)]
    ready.release()
    go.wait()
    start = t_start.value
    workers = [
        threading.Thread(target=_client, args=(
            clients[0 if shared else i], items[i::threads], start,
            seconds, records[i]))
        for i in range(threads)
    ]
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    for c in clients:
        c.close()
    with open(out_path, "wb") as f:
        pickle.dump([r for rec in records for r in rec], f)
