"""The closed loop: ``clients`` threads, each sending its next request when
its previous reply is in, for the whole window.

Reads the traffic keys ``clients``, ``sizes``, ``chain`` and ``restart``
(see :mod:`gen`).  A driver module gives the harness three functions:

* ``workers(traffic)``: the service's worker count;
* ``warm(cell)``: the (sizes, store) of the requests set-up serves, every
  shape the window will use;
* ``serve(cell, svc, seconds, log, on_start)``: starts the load, returns a
  :class:`harness.Load` at the window's start, and hands every request it
  sends to ``log.add``.
"""

from __future__ import annotations

import threading
import time

import gen
import harness

# request indices of the warm-up, apart from the window's
WARM_INDEX = 10**9


def workers(traffic: dict) -> int:
    return traffic["clients"]


def warm(cell):
    stream = gen.RequestStream(cell.traffic, cell.seed)
    clients = cell.traffic["clients"]
    return [
        (sizes, cell.store(sizes, clients, WARM_INDEX + w))
        for w, sizes in enumerate(stream.warm_sizes())
    ]


def serve(cell, svc, seconds: float, log, on_start=None) -> harness.Load:
    traffic = cell.traffic
    stream = gen.RequestStream(traffic, cell.seed)
    chain = bool(traffic.get("chain"))
    restart = traffic.get("restart") if chain else None
    start = threading.Barrier(traffic["clients"] + 1)
    bounds = {}

    def client(c: int) -> None:
        prev, sent = None, 0
        start.wait()
        while time.perf_counter() < bounds["end"]:
            k, sizes = stream.next()
            if restart and sent % restart == 0:
                prev = None
            req = harness.send(cell, svc, c, k, sizes,
                               cell.store(sizes, c, k, prev))
            prev = req.out if chain else None
            sent += 1
            log.add(req)

    threads = [
        threading.Thread(target=client, args=(c,), name=f"bench-client-{c}")
        for c in range(traffic["clients"])
    ]
    for t in threads:
        t.start()
    if on_start is not None:
        on_start()
    bounds["start"] = time.perf_counter()
    bounds["end"] = bounds["start"] + seconds
    start.wait()

    def join() -> dict:
        for t in threads:
            t.join()
        return {"size_passes": stream.passes()}

    return harness.Load(bounds["start"], bounds["end"], join)
