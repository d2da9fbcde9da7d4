"""The one traffic generator: turns a traffic file's parameters and a seed
into the requests of a run.

A traffic file (``bench/traffic/<name>.json``) holds data only:

``driver``
    the module under ``bench/drivers/`` that sends the requests (how many
    at a time, when); ``closed`` when the file names none.  A mix that needs
    another arrival process or another set of tenants adds a driver of its
    own and names it here.
``clients``
    for the closed loop: clients, each waiting for its reply before it
    sends again (the service gets as many workers).
``sizes``
    the problem sizes of a request, by name.  A number is fixed; an object
    ``{"range": [lo, hi], "warm": [...]}`` gives the requests sizes from
    ``lo..hi`` (inclusive) dealt as pairs (lo+k, hi-k) in a seeded order,
    so every seed's window holds about the same mix of small and large;
    ``warm`` are the sizes set-up serves first (one per trace bucket the
    range reaches), kept out of the window.  Once every size has been sent
    the same sequence starts again.
``chain``
    each client's request takes its previous reply as input (a time loop
    carried by the traffic); ``restart`` after that many requests the client
    starts again from its first input.
``structure``
    the sparsity structure a configuration draws its index arrays from:
    ``{"kind": k, "seed": s, ...}`` names ``bench/structures/<k>.py``, which
    draws the edges from its own seed ``s``; the run's seed relabels them.
"""

from __future__ import annotations

import importlib.util
import itertools
import threading
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

BENCH = Path(__file__).resolve().parent

# seeds above 2**63 are folded in, not refused: SeedSequence takes any int
_SEED_SPACE = 2**64


def rng(seed: int, *path: int) -> np.random.Generator:
    """A generator for one purpose of one run: ``path`` names the purpose
    (client, request index, ...), so draws never depend on the order the
    threads happened to take."""

    return np.random.default_rng(
        np.random.SeedSequence([seed % _SEED_SPACE, *path])
    )


def load_module(path: Path, name: str):
    """The Python file at ``path`` as a module named ``name``."""

    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def plugin(kind: str, name: str, base: Path = BENCH):
    """``<base>/<kind>/<name>.py``: a driver, a structure or a metric
    reader, found by its name under the benchmark's directory."""

    path = Path(base) / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {kind[:-1]} {name!r}: {path} does not exist")
    return load_module(path, f"{kind}_{name}")


def size_values(spec: dict) -> Tuple[List[int], List[int]]:
    """(the window's sizes in ascending order, the warm sizes)."""

    lo, hi = spec["range"]
    warm = list(spec.get("warm", []))
    return [n for n in range(lo, hi + 1) if n not in warm], warm


def size_order(spec, seed: int) -> List[int]:
    """The window's sequence of one varying size: every size of the range
    once, dealt as pairs (lo+k, hi-k) in a seeded order."""

    values, _ = size_values(spec)
    r = rng(seed, 0xA11)
    pairs = [
        (values[k], values[len(values) - 1 - k])
        for k in range(len(values) // 2)
    ]
    out: List[int] = []
    for k in r.permutation(len(pairs)):
        a, b = pairs[k]
        out.extend((a, b) if r.random() < 0.5 else (b, a))
    if len(values) % 2:
        out.insert(int(r.integers(0, len(out) + 1)), values[len(values) // 2])
    return out


class RequestStream:
    """The requests of one run, shared by the clients: each ``next()`` is
    the next request of the seeded sequence, whichever client asks."""

    def __init__(self, traffic: dict, seed: int) -> None:
        self.traffic = traffic
        sizes = traffic["sizes"]
        self._varying = {
            k: size_order(v, seed) for k, v in sizes.items()
            if not isinstance(v, int)
        }
        self._fixed = {k: v for k, v in sizes.items() if isinstance(v, int)}
        self._lock = threading.Lock()
        self._count = itertools.count()
        self.sent = 0

    def warm_sizes(self) -> List[Dict[str, int]]:
        """The sizes set-up serves before the window: every trace bucket the
        window reaches, with none of the window's own sizes."""

        out = [dict(self._fixed)]
        for k, spec in self.traffic["sizes"].items():
            if isinstance(spec, int):
                continue
            _, warm = size_values(spec)
            out = [dict(o, **{k: w}) for o in out for w in warm]
        return out

    def passes(self) -> int:
        """How many passes over its sizes the stream has begun (a size
        repeats from the second on)."""

        if not self._varying:
            return 1
        period = min(len(order) for order in self._varying.values())
        return -(-self.sent // period)

    def next(self) -> Tuple[int, Dict[str, int]]:
        """(request index, sizes)."""

        with self._lock:
            k = next(self._count)
            self.sent = k + 1
        sizes = dict(self._fixed)
        for name, order in self._varying.items():
            sizes[name] = order[k % len(order)]
        return k, sizes


def edges(structure: dict, cfg: dict, seed: int,
          base: Path = BENCH) -> Tuple[np.ndarray, np.ndarray]:
    """(row, col) of a run's matrix, int64.  The structure's module draws
    the edges from the structure's own ``seed``, so every run holds the
    same degrees (and the same inspector depth); the run's seed relabels
    the vertices and reorders the edges, as Graph500 does."""

    draw = plugin("structures", structure["kind"], base).edges
    row, col = draw(cfg, structure, rng(structure["seed"], 7))
    r = rng(seed, 8)
    labels = r.permutation(1 << cfg["SCALE"])
    order = r.permutation(row.size)
    return labels[row][order], labels[col][order]
