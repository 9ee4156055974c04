"""What every kind of traffic shares: reading a mix file
(``benchmark/traffic/<mix>.json``), finding its kind
(``benchmark/traffic/<kind>.py``) by name, making items in worker
processes, and the loops that offer the requests.

A kind module defines ``Traffic``, a subclass of :class:`Kind`:

- ``shared_jobs()`` and ``item_job(stream, i)``: ``(function name, args)``
  of functions in the kind's own module, which the prefetcher runs in worker
  processes.  They touch only the benchmark's own arithmetic (``ecref``,
  ``kzgref``), never JAX or the program.
- ``take_shared(results)``: the parent wraps what the shared jobs made.
- ``request(raw)``: the program's own objects for one request, made outside
  the timed call.
- ``call(request)``: the timed entry, the one the node calls.
- ``work(raw)``: the live sizes of the request, which the roofline readers
  count their work from.
- ``drive(next_request, fire, deadline)``: the loop that offers the
  requests; :class:`Kind`'s is a closed loop of one caller, and a kind
  that needs arrivals of its own calls :func:`open_loop`.

Item ``i`` of a stream depends only on ``(seed, stream, i)``, never on the
number of workers or the order they finish in.  ``expect`` on each raw item
is its spec verdict, known from how it was made.
"""

from __future__ import annotations

import importlib.util
import json
import os
import random
import time
from concurrent.futures import ProcessPoolExecutor, ThreadPoolExecutor
from multiprocessing import get_context


def load_mix(name: str, root: str) -> dict:
    """``<root>/benchmark/traffic/<name>.json``."""
    with open(os.path.join(root, "benchmark", "traffic", f"{name}.json")) as f:
        return json.load(f)


def kind_path(kind: str, root: str) -> str:
    return os.path.join(root, "benchmark", "traffic", f"{kind}.py")


_MODULES: dict = {}


def load_module(path: str):
    """The module in ``path``, loaded once a process."""
    if path not in _MODULES:
        name = "bench_traffic_" + os.path.splitext(os.path.basename(path))[0]
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def open_traffic(cfg: dict, mix: dict, seed: int, root: str) -> "Kind":
    path = kind_path(mix["kind"], root)
    traffic = load_module(path).Traffic(cfg, mix, seed)
    traffic.path = path
    return traffic


def run_job(path: str, fn: str, args: tuple):
    """A worker's job: ``fn(*args)`` of the kind module in ``path``."""
    return getattr(load_module(path), fn)(*args)


def rng(seed: int, *parts) -> random.Random:
    return random.Random("/".join(str(p) for p in (seed,) + parts))


def tampered(seed: int, stream: str, i: int, every: int):
    """The tampered group item ``i`` belongs to, or None: one item in each run
    of ``every`` consecutive items of the window's stream, at a seeded place."""
    if stream != "window" or not every:
        return None
    group = i // every
    if i % every != rng(seed, "tamper", group).randrange(every):
        return None
    return group


def closed_loop(next_request, fire, deadline: float) -> None:
    """One caller, handing over its next request when the previous verdict
    is back, until ``deadline`` or no request is left."""
    while time.perf_counter() < deadline:
        job = next_request()
        if job is None:
            return
        fire(job, None)


def open_loop(next_request, fire, deadline: float, arrivals, callers: int) -> None:
    """Requests offered at ``arrivals`` (seconds from now, ascending) whatever
    the verdicts before them; each latency runs from its arrival.  Waits for
    every request offered before ``deadline``."""
    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=max(1, callers)) as ex:
        for at in arrivals:
            due = t0 + at
            if due >= deadline:
                break
            job = next_request()
            if job is None:
                break
            time.sleep(max(0.0, due - time.perf_counter()))
            ex.submit(fire, job, due)


class Kind:
    """The steps every kind shares; a kind overrides what it needs."""

    path = ""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        self.cfg, self.mix, self.seed = cfg, mix, seed

    def shared_jobs(self) -> list:
        return []

    def take_shared(self, results) -> None:
        pass

    def drive(self, next_request, fire, deadline: float) -> None:
        closed_loop(next_request, fire, deadline)


class Prefetch:
    """Makes a run's items in worker processes while the parent brings up
    the chip: the shared jobs, then ``warmup`` items, then ``pool`` items of
    the window's stream.  Workers never import JAX.  Leaving the ``with``
    block stops and joins every worker."""

    def __init__(self, traffic: Kind, warmup: int, pool: int, workers: int):
        self.traffic = traffic
        self._ex = ProcessPoolExecutor(max_workers=max(1, workers),
                                       mp_context=get_context("spawn"))
        self._shared = [self._ex.submit(run_job, traffic.path, fn, a)
                        for fn, a in traffic.shared_jobs()]
        self._warm = [self._submit("warmup", i) for i in range(warmup)]
        self._pool = [self._submit("window", i) for i in range(pool)]

    def _submit(self, stream: str, i: int):
        fn, args = self.traffic.item_job(stream, i)
        return self._ex.submit(run_job, self.traffic.path, fn, args)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._ex.shutdown(wait=True, cancel_futures=True)
        return False

    def shared(self):
        self.traffic.take_shared([f.result() for f in self._shared])

    def warmup(self) -> list:
        return [f.result() for f in self._warm]

    def pool(self) -> list:
        return [f.result() for f in self._pool]


def make_inline(traffic: Kind, stream: str, i: int) -> dict:
    """An item made in this process: past the pool, or in a test."""
    fn, args = traffic.item_job(stream, i)
    return run_job(traffic.path, fn, args)
