"""The traffic generators: a mix's parameters in, one query's input out.

A traffic file under ``bench/traffic/`` names a ``generator`` and its
parameters: one of ``GENERATORS`` below, else the ``generate(rng,
params, cfg)`` of ``bench/generators/<generator>.py``.  Each query's
input is drawn from ``(seed, query index)`` alone, so a run never
simulates the same input twice and two runs of one seed simulate the
same inputs.  Sizes never depend on the seed.

Built-in generators (vectorised copies of the program's builders, with
the same semantics):

* ``mixed_trace`` -- ``n_ops`` page ops, each a read with probability
  ``read_fraction``; a drive of ``ways`` ways places them by
  ``round_robin`` over its channels, then its ways.
* ``poisson_stream`` -- ``n_requests`` one-page requests with
  exponential inter-arrival gaps of mean ``mean_interarrival_us``, the
  first at t = 0, each a read with probability ``read_fraction``.
* ``overwrite_stream`` -- ``n_requests`` one-page requests to uniform
  random logical pages of the drive's logical space, each a read with
  probability ``read_fraction``; all arrive at t = 0 (a saturating
  burst) unless ``mean_interarrival_us`` is positive.
"""

from __future__ import annotations

import numpy as np

import loader

READ, WRITE = 0, 1


def rng_for(seed: int, index: int) -> np.random.Generator:
    """The stream of query ``index`` of a run seeded ``seed``; warm-up
    queries take negative indices."""
    return np.random.default_rng([int(seed) % 2**64, int(index) % 2**64])


def _classes(rng, n: int, read_fraction: float) -> np.ndarray:
    return np.where(rng.random(n) < read_fraction, READ, WRITE).astype(
        np.int32)


def _arrivals(rng, n: int, mean_us: float) -> np.ndarray:
    if mean_us <= 0.0:
        return np.zeros(n, np.float32)
    gaps = rng.exponential(mean_us, n)
    gaps[0] = 0.0
    return np.cumsum(gaps).astype(np.float32)


def round_robin(n: int, channels: int, ways: int):
    """(channel, way, parity) of ``n`` ops placed round-robin over
    channels, then ways; a chip's page parity alternates with each
    visit."""
    t = np.arange(n)
    return ((t % channels).astype(np.int32),
            ((t // channels) % ways).astype(np.int32),
            ((t // (channels * ways)) % 2).astype(np.int32))


def mixed_trace(rng, p: dict, cfg: dict) -> dict:
    return {"cls": _classes(rng, p["n_ops"], p["read_fraction"])}


def poisson_stream(rng, p: dict, cfg: dict) -> dict:
    n = p["n_requests"]
    arrival = _arrivals(rng, n, p["mean_interarrival_us"])
    return {"arrival_us": arrival,
            "op_cls": _classes(rng, n, p["read_fraction"])}


def logical_pages(cfg: dict) -> int:
    f = cfg["ftl"]
    return int(f["blocks"] * f["pages_per_block"]
               / (1.0 + f["overprovision"]))


def overwrite_stream(rng, p: dict, cfg: dict) -> dict:
    n = p["n_requests"]
    return {"arrival_us": _arrivals(rng, n, p.get("mean_interarrival_us",
                                                  0.0)),
            "op_cls": _classes(rng, n, p.get("read_fraction", 0.0)),
            "lpn": rng.integers(0, logical_pages(cfg), n)}


GENERATORS = {"mixed_trace": mixed_trace, "poisson_stream": poisson_stream,
              "overwrite_stream": overwrite_stream}


def query_input(traffic: dict, cfg: dict, seed: int, index: int) -> dict:
    """Input of query ``index`` of a run seeded ``seed``."""
    return generator(traffic["generator"])(rng_for(seed, index), traffic,
                                           cfg)


def generator(name: str):
    """The generator of that name: built in, else a file of its own."""
    if name in GENERATORS:
        return GENERATORS[name]
    return loader.load("generators", name).generate
