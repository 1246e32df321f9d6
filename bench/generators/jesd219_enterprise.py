"""JEDEC JESD219A's enterprise endurance workload, drawn by the
benchmark itself (independent of the program's ``jesd219_stream``).

Parameters: ``n_requests`` and ``read_fraction`` (0: the workload is
all writes) from the traffic file; the transfer-size table
(``transfer_sizes_kib``: [KiB, share]), the alignment (``align_kib``),
the access skew (``access_skew``: [share of the LBA space, share of
accesses], regions from LBA 0), the sector (``sector_bytes``) and the
drive from the configuration.  A transfer of ``align_kib`` or more
starts on that boundary, a smaller one at any sector; an access is
uniform within its region; a transfer that would pass the end of the
LBA space starts early enough to fit.  All arrive at t = 0.

Input: ``arrival_us``, ``op_cls``, ``start_sector`` (the first sector's
LBA) and ``n_sectors``, each [n_requests].
"""

from __future__ import annotations

import numpy as np

from generate import WRITE, logical_pages


def lba_sectors(cfg: dict) -> int:
    """Sectors of the drive's user LBA space."""
    page = cfg["cell"][cfg["cells"][0]]["page_data_bytes"]
    return logical_pages(cfg) * (page // cfg["sector_bytes"])


def generate(rng, p: dict, cfg: dict) -> dict:
    if p.get("read_fraction", 0.0) != 0.0:
        raise ValueError("JESD219A's enterprise workload is all writes")
    n = p["n_requests"]
    kib, share = np.asarray(cfg["transfer_sizes_kib"], np.float64).T
    sectors = np.round(kib * 1024 / cfg["sector_bytes"]).astype(np.int64)
    align = int(round(cfg["align_kib"] * 1024 / cfg["sector_bytes"]))
    lba = lba_sectors(cfg)
    size = sectors[np.minimum(
        np.searchsorted(np.cumsum(share) / share.sum(), rng.random(n),
                        side="right"), len(share) - 1)]
    part, hits = np.asarray(cfg["access_skew"], np.float64).T
    bounds = np.round(np.concatenate([[0.0], np.cumsum(part)])
                      / part.sum() * lba).astype(np.int64)
    region = np.minimum(
        np.searchsorted(np.cumsum(hits) / hits.sum(), rng.random(n),
                        side="right"), len(hits) - 1)
    unit = np.where(size >= align, align, 1)
    lo = (bounds[region] + unit - 1) // unit
    hi = np.maximum(bounds[region + 1] // unit, lo + 1)
    start = rng.integers(lo, hi) * unit
    start = np.minimum(start, (lba - size) // unit * unit)
    return {"arrival_us": np.zeros(n, np.float32),
            "op_cls": np.full(n, WRITE, np.int32),
            "start_sector": start, "n_sectors": size.astype(np.int32)}
