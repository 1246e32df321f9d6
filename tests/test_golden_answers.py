"""Golden answers: the simulator's absolute results, pinned bit-equal.

Engine-agreement tests compare engines with each other and with the
oracle, so a drift of the shared recurrence would move them together.
These cases pin what the simulator answers, recorded once in
``tests/data/golden_answers.json``: a change that claims only speed
must leave every one of them bit-identical (ROADMAP north star 1).

* Design space: every point of the paper's 4-channel family (CONV or
  PROPOSED, SLC or MLC, 1-16 ways) under each issue policy, on a
  fixed-seed 1,024-op 70%-read mixed trace placed over that point's
  ways: ``Simulator.run``'s end time (the scan engine) and the point's
  lane of the prefix ``sweep`` over that number of ways' four tables.
* Aged drive: a small uniform overwrite burst and a small JESD219A
  enterprise burst on a preconditioned 256-block drive, translated by
  the host translator and by the scan translator, lowered by the
  static stripe and folded on the FTL op table: the FTL counters and
  the end time.
"""

import dataclasses
import functools
import json
from pathlib import Path

import numpy as np
import pytest

import repro.api as api
from repro.core import ftl, ftl_scan
from repro.core.trace import mixed_trace

GOLDEN = Path(__file__).parent / "data" / "golden_answers.json"

CHANNELS, N_OPS, READ_FRACTION, SEED = 4, 1024, 0.7, 2015
INTERFACES = ("conv", "proposed")
CELLS = ("slc", "mlc")
WAYS = tuple(range(1, 17))
POLICIES = ("eager", "batched")
DESIGN_CASES = [(policy, iface, cell, ways) for policy in POLICIES
                for iface in INTERFACES for cell in CELLS for ways in WAYS]

AGED_DRIVE = api.SSDConfig(interface=api.InterfaceKind.PROPOSED,
                           cell=api.CellType.SLC, channels=4, ways=16)
AGED_SPEC = ftl.FTLSpec(blocks=256, pages_per_block=64, overprovision=0.12,
                        precondition=True)
PAGE_BYTES = 2048
SECTORS_PER_PAGE = PAGE_BYTES // 512
TRANSLATORS = {"host": ftl.translate, "scan": ftl_scan.translate_scan}
STREAMS = ("overwrite", "jesd219")
AGED_CASES = [(stream, translator) for stream in STREAMS
              for translator in TRANSLATORS]
COUNTERS = ("host_pages_written", "total_pages_written", "gc_pages_moved",
            "gc_reads", "gc_writes", "erases", "rmw_reads",
            "host_bytes_written", "free_page_low_watermark")


def _config(iface: str, cell: str, ways: int, policy: str) -> api.SSDConfig:
    return api.SSDConfig(interface=api.InterfaceKind(iface),
                         cell=api.CellType(cell), channels=CHANNELS,
                         ways=ways, policy=policy)


@functools.cache
def design_answers(ways: int, policy: str) -> dict[str, dict[str, float]]:
    """{"<iface>/<cell>": {"run_end_us", "sweep_end_us"}} at ``ways``
    under ``policy``: one trace, one sweep over the four tables."""
    trace = mixed_trace(N_OPS, CHANNELS, ways, READ_FRACTION, seed=SEED)
    points = [(iface, cell) for iface in INTERFACES for cell in CELLS]
    configs = [_config(iface, cell, ways, policy) for iface, cell in points]
    sims = [api.Simulator.for_config(c) for c in configs]
    swept = np.asarray(sims[0].sweep([s.table for s in sims], trace,
                                     policy=policy, engine="prefix"))
    return {f"{iface}/{cell}": {"run_end_us": float(sim.run(trace).end_us),
                                "sweep_end_us": float(swept[i])}
            for i, ((iface, cell), sim) in enumerate(zip(points, sims))}


def _stream(name: str) -> api.RequestStream:
    if name == "overwrite":
        return api.overwrite_stream(2048, AGED_SPEC.logical_pages, seed=7)
    return api.jesd219_stream(512, AGED_SPEC.logical_pages,
                              SECTORS_PER_PAGE, seed=7)


@functools.cache
def _ftl_session() -> api.Simulator:
    return api.Simulator(AGED_DRIVE,
                         table=api.ftl_op_class_table(AGED_DRIVE, AGED_SPEC))


def aged_answer(stream: str, translator: str) -> dict:
    """FTL counters, WAF, op count and end time of one aged burst."""
    spec = AGED_SPEC
    if stream == "jesd219":
        spec = dataclasses.replace(spec, sectors_per_page=SECTORS_PER_PAGE)
    tr = TRANSLATORS[translator](_stream(stream), spec,
                                 page_bytes=PAGE_BYTES)
    trace = api.lower_ops(tr.op_cls, tr.arrival_us, AGED_DRIVE.channels,
                          AGED_DRIVE.ways, "stripe", payload=tr.payload)
    stats = dataclasses.asdict(tr.stats)
    return {"counters": {k: int(stats[k]) for k in COUNTERS},
            "waf": float(tr.stats.waf),
            "gc_op_count": int(tr.stats.gc_op_count),
            "n_ops": int(tr.n_ops),
            "end_us": float(_ftl_session().run(trace).end_us)}


def record() -> dict:
    """Every answer the tests pin, as ``golden_answers.json`` holds it."""
    return {
        "design": {f"{policy}/{ways}": design_answers(ways, policy)
                   for policy in POLICIES for ways in WAYS},
        "aged": {f"{stream}/{translator}": aged_answer(stream, translator)
                 for stream, translator in AGED_CASES}}


@functools.cache
def _golden() -> dict:
    return json.loads(GOLDEN.read_text())


@pytest.mark.parametrize(
    "policy,iface,cell,ways", DESIGN_CASES,
    ids=[f"{p}-{i}-{c}-{w}w" for p, i, c, w in DESIGN_CASES])
def test_design_point_end_times_pinned(policy, iface, cell, ways):
    got = design_answers(ways, policy)[f"{iface}/{cell}"]
    assert got == _golden()["design"][f"{policy}/{ways}"][f"{iface}/{cell}"]


@pytest.mark.parametrize("stream,translator", AGED_CASES,
                         ids=[f"{s}-{t}" for s, t in AGED_CASES])
def test_aged_drive_answers_pinned(stream, translator):
    got = aged_answer(stream, translator)
    assert got["counters"]["gc_writes"] > 0          # GC ran in the window
    assert got == _golden()["aged"][f"{stream}/{translator}"]
