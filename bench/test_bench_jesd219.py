"""The JESD219A enterprise cell (``jesd219_enterprise``), on the CPU at
a small drive: its kind, reference and generator, all files found by
name, run end to end through ``run.run`` and read correct; the control
and the faults that matter to sector addressing read not correct; and
the generator draws JESD219A's tables.
"""

from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import jax
import ml_dtypes
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import generate  # noqa: E402
import loader  # noqa: E402
import program_spans as ps  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import trace_reduce as tr  # noqa: E402
from repro.core import ftl_scan  # noqa: E402
from repro.launch import compile_cache  # noqa: E402

CELL = "jesd219_enterprise"
SMALL_TRAFFIC = {"n_requests": 512, "warmup_queries": 1, "check_queries": 2}
SMALL_DRIVE = {"blocks": 64}
MS = 1_000_000


@pytest.fixture
def small_run(monkeypatch):
    """``run.run`` of the cell at a small size on the CPU."""
    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: None)
    orig = run.load_cell

    def load(name):
        c = orig(name)
        c["traffic"] = {**c["traffic"], **SMALL_TRAFFIC}
        c["config"]["ftl"] = {**c["config"]["ftl"], **SMALL_DRIVE}
        return c

    monkeypatch.setattr(run, "load_cell", load)

    def go(trace=False):
        return run.run(CELL, 2**31 + 77, 0.3, trace,
                       chips_of=lambda n: jax.devices())
    return go


def kind_class():
    return run.kind_class(run.load_cell(CELL)["traffic"]["query"])


def test_cell_runs_and_is_correct(small_run):
    res = small_run(trace=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["checks"]["count_mismatch"]["value"] == 0.0
    # the new counter, read from the program's own span
    assert 0.0 < res["metrics"]["rmw_read_pct"]["value"] < 10.0
    assert res["metrics"]["ftl_machine_runs_per_query"]["value"] >= 2


def _control(monkeypatch):
    monkeypatch.setattr(kind_class(), "call", lambda self, inp: self.expect(
        inp, dtype=ml_dtypes.bfloat16))


def _no_rmw_reads(monkeypatch):
    monkeypatch.setattr(ftl_scan, "rmw_mask",
                        lambda cls, *a: np.zeros(len(cls), bool))


def _offset_ignored(monkeypatch):
    """Each request counted from its page's first sector: the offset is
    lost, so a request's pages are counted from its size alone."""
    kind = kind_class()
    orig = kind.stream

    def stream(self, inp):
        s = orig(self, inp)
        n = np.asarray(s.n_sectors, np.int64)
        return dataclasses.replace(
            s, first_sector=np.zeros_like(s.first_sector),
            n_pages=(-(-n // self.spp)).astype(np.int32))
    monkeypatch.setattr(kind, "stream", stream)


@pytest.mark.parametrize("fault", [_control, _no_rmw_reads,
                                   _offset_ignored],
                         ids=["bfloat16_control", "rmw_reads_left_out",
                              "sector_offset_ignored"])
def test_fault_is_not_correct(small_run, monkeypatch, fault):
    fault(monkeypatch)
    res = small_run()
    assert not res["correct"], res["checks"]


def test_generator_draws_the_standards_tables():
    cfg = run.load_cell(CELL)["config"]
    n = 131072
    inp = generate.generator("jesd219_enterprise")(
        generate.rng_for(2**31 + 5, 0), {"n_requests": n}, cfg)
    start, size = inp["start_sector"], inp["n_sectors"]
    lba = loader.load("generators", "jesd219_enterprise").lba_sectors(cfg)
    assert (start >= 0).all() and (start + size <= lba).all()
    assert (inp["op_cls"] == generate.WRITE).all()

    def within_sampling_error(hits, share):
        sigma = np.sqrt(share * (1 - share) / n)
        assert abs(np.mean(hits) - share) < 5 * sigma, (np.mean(hits), share)

    for kib, share in cfg["transfer_sizes_kib"]:
        within_sampling_error(size == round(kib * 2), share)
    aligned = size >= 8
    assert (start[aligned] % 8 == 0).all()
    assert (start[~aligned] % 8 != 0).any()
    edge = 0.0
    for part, share in cfg["access_skew"]:
        lo, edge = edge, edge + part
        within_sampling_error((start >= round(lo * lba))
                              & (start < round(edge * lba)), share)


def test_fast_float64_loop_is_the_reference_recurrence():
    """The reference's float64 loop gives ``reference.simulate``'s
    answer bit for bit."""
    ref = loader.load("kinds", "jesd219_ref")
    cfg = run.load_cell(CELL)["config"]
    tab = reference.stacked_tables(cfg, [("proposed", "slc", 16)])
    rng = np.random.default_rng(2**31 + 3)
    cls = rng.integers(2, 7, 3000)
    arrival = np.sort(rng.random(3000) * 5e3)
    end, done = ref.simulate_one(tab, cls, 4, 16, arrival)
    want_end, want_done = reference.simulate(
        tab, cls, *reference.stripe(3000, 4, 16), 4, 16, arrival=arrival,
        completions=True)
    assert end == want_end[0]
    assert np.array_equal(done, want_done[0])


def test_rmw_read_pct_reads_the_translate_counters():
    """``rmw_read_pct`` is 100 x RMW reads over host page ops, summed
    over the query's translation spans; preconditioning spans and
    spans of a program without the counter count nothing."""
    def reduced(host):
        return tr.Reduced(programs={}, spans=[], window=(0, 40 * MS),
                          program_spans=ps.from_events(host, (0, 40 * MS)),
                          queries=2)

    sectored, paged = [], []
    for q, (rmw, ops) in enumerate([(30, 1000), (10, 1000)]):
        t0 = q * 20 * MS
        pre = ("ssd.precondition", t0 + MS, 2 * MS,
               {"writes": 512, "machine_runs": 1})
        sectored += [("ssd.translate", t0, 5 * MS,
                      {"host_ops": ops, "out_ops": 3000, "machine_runs": 2,
                       "rmw_reads": rmw, "partial_pages": rmw + 5,
                       "host_sectors": 4 * ops}), pre]
        paged += [("ssd.translate", t0, 5 * MS,
                   {"host_ops": ops, "out_ops": 3000, "machine_runs": 2}),
                  pre]
    read = run.load_metric("rmw_read_pct")
    assert read(reduced(sectored)) == pytest.approx(2.0)
    assert read(reduced(paged)) is None
