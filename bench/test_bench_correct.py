"""``correct`` fails where it should: the control and the faults.

Each test drives a whole run of a cell through ``run.run`` -- set-up,
warm-up, the closed-loop window and the check against the plain
reference -- on the CPU at a small size, with the look for a chip
skipped.  Unbroken, every cell reads correct.  With the control (the
plain reference in bfloat16, one precision below the float32 that the
configurations state) put in the program's place, or with the timed
path broken underneath, it must read not correct.  The limits are the
committed ones (``bench/limits/``).
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
import run  # noqa: E402
import system  # noqa: E402
from repro import api  # noqa: E402
from repro.launch import compile_cache  # noqa: E402

#: Small sizes the CPU runs in seconds; the faults and the control must
#: show at these sizes too.
SMALL = {
    "design_sweep": {"n_ops": 4096},
    "loaded_latency": {"n_requests": 4096},
    "aged_overwrite": {"n_requests": 512},
}
SMALL_DRIVE = {"blocks": 64}


@pytest.fixture
def small_run(monkeypatch):
    """``run.run`` at a small size on the CPU, leaving JAX's persistent
    cache as the test process had it."""
    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: None)
    orig = run.load_cell

    def load(name):
        c = orig(name)
        c["traffic"] = {**c["traffic"], **SMALL[name], "warmup_queries": 1,
                        "check_queries": 3}
        if "ftl" in c["config"]:
            c["config"]["ftl"] = {**c["config"]["ftl"], **SMALL_DRIVE}
        return c

    monkeypatch.setattr(run, "load_cell", load)

    def go(name):
        return run.run(name, 2**31 + 99, 0.3, False,
                       chips_of=lambda n: jax.devices())
    return go


def patch_answer(monkeypatch, method, change):
    """Break ``Simulator.<method>`` underneath the harness: ``change(
    args, kwargs, call)`` returns the broken answer, ``call`` being the
    unbroken method."""
    orig = getattr(api.Simulator, method)

    def broken(self, *args, **kw):
        return change(args, kw, lambda *a, **k: orig(self, *a, **k))
    monkeypatch.setattr(api.Simulator, method, broken)


def _altered(args, kw, call):
    out = call(*args, **kw)
    if isinstance(out, api.SimResult):
        lat = np.array(out.request_lat_us, copy=True)
        lat[len(lat) // 2] += 0.05 * out.end_us
        stats = dataclasses.replace(out.ftl_stats,
                                    erases=out.ftl_stats.erases + 1) \
            if out.ftl_stats is not None else None
        return dataclasses.replace(out, request_lat_us=lat,
                                   ftl_stats=stats)
    out = np.array(out, copy=True)
    out[len(out) // 2] *= 1.05
    return out


def _first_half(x):
    """An OpTrace or RequestStream cut to its first half."""
    n = len(x.cls if isinstance(x, api.OpTrace) else x.op_cls)
    return dataclasses.replace(x, **{
        f.name: getattr(x, f.name)[:n // 2]
        for f in dataclasses.fields(x)
        if isinstance(getattr(x, f.name), np.ndarray)
        and len(getattr(x, f.name)) == n})


def _half(args, kw, call):
    return call(*(_first_half(a) if isinstance(a, (api.OpTrace,
                                                    api.RequestStream))
                  else a for a in args), **kw)


def _stale():
    first = {}

    def change(args, kw, call):
        if "answer" not in first:
            first["answer"] = call(*args, **kw)
        return first["answer"]
    return change


def _one_placement(args, kw, call):
    """Every sweep placed over the most ways, whatever its tables'."""
    tables, trace = args
    ways = 16
    chan, way, par = generate.round_robin(trace.n_ops, trace.channels, ways)
    return call(tables, dataclasses.replace(
        trace, channel=chan, way=way, parity=par, ways=ways), **kw)


def _unaged(args, kw, call):
    kw = {**kw, "ftl": dataclasses.replace(kw["ftl"], precondition=False)}
    return call(*args, **kw)


FAULTS = {
    "altered": lambda: _altered,
    "half": lambda: _half,
    "stale": _stale,
}


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_unbroken_run_is_correct(small_run, cell):
    res = small_run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) >= {"setup_s", "sim_ops_per_s"}


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_control_is_not_correct(small_run, monkeypatch, cell):
    kind = run.kind_class(run.load_cell(cell)["traffic"]["query"])
    monkeypatch.setattr(kind, "call", lambda self, inp: self.expect(
        inp, dtype=ml_dtypes.bfloat16))
    res = small_run(cell)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell,method", [("design_sweep", "sweep"),
                                         ("loaded_latency", "run"),
                                         ("aged_overwrite", "run")])
def test_fault_is_not_correct(small_run, monkeypatch, cell, method, fault):
    patch_answer(monkeypatch, method, FAULTS[fault]())
    res = small_run(cell)
    assert not res["correct"], res["checks"]


def test_aged_drive_left_unaged_is_not_correct(small_run, monkeypatch):
    patch_answer(monkeypatch, "run", _unaged)
    res = small_run("aged_overwrite")
    assert not res["correct"], res["checks"]



def test_sweep_placed_over_one_number_of_ways_is_not_correct(small_run,
                                                            monkeypatch):
    patch_answer(monkeypatch, "sweep", _one_placement)
    res = small_run("design_sweep")
    assert not res["correct"], res["checks"]


def test_sweep_places_each_point_over_its_own_ways():
    """A drive of one way cannot overlap its chips' array times: at
    every interface and cell it takes more than twice as long as the
    same drive with 16 ways (a trace placed over 16 ways for both would
    give one end time)."""
    kind = system.Sweep(run.load_cell("design_sweep")["config"],
                        {"n_ops": 512})
    assert sorted(len(idx) for idx in kind.groups.values()) == [4] * 16
    end = kind.expect(generate.mixed_trace(generate.rng_for(3, 0),
                                           {"n_ops": 512,
                                            "read_fraction": 0.7}, {}))
    by_ways = np.asarray(end["end_us"]).reshape(4, 16)
    assert (by_ways[:, 0] > 2 * by_ways[:, -1]).all()
