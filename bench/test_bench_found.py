"""A cell made only of new files runs through the harness.

Its query kind (with a plain reference of its own beside it), its
generator, its configuration and its per-layer metric exist only as
files under a temporary benchmark directory that ``loader`` is pointed
at, beside a ``BENCHMARK.json`` of their own.  ``run.run`` finds each by
name, drives a traced run on the CPU with the look for a chip skipped,
and reads it correct.
"""

from __future__ import annotations

import json
import sys
import textwrap
from pathlib import Path

import jax
import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import generate  # noqa: E402
import loader  # noqa: E402
import run  # noqa: E402
import system  # noqa: E402
from repro.launch import compile_cache  # noqa: E402

SPEC = {
    "configs": [{"name": "demo_drive", "file": "bench/configs/demo_drive.json",
                 "reduced": []}],
    "workloads": [{"name": "demo_cell", "config": "demo_drive",
                   "traffic": "demo_mix", "chips": 1}],
    "end_to_end": [{"name": "sim_ops_per_s", "unit": "ops/s"},
                   {"name": "setup_s", "unit": "s"}],
    "per_layer": [{"name": "demo_spans_per_query", "unit": "count",
                   "workloads": ["demo_cell"]}],
}

FILES = {
    "traffic/demo_mix.json": json.dumps({
        "generator": "demo_writes", "query": "demo_one_point",
        "n_ops": 512, "write_fraction": 0.4, "ways": 4,
        "warmup_queries": 1, "check_queries": 2}),
    "limits/demo_cell.json": json.dumps({"end_rel_gap": 0.01}),
    "generators/demo_writes.py": '''
        import numpy as np


        def generate(rng, p, cfg):
            n = p["n_ops"]
            return {"cls": (rng.random(n) < p["write_fraction"]).astype(
                np.int32)}
        ''',
    "kinds/demo_reference.py": '''
        import reference


        def end_us(cfg, ways, cls, dtype):
            """The drive's end time, by the benchmark's event loop."""
            tab = reference.stacked_tables(cfg, [("proposed", "slc", ways)])
            place = reference.stripe(len(cls), cfg["channels"], ways)
            return reference.simulate(tab, cls, *place, cfg["channels"],
                                      ways, dtype=dtype)
        ''',
    "kinds/demo_one_point.py": '''
        import numpy as np

        import demo_reference
        import reference


        class Kind:
            def __init__(self, cfg, traffic):
                from repro.api import (CellType, InterfaceKind, SSDConfig,
                                       Simulator)
                self.cfg, self.ways = cfg, traffic["ways"]
                self.sim = Simulator.for_config(SSDConfig(
                    interface=InterfaceKind.PROPOSED, cell=CellType.SLC,
                    channels=cfg["channels"], ways=self.ways,
                    policy=cfg["issue_policy"]))

            def call(self, inp):
                from repro.api import OpTrace
                ch, n = self.cfg["channels"], len(inp["cls"])
                chan, way, par = reference.stripe(n, ch, self.ways)
                trace = OpTrace(cls=inp["cls"], channel=chan, way=way,
                                parity=par, channels=ch, ways=self.ways)
                return {"end_us": np.asarray(self.sim.sweep(None, trace))}

            def work(self, inp):
                return len(inp["cls"])

            def expect(self, inp, dtype=np.float64):
                return {"end_us": demo_reference.end_us(
                    self.cfg, self.ways, inp["cls"], dtype)}

            @staticmethod
            def readings(got, want):
                gap = np.abs(got["end_us"] - want["end_us"]) / want["end_us"]
                return {"end_rel_gap": float(np.max(gap))}
        ''',
    "metrics/demo_spans_per_query.py": '''
        def read(r):
            if not r.queries or not r.program_spans:
                return None
            return len(r.program_spans) / r.queries
        ''',
}


@pytest.fixture
def new_bench(tmp_path, monkeypatch):
    """A benchmark directory holding only the new cell's files, with the
    loader pointed at it."""
    bench = tmp_path / "bench"
    for rel, text in FILES.items():
        path = bench / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(text).lstrip())
    (bench / "configs").mkdir()
    (bench / "configs" / "demo_drive.json").write_text(
        (BENCH / "configs" / "paper_ddr_4x16.json").read_text())
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(SPEC))
    monkeypatch.setattr(loader, "BENCH", bench)
    monkeypatch.setattr(sys, "path", list(sys.path))
    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: None)
    return bench


def test_new_files_are_found_by_name(new_bench):
    kind = run.kind_class("demo_one_point")
    assert Path(sys.modules[kind.__module__].__file__).parent == \
        new_bench / "kinds"
    assert run.kind_class("sweep") is system.Sweep
    gen = generate.generator("demo_writes")
    assert gen.__module__ == "bench_generators_demo_writes"
    assert generate.generator("mixed_trace") is generate.mixed_trace
    assert run.load_metric("demo_spans_per_query").__module__ == \
        "bench_metrics_demo_spans_per_query"
    with pytest.raises(FileNotFoundError, match="demo_missing"):
        run.kind_class("demo_missing")


def test_cell_of_new_files_runs_and_is_correct(new_bench):
    res = run.run("demo_cell", 2**31 + 4242, 0.3, True,
                  chips_of=lambda n: jax.devices())
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["checks"]["end_rel_gap"]["value"] < 1e-4
    # the metric found by name read the program's own spans
    assert res["metrics"]["demo_spans_per_query"]["value"] > 0
