"""``sharded_sweep`` on four devices: it shards, reads correct, and the
control and the fault "exchange between chips left out" read not
correct.

One subprocess with a forced four-device host platform (JAX's device
count is fixed when it starts) drives three whole runs of the cell
through ``run.run`` at a small trace, with the look for a chip skipped
and the committed limits: unbroken; with each sharded call's answer
taken from the first device's shard alone (every point of the call
gets the first point's end time, as if the results were never gathered
from the other chips); and with the control, the plain reference in
bfloat16, in the program's place.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent

CODE = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, {bench!r})
    import jax
    import jax.numpy as jnp
    import ml_dtypes
    import run
    from repro.core import api
    from repro.launch import compile_cache

    compile_cache.use_compile_cache = lambda: None
    load_cell = run.load_cell

    def small(name):
        c = load_cell(name)
        c["traffic"] = {{**c["traffic"], "n_ops": 1024, "warmup_queries": 1,
                        "check_queries": 2}}
        return c
    run.load_cell = small

    def go():
        res = run.run("sharded_sweep", 2**31 + 77, 0.3, False,
                      chips_of=lambda n: jax.devices()[:n])
        return {{"correct": res["correct"], "failed": res["failed"],
                "checks": res["checks"]}}

    out = {{"devices": len(jax.devices())}}
    out["unbroken"] = go()
    mesh = api._points_mesh()
    out["mesh"] = None if mesh is None else int(mesh.devices.size)

    sharded = api._sharded_batch_fn

    def first_shard_only(*a, **k):
        fn = sharded(*a, **k)

        def call(*args):
            end = fn(*args)
            return jnp.broadcast_to(end[:1], end.shape)
        return call
    api._sharded_batch_fn = first_shard_only
    out["exchange_left_out"] = go()
    api._sharded_batch_fn = sharded

    run.kind_class("sweep").call = lambda self, inp: self.expect(
        inp, dtype=ml_dtypes.bfloat16)
    out["control"] = go()
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def four_device_runs():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(BENCH.parent / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", CODE.format(bench=str(BENCH))],
                       capture_output=True, text=True, env=env,
                       cwd=str(BENCH.parent), timeout=900)
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    assert lines, r.stdout[-4000:] + r.stderr[-4000:]
    return json.loads(lines[-1][len("RESULT "):])


def test_sweep_shards_over_four_devices(four_device_runs):
    assert four_device_runs["devices"] == 4
    assert four_device_runs["mesh"] == 4


def test_unbroken_sharded_run_is_correct(four_device_runs):
    res = four_device_runs["unbroken"]
    assert res["correct"] and res["failed"] == 0, res["checks"]


def test_exchange_between_chips_left_out_is_not_correct(four_device_runs):
    res = four_device_runs["exchange_left_out"]
    assert not res["correct"], res["checks"]


def test_sharded_control_is_not_correct(four_device_runs):
    res = four_device_runs["control"]
    assert not res["correct"], res["checks"]
