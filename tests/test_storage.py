"""Checkpoint engine, data pipeline, SSD pricing and KV-offload planning."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.interface import InterfaceKind
from repro.core.sim import SSDConfig
from repro.storage.checkpoint import CheckpointEngine
from repro.storage.datapipe import (FileBackedTokens, StripedTokenStore,
                                    SyntheticTokens, pipeline_io_trace)
from repro.storage.kvoffload import KV_SHAPES, kv_bytes_per_token, plan_kv_offload
from repro.storage.ssd_model import compare_interfaces, estimate_io, plan_geometry


def _state():
    k = jax.random.PRNGKey(0)
    return {"params": {"w": jax.random.normal(k, (64, 32)),
                       "b": jnp.zeros((32,), jnp.bfloat16)},
            "opt": {"count": jnp.ones((), jnp.int32)}}


def test_checkpoint_roundtrip(tmp_path):
    eng = CheckpointEngine(tmp_path, channels=3, ways=2)
    state = _state()
    eng.save(10, state, extra={"pipe_cursor": 7}, blocking=True)
    step, restored, extra = eng.restore(template=state)
    assert step == 10 and extra["pipe_cursor"] == 7
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        assert np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
        assert np.asarray(a).dtype == np.asarray(b).dtype


def test_checkpoint_gc_and_latest(tmp_path):
    eng = CheckpointEngine(tmp_path, keep=2)
    st = _state()
    for step in (1, 2, 3):
        eng.save(step, st, blocking=True)
    assert eng.latest_step() == 3
    steps = sorted(p.name for p in tmp_path.glob("step_*"))
    assert len(steps) == 2  # keep=2


def test_checkpoint_resave_replaces_step(tmp_path):
    eng = CheckpointEngine(tmp_path)
    first = {"w": jnp.zeros((8, 4))}
    second = {"w": jnp.arange(32, dtype=jnp.float32).reshape(8, 4)}
    eng.save(4, first, extra={"v": 1}, blocking=True)
    eng.save(4, second, extra={"v": 2}, blocking=True)
    step, restored, extra = eng.restore(template=second)
    assert step == 4 and extra == {"v": 2}
    np.testing.assert_array_equal(restored["w"], np.asarray(second["w"]))
    assert sorted(p.name for p in tmp_path.iterdir()) == ["step_00000004"]


def test_checkpoint_failed_write_raises(tmp_path, monkeypatch):
    """A write that fails in the save thread raises from ``wait()``, or
    else from the next ``save()``, and leaves no partial step behind."""
    eng = CheckpointEngine(tmp_path)

    def full_disk(*args, **kwargs):
        raise OSError(28, "No space left on device")

    with monkeypatch.context() as m:
        m.setattr(np, "save", full_disk)
        eng.save(1, _state())
        with pytest.raises(OSError, match="No space"):
            eng.wait()
        eng.save(2, _state())
        with pytest.raises(OSError, match="No space"):
            eng.save(3, _state(), blocking=True)
    assert list(tmp_path.iterdir()) == []
    eng.save(3, _state(), blocking=True)        # the error is not sticky
    assert eng.latest_step() == 3


def test_elastic_reshard_subprocess():
    """Checkpoint on a (4,2) mesh, restore onto (2,2) and (8,1) — elastic."""
    code = textwrap.dedent("""
        import os, tempfile
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.storage.checkpoint import CheckpointEngine, place_on_mesh

        state = {"w": jnp.arange(512, dtype=jnp.float32).reshape(16, 32)}
        d = tempfile.mkdtemp()
        m1 = jax.make_mesh((4, 2), ("data", "model"))
        sharded = jax.device_put(state["w"], NamedSharding(m1, P("data", "model")))
        eng = CheckpointEngine(d, channels=2)
        eng.save(1, {"w": sharded}, blocking=True)

        for shape in ((2, 2), (8, 1)):   # elastic: fewer / rearranged devices
            m2 = jax.make_mesh(shape, ("data", "model"))
            step, host, _ = eng.restore(template={"w": state["w"]})
            placed = place_on_mesh(host, {"w": NamedSharding(m2, P("data", "model"))})
            np.testing.assert_array_equal(np.asarray(placed["w"]),
                                          np.asarray(state["w"]))
        print("ELASTIC_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "ELASTIC_OK" in r.stdout, r.stdout + r.stderr


def test_checkpoint_modeled_ssd_stall(tmp_path):
    eng = CheckpointEngine(tmp_path)
    eng.save(1, _state(), blocking=True)
    res = eng.wait()
    assert res.nbytes > 0
    # DDR interface strictly reduces the projected stall (paper's headline)
    assert res.modeled["proposed"] < res.modeled["sync_only"] <= res.modeled["conv"]


def test_synthetic_pipeline_deterministic_resume():
    a = SyntheticTokens(1000, batch=2, seq=8, seed=1)
    it = iter(a)
    for _ in range(5):
        next(it)                    # advance past the first five batches
    st = a.state()
    more = [next(it) for _ in range(2)]
    b = SyntheticTokens(1000, batch=2, seq=8, seed=1)
    b.restore(st)
    it2 = iter(b)
    for expected in more:
        got = next(it2)
        assert np.array_equal(expected["inputs"], got["inputs"])


def test_file_backed_pipeline(tmp_path):
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 5000, 40_000, dtype=np.int32)
    store = StripedTokenStore.write(tmp_path, tokens, channels=4)
    pipe = FileBackedTokens(store, batch=4, seq=16, ways=2)
    it = iter(pipe)
    b1 = next(it)
    assert b1["inputs"].shape == (4, 16)
    assert np.array_equal(b1["inputs"][:, 1:], b1["labels"][:, :-1])
    pipe.close()


def test_ssd_model_ordering_and_planning():
    ests = compare_interfaces(10 << 30, "write", channels=2, ways=8)
    assert ests["proposed"].seconds < ests["sync_only"].seconds \
        < ests["conv"].seconds
    plan = plan_geometry(10 << 30, budget_s=120.0, mode="write")
    assert plan is not None and plan.seconds <= 120.0
    # impossible budget -> None
    assert plan_geometry(10 << 40, budget_s=0.1, mode="write") is None


def test_estimate_energy_scales_with_bytes():
    cfg = SSDConfig(interface=InterfaceKind.PROPOSED, channels=2, ways=8)
    e1 = estimate_io(1 << 30, cfg, "read")
    e2 = estimate_io(2 << 30, cfg, "read")
    assert e2.energy_joules == pytest.approx(2 * e1.energy_joules, rel=1e-6)
    assert e2.seconds == pytest.approx(2 * e1.seconds, rel=1e-6)


def test_pipeline_emits_priceable_trace(tmp_path):
    """The datapipe's access pattern is an SSD op trace the cost model
    can price directly (reads only; synthetic pipes do no I/O)."""
    from repro.storage.ssd_model import estimate_trace
    rng = np.random.default_rng(0)
    store = StripedTokenStore.write(
        tmp_path, rng.integers(0, 5000, 40_000, dtype=np.int32), channels=2)
    pipe = FileBackedTokens(store, batch=4, seq=16, ways=2)
    it = iter(pipe)
    next(it)
    pipe.close()
    tr = pipeline_io_trace(pipe, n_batches=64)
    assert tr is not None and tr.channels == 2
    est = estimate_trace(tr, SSDConfig(channels=2, ways=2),
                         total_bytes=64 * 4 * 17 * 4)
    assert est.seconds > 0 and est.write_bytes == 0 and est.read_bytes > 0
    assert pipeline_io_trace(SyntheticTokens(10, 1, 8), 4) is None


def test_kv_offload_planning():
    qwen = plan_kv_offload(KV_SHAPES["qwen2-0.5b"], 524288)
    assert qwen.applicable
    assert qwen.tokens_per_s["proposed"] > 1.5 * qwen.tokens_per_s["conv"]
    xl = plan_kv_offload(KV_SHAPES["xlstm-350m"], 524288)
    assert not xl.applicable                      # attention-free
    rg = plan_kv_offload(KV_SHAPES["recurrentgemma-9b"], 524288)
    assert not rg.applicable                      # windowed-only attention


#: (hot, cold) KV bytes a token adds, as the published model configs
#: gave them: 2 (K and V) x kv heads x head dim x 2 bytes (bf16) x full
#: attention layers.
KV_BYTES_PER_TOKEN = {
    "qwen2-0.5b": (0, 12288),
    "minicpm-2b": (0, 368640),
    "granite-3-2b": (0, 81920),
    "starcoder2-3b": (0, 30720),
    "llama4-maverick-400b-a17b": (0, 196608),
    "granite-moe-3b-a800m": (0, 65536),
    "musicgen-medium": (0, 294912),
    "recurrentgemma-9b": (0, 0),
    "qwen2-vl-2b": (0, 28672),
    "xlstm-350m": (0, 0),
}


@pytest.mark.parametrize("shape_id", sorted(KV_BYTES_PER_TOKEN))
def test_kv_bytes_per_token_pinned(shape_id):
    assert KV_SHAPES[shape_id].name == shape_id
    assert kv_bytes_per_token(KV_SHAPES[shape_id]) == \
        KV_BYTES_PER_TOKEN[shape_id]
