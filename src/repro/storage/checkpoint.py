"""Fault-tolerant sharded checkpointing with the paper's I/O principles.

Layout:
    <dir>/step_<N>/MANIFEST.json                 tree structure + meta
    <dir>/step_<N>/ch<k>/<leaf>__c<j>.npy        chunked leaf data

The writer applies the paper's three levers directly:

* **channel striping** — leaf chunks round-robin across ``channels``
  writer threads (independent files ≈ independent NAND channels);
* **way interleaving** — each channel keeps ``ways`` outstanding chunk
  buffers so serialization (host compute ≈ t_PROG) overlaps the write
  of other chunks — the paper's latency-*hiding* lever;
* **DDR pacing** — the whole save runs on a background thread
  (double-buffered against training compute), and the projected stall
  on a production SSD tier is priced by the paper's bandwidth/energy
  model (``repro.storage.ssd_model``), enabling checkpoint-interval
  planning (stall budget = bytes / modeled BW).

Restore is **elastic**: arrays are loaded host-side and re-placed with
``jax.device_put`` against whatever mesh/sharding the *new* job uses —
mesh-shape changes are pure respecification (tested 8→4→8 devices).
Data-pipeline state rides in the manifest for deterministic resume.
"""

from __future__ import annotations

import concurrent.futures as cf
import dataclasses
import json
import pathlib
import re
import shutil
import threading
import time
from typing import Any

import jax
import ml_dtypes
import numpy as np

from repro.core.sched import lower_static
from repro.core.sim import SSDConfig
from repro.core.workload import checkpoint_requests
from repro.storage.ssd_model import estimate_trace_interfaces

CHUNK_BYTES = 16 << 20


def _flatten(tree: Any) -> dict[str, np.ndarray]:
    out = {}

    def visit(key_path, leaf):
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in key_path)
        out[path] = leaf
        return leaf

    jax.tree_util.tree_map_with_path(visit, tree)
    return out


def _safe(name: str) -> str:
    return re.sub(r"[^\w\.]", "_", name)


def _replace_dir(src: pathlib.Path, dst: pathlib.Path) -> None:
    """Give ``src`` the name ``dst``.  An existing ``dst`` moves aside
    first and goes only once ``src`` holds the name (or comes back if
    the rename fails), so a step is never seen half-written."""
    old = dst.with_name(dst.name + ".old")
    shutil.rmtree(old, ignore_errors=True)
    if dst.exists():
        dst.rename(old)
    try:
        src.rename(dst)
    except BaseException:
        if old.exists():
            old.rename(dst)
        raise
    shutil.rmtree(old, ignore_errors=True)


@dataclasses.dataclass
class SaveResult:
    step: int
    nbytes: int
    wall_s: float
    modeled: dict[str, float]    # interface -> projected SSD write seconds


class CheckpointEngine:
    def __init__(self, directory: str | pathlib.Path, *, channels: int = 4,
                 ways: int = 4, ssd: SSDConfig | None = None,
                 keep: int = 2):
        self.dir = pathlib.Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.channels = channels
        self.ways = ways
        self.ssd = ssd or SSDConfig()
        self.keep = keep
        self._pending: threading.Thread | None = None
        self._last: SaveResult | None = None
        self._error: Exception | None = None

    # -- save ---------------------------------------------------------------

    def save(self, step: int, state: Any, *, extra: dict | None = None,
             blocking: bool = False) -> None:
        """Snapshot to host memory synchronously, write asynchronously.
        A failure of the previous save raises here (see :meth:`wait`)."""
        host = {k: np.asarray(v) for k, v in _flatten(state).items()}
        self.wait()
        t = threading.Thread(target=self._write, args=(step, host, extra or {}),
                             daemon=True)
        self._pending = t
        t.start()
        if blocking:
            self.wait()

    def _write(self, step: int, host: dict[str, np.ndarray], extra: dict):
        """The save thread: write the step under a temporary name, then
        give it the step's name.  A failure is kept for :meth:`wait`."""
        out = self.dir / f"step_{step:08d}.tmp"
        try:
            t0 = time.time()
            shutil.rmtree(out, ignore_errors=True)   # a failed save's leftovers
            nbytes = self._write_chunks(out, step, host, extra)
            _replace_dir(out, self.dir / f"step_{step:08d}")
            wall = time.time() - t0
            # the save is a request-level workload (a zero-arrival write
            # burst: the writer queues every chunk at once), lowered by
            # the static stripe scheduler onto the tier's geometry and
            # priced on the joint multi-channel simulation; the placement
            # depends only on cell/geometry, not on the interface kind,
            # so one per-interface fan-out through the cached Simulator
            # sessions prices all three
            requests = checkpoint_requests(nbytes, self.ssd)
            tr = lower_static(requests, self.ssd.channels, self.ssd.ways).trace
            modeled = {kind: est.seconds for kind, est in
                       estimate_trace_interfaces(tr, self.ssd,
                                                 total_bytes=nbytes).items()}
            self._last = SaveResult(step, nbytes, wall, modeled)
            self._gc()
        except Exception as e:   # raised by wait(), in the caller's thread
            shutil.rmtree(out, ignore_errors=True)
            self._error = e

    def _write_chunks(self, out: pathlib.Path, step: int,
                      host: dict[str, np.ndarray], extra: dict) -> int:
        """Write one step's chunks and manifest under ``out``; returns
        the bytes written."""
        out.mkdir(parents=True)
        chunks: list[tuple[pathlib.Path, np.ndarray]] = []
        manifest: dict[str, Any] = {"step": step, "extra": extra, "leaves": {}}
        for path, arr in host.items():
            flat = arr.reshape(-1)
            n_chunks = max(1, -(-arr.nbytes // CHUNK_BYTES))
            per = -(-flat.size // n_chunks)
            manifest["leaves"][path] = {
                "shape": list(arr.shape), "dtype": str(arr.dtype),
                "chunks": n_chunks}
            for j in range(n_chunks):
                ch = (len(chunks)) % self.channels   # channel striping
                d = out / f"ch{ch}"
                d.mkdir(exist_ok=True)
                chunks.append((d / f"{_safe(path)}__c{j}.npy",
                               flat[j * per:(j + 1) * per]))
        # ways = outstanding buffers per channel writer
        with cf.ThreadPoolExecutor(max_workers=self.channels * self.ways) as ex:
            list(ex.map(lambda fc: np.save(fc[0], fc[1]), chunks))
        (out / "MANIFEST.json").write_text(json.dumps(manifest))
        return sum(int(c.nbytes) for _, c in chunks)

    def _gc(self):
        steps = sorted(self.dir.glob("step_????????"))
        for old in steps[:-self.keep]:
            shutil.rmtree(old)

    def wait(self) -> SaveResult | None:
        """Wait for the save in flight; raise what made it fail."""
        if self._pending is not None:
            self._pending.join()
            self._pending = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err
        return self._last

    # -- restore (elastic) ----------------------------------------------------

    def latest_step(self) -> int | None:
        steps = sorted(self.dir.glob("step_????????"))
        return int(steps[-1].name.split("_")[1]) if steps else None

    def restore(self, step: int | None = None,
                template: Any = None) -> tuple[int, Any, dict]:
        """Returns (step, host-side state pytree, extra).

        ``template`` (any pytree with the same structure, e.g. from
        ``jax.eval_shape``) rebuilds the tree; pass None to get the flat
        {path: array} dict.
        """
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {self.dir}")
        src = self.dir / f"step_{step:08d}"
        manifest = json.loads((src / "MANIFEST.json").read_text())
        flat: dict[str, np.ndarray] = {}
        idx = 0
        for path, meta in manifest["leaves"].items():
            parts = []
            for j in range(meta["chunks"]):
                ch = idx % self.channels
                f = src / f"ch{ch}" / f"{_safe(path)}__c{j}.npy"
                if not f.exists():   # channel count may differ across jobs
                    hits = list(src.glob(f"ch*/{_safe(path)}__c{j}.npy"))
                    f = hits[0]
                parts.append(np.load(f))
                idx += 1
            arr = np.concatenate(parts) if len(parts) > 1 else parts[0]
            if str(arr.dtype) != meta["dtype"]:
                # np.load returns raw-void views for ml_dtypes types (bf16...)
                arr = arr.view(np.dtype(getattr(ml_dtypes, meta["dtype"])))
            flat[path] = arr.reshape(meta["shape"])
        if template is None:
            return step, flat, manifest["extra"]
        ref = _flatten(template)
        leaves_order = list(ref.keys())
        rebuilt = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(template),
            [flat[k] for k in leaves_order])
        return step, rebuilt, manifest["extra"]


def place_on_mesh(host_state: Any, shardings: Any) -> Any:
    """Elastic re-placement: works for any mesh shape/sharding (ZeRO/TP/...)."""
    return jax.tree.map(jax.device_put, host_state, shardings)
