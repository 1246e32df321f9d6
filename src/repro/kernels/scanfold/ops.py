"""Public ops: the scan engine's masked folds as one Pallas kernel.

``scan_fold_end_time`` and ``scan_fold_completions`` take the arguments
of ``repro.core.sim.trace_end_time_masked`` /
``trace_completions_masked`` with the validity mask replaced by the op
count ``n_ops`` (the mask of a padded bucket is ``arange(T) < n_ops``),
and return the same answers bit for bit.  ``repro.core.api.ScanEngine``
calls them on a TPU; elsewhere it keeps the ``lax.scan`` folds, which
XLA:CPU runs fast.  ``interpret=None`` interprets exactly off-TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.sim import MAX_WAYS
from repro.kernels.maxplus.kernel import resolve_interpret
from repro.kernels.scanfold.kernel import pack_ops, scan_fold


def _fold(table, cls, channel, way, parity, arrival_us, extra_us, n_ops,
          n_channels, batched, completions, interpret):
    table = jnp.stack([jnp.asarray(col, jnp.float32) for col in table])
    state, comp = scan_fold(
        table, pack_ops(cls, channel, way, parity, table.shape[1]),
        arrival_us.astype(jnp.float32), extra_us.astype(jnp.float32),
        jnp.asarray(n_ops, jnp.int32), n_channels=n_channels,
        batched=batched, completions=completions,
        interpret=resolve_interpret(interpret))
    # max(max bus_free, max chip_free): the leading C * (1 + MAX_WAYS)
    return jnp.max(state[: n_channels * (1 + MAX_WAYS)]), comp


@functools.partial(jax.jit, static_argnames=("n_channels", "batched",
                                             "interpret"))
def scan_fold_end_time(cmd_us, pre_us, slot_us, post_lo_us, post_hi_us,
                       ctrl_us, arb_us, cls, channel, way, parity,
                       arrival_us, extra_us, n_ops, *, n_channels: int,
                       batched: bool, interpret: bool | None = None):
    """End time (us) of the first ``n_ops`` ops of a padded trace."""
    return _fold((cmd_us, pre_us, slot_us, post_lo_us, post_hi_us, ctrl_us,
                  arb_us), cls, channel, way, parity, arrival_us, extra_us,
                 n_ops, n_channels, batched, False, interpret)[0]


@functools.partial(jax.jit, static_argnames=("n_channels", "batched",
                                             "interpret"))
def scan_fold_completions(cmd_us, pre_us, slot_us, post_lo_us, post_hi_us,
                          ctrl_us, arb_us, cls, channel, way, parity,
                          arrival_us, extra_us, n_ops, *, n_channels: int,
                          batched: bool, interpret: bool | None = None):
    """(end_us, [T] per-op completions) of the first ``n_ops`` ops of a
    padded trace; entries past ``n_ops`` are garbage."""
    return _fold((cmd_us, pre_us, slot_us, post_lo_us, post_hi_us, ctrl_us,
                  arb_us), cls, channel, way, parity, arrival_us, extra_us,
                 n_ops, n_channels, batched, True, interpret)
