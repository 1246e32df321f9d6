"""KV-cache SSD-offload planning for long-context decode (paper tie-in).

For the 500k-token decode shape the KV/recurrent state may exceed HBM;
a production serving tier pages cold KV blocks to local SSD.  Whether
that is *feasible* is exactly the paper's question: per decoded token
the tier must stream ``bytes_per_token`` back under the latency budget,
so the sustained read bandwidth of the SSD interface bounds tokens/s.
This module sizes the state per architecture (a :class:`KVShape`, the
published attention shape that sets the cache's bytes), emits the
decode loop's actual **op trace** — a cold-KV read burst plus a small
KV-append write burst per token, striped over the tier's channels — and
prices it on the joint multi-channel simulation (CONV / SYNC_ONLY /
PROPOSED): the DDR interface (PROPOSED) roughly doubles the feasible
paging rate at equal pin count (paper Table 3 read rows), and the mixed
read/write contention of the append stream is now simulated rather than
ignored.

For attention-free architectures (xLSTM) the recurrent state is O(1)
per layer, and a windowed-only one (RecurrentGemma) keeps an
O(window) ring: neither grows with the sequence, so neither needs
paging: ``plan.applicable = False`` (DESIGN.md §Arch-applicability).
"""

from __future__ import annotations

import dataclasses

from repro.core.nand import CellType
from repro.core.sched import lower_static
from repro.core.sim import SSDConfig
from repro.core.trace import OpTrace
from repro.core.workload import RequestStream, kvoffload_requests
from repro.storage.ssd_model import estimate_trace_interfaces


@dataclasses.dataclass(frozen=True)
class KVShape:
    """What one sequence's KV cache grows by per token: a bf16 key and
    value of ``n_kv_heads x head_dim`` in each of ``full_attn_layers``
    (the non-windowed attention layers; windowed and recurrent layers
    hold state that does not grow with the sequence)."""

    name: str
    full_attn_layers: int
    n_kv_heads: int
    head_dim: int


#: The published shapes the storage tier plans for, by id.
KV_SHAPES: dict[str, KVShape] = {s.name: s for s in (
    # arXiv:2407.10671 / hf:Qwen/Qwen2-0.5B
    KVShape("qwen2-0.5b", 24, 2, 64),
    # arXiv:2404.06395 / hf:openbmb/MiniCPM-2B
    KVShape("minicpm-2b", 40, 36, 64),
    # hf:ibm-granite/granite-3.0-2b-base
    KVShape("granite-3-2b", 40, 8, 64),
    # arXiv:2402.19173 / hf:bigcode/starcoder2-3b
    KVShape("starcoder2-3b", 30, 2, 128),
    # hf:meta-llama/Llama-4-Scout-17B-16E (family); assignment sheet
    KVShape("llama4-maverick-400b-a17b", 48, 8, 128),
    # hf:ibm-granite/granite-3.0-1b-a400m-base (family); assignment sheet
    KVShape("granite-moe-3b-a800m", 32, 8, 64),
    # arXiv:2306.05284 / hf:facebook/musicgen-medium
    KVShape("musicgen-medium", 48, 24, 64),
    # arXiv:2402.19427 / hf:google/recurrentgemma-9b: its 12 attention
    # layers are local (window 2048), the other 26 RG-LRU
    KVShape("recurrentgemma-9b", 0, 1, 256),
    # arXiv:2409.12191 / hf:Qwen/Qwen2-VL-2B
    KVShape("qwen2-vl-2b", 28, 2, 128),
    # arXiv:2405.04517 (xLSTM[7:1] 350M class): attention-free
    KVShape("xlstm-350m", 0, 4, 256),
)}


@dataclasses.dataclass(frozen=True)
class KVOffloadPlan:
    applicable: bool
    state_bytes_per_seq: int          # total cached state for one sequence
    hot_bytes_per_seq: int            # must stay in HBM (windows, recur state)
    cold_bytes_per_seq: int           # pageable to SSD
    read_mb_per_token: float          # SSD traffic per decoded token
    tokens_per_s: dict[str, float]    # interface -> sustainable decode rate
    trace: OpTrace | None = None      # per-token op trace (window)
    requests: RequestStream | None = None   # placement-free workload window
    note: str = ""


def kv_bytes_per_token(shape: KVShape) -> tuple[int, int]:
    """(hot, cold) cache bytes added per token for one sequence.  Hot
    is 0: no state that stays in HBM grows with the sequence."""
    dtype_bytes = 2  # bf16 cache
    return 0, 2 * shape.n_kv_heads * shape.head_dim * dtype_bytes \
        * shape.full_attn_layers


def plan_kv_offload(shape: KVShape, seq_len: int, *,
                    latency_budget_ms: float = 50.0,
                    channels: int = 4, ways: int = 8,
                    cell: CellType = CellType.MLC) -> KVOffloadPlan:
    hot_rate, cold_rate = kv_bytes_per_token(shape)
    if cold_rate == 0:
        return KVOffloadPlan(
            applicable=False, state_bytes_per_seq=0, hot_bytes_per_seq=0,
            cold_bytes_per_seq=0, read_mb_per_token=0.0, tokens_per_s={},
            note=f"{shape.name}: attention-free / windowed-only — state is "
                 f"O(1)/O(window) per layer; KV offload inapplicable.")
    cold_total = cold_rate * seq_len
    # decode touches the whole cold KV once per token (full-attention read)
    # and appends one token's KV — a mixed read/write trace per token
    read_mb = cold_total / 1e6
    per_token_mb = (cold_total + cold_rate) / 1e6   # read burst + KV append
    # the decode loop is a request-level workload (read burst + append
    # writes per token); the stripe lowering depends only on
    # geometry/cell, not on the interface kind, so one fan-out through
    # the cached Simulator sessions prices the mixed window's sustained
    # rate under all three interfaces
    base = SSDConfig(cell=cell, channels=channels, ways=ways)
    requests = kvoffload_requests(cold_total, base, n_tokens=2,
                                  append_bytes_per_token=cold_rate)
    trace = lower_static(requests, base.channels, base.ways).trace
    rates = {kind: est.bandwidth_mb_s / per_token_mb
             for kind, est in estimate_trace_interfaces(trace, base).items()}
    return KVOffloadPlan(
        applicable=True,
        state_bytes_per_seq=cold_total,
        hot_bytes_per_seq=hot_rate * seq_len,
        cold_bytes_per_seq=cold_total,
        read_mb_per_token=read_mb,
        tokens_per_s=rates,
        trace=trace,
        requests=requests,
        note=f"{shape.name}: full-attention KV {cold_total/2**30:.1f} GiB/seq at "
             f"S={seq_len}; PROPOSED sustains "
             f"{rates['proposed']:.2f} tok/s vs CONV {rates['conv']:.2f}.")
