"""Sector-addressed FTL streams (DESIGN.md §2.10-§2.11): a write that
covers a mapped page in part is a read-modify-write.  The scan
translator and the host oracle must agree op for op — classes,
arrivals, payloads, request ids, GC flags, stats, final drive state —
with each other and with the benchmark's plain reference
(``bench/kinds/jesd219_ref.py``, which imports nothing of the program),
on a fresh and on a preconditioned drive; and the page-granular
translation must stay bit-identical to what it was before sectors."""

import dataclasses
import hashlib
import sys
from pathlib import Path

import numpy as np
import pytest

import repro.api as api
from repro.core import ftl, ftl_scan
from repro.core.nand import CellType
from repro.core.sim import SSDConfig
from repro.core.workload import (JESD219_ENTERPRISE_SIZES_KIB,
                                 WORKLOAD_KINDS, RequestStream, WRITE,
                                 build_workload, iter_request_chunks,
                                 jesd219_stream, multi_tenant,
                                 overwrite_stream, request_partial)

BENCH = Path(__file__).resolve().parents[1] / "bench"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "kinds"))

import jesd219_ref  # noqa: E402
import reference  # noqa: E402

SPP = 4
SPEC = ftl.FTLSpec(blocks=64, pages_per_block=16, overprovision=0.25,
                   sectors_per_page=SPP)
PAGE_BYTES = 2048


def sector_stream(start, n_sectors, spp=SPP):
    """Writes at t = 0 from sector ``start`` (LBA) of ``n_sectors``."""
    start = np.asarray(start, np.int64)
    n = np.asarray(n_sectors, np.int64)
    first = start % spp
    r = len(start)
    return RequestStream(
        arrival_us=np.zeros(r, np.float32), op_cls=np.full(r, WRITE),
        n_pages=(-(-(first + n) // spp)).astype(np.int32),
        stream=np.zeros(r, np.int32), lpn=start // spp,
        first_sector=first.astype(np.int32),
        n_sectors=n.astype(np.int32))


def plain_drive(spec):
    """The benchmark reference's drive for ``spec``: fresh, or aged as
    ``spec`` preconditions (greedy GC)."""
    cfg = {"ftl": {"blocks": spec.blocks,
                   "pages_per_block": spec.pages_per_block,
                   "overprovision": spec.overprovision,
                   "gc_policy": spec.gc_policy,
                   "gc_free_blocks": spec.gc_free_blocks,
                   "precondition": spec.precondition,
                   "precondition_passes": spec.precondition_passes,
                   "precondition_seed": spec.seed}}
    return reference.aged_ftl(cfg)


def assert_same_translation(th, ts):
    for f in ("op_cls", "arrival_us", "payload", "request_id", "gc"):
        assert np.array_equal(getattr(th, f), getattr(ts, f)), f
    assert th.stats == ts.stats
    for f in ("l2p", "p2l", "valid_count", "full", "fill_seq",
              "erase_count"):
        assert np.array_equal(getattr(th.state, f), getattr(ts.state, f)), f
    assert list(th.state.free) == list(ts.state.free)
    assert (th.state.open_block, th.state.next_page, th.state._seq) == \
        (ts.state.open_block, ts.state.next_page, ts.state._seq)


def assert_matches_reference(tr, stream, spec):
    """The translation is the plain reference's, op for op."""
    drive = plain_drive(spec)
    start = np.asarray(stream.lpn, np.int64) * SPP + stream.first_sector
    cls, req, gc, rmw = jesd219_ref.translate(drive, start,
                                              stream.n_sectors, SPP)
    assert np.array_equal(tr.op_cls, cls)
    assert np.array_equal(tr.gc, gc)
    assert np.array_equal(tr.request_id[~gc], req[~gc])
    assert (tr.request_id[gc] == -1).all()
    assert np.array_equal(tr.payload, ~gc & ~rmw)
    s = tr.stats
    assert s.rmw_reads == int(rmw.sum())
    assert s.host_bytes_written == int(np.sum(stream.n_sectors)) * 512
    for k, v in drive.counts.items():
        assert getattr(s, k) == v, k
    assert np.array_equal(tr.state.l2p, drive.l2p)


def both(stream, spec, **kw):
    th = ftl.translate(stream, spec, page_bytes=PAGE_BYTES, **kw)
    ts = ftl_scan.translate_scan(stream, spec, page_bytes=PAGE_BYTES, **kw)
    assert_same_translation(th, ts)
    return ts


# (start sector, sectors) of each request, and the RMW reads it costs on
# a fresh drive: page p holds sectors 4p .. 4p+3
CASES = {
    # page 10's last 3 sectors, then page 11 whole
    "partial_head": ([4 * 10 + 1], [7], 0),
    # page 20 whole, then page 21's first 2 sectors
    "partial_tail": ([4 * 20], [6], 0),
    # sectors 1-2 of page 30: one page, partial on both sides
    "inside_one_page": ([4 * 30 + 1], [2], 0),
    # a partial write to an unmapped page reads nothing; the second
    # finds it mapped by the first
    "unmapped_then_mapped": ([4 * 5 + 2, 4 * 5], [1, 3], 1),
    # a whole 4 KiB write on its alignment: two whole pages, no read
    "aligned_4k": ([4 * 40], [8], 0),
    # a 64 KiB write starting mid-page: partial head and tail pages
    "long_unaligned": ([4 * 50 + 3], [128], 0),
}


@pytest.mark.parametrize("precondition", [False, True],
                         ids=["fresh", "preconditioned"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_partial_page_cases(case, precondition):
    starts, sizes, fresh_rmw = CASES[case]
    spec = dataclasses.replace(SPEC, precondition=precondition)
    stream = sector_stream(starts, sizes)
    tr = both(stream, spec)
    assert_matches_reference(tr, stream, spec)
    partial = request_partial(stream, SPP)
    # a preconditioned drive has every logical page mapped
    want = int(partial.sum()) if precondition else fresh_rmw
    assert tr.stats.rmw_reads == want
    reads = np.flatnonzero(tr.op_cls == ftl.FTL_READ)
    assert len(reads) == want
    # each RMW read comes right before its write, in its request
    assert (tr.op_cls[reads + 1] == ftl.FTL_WRITE).all()
    assert np.array_equal(tr.request_id[reads], tr.request_id[reads + 1])
    assert not tr.payload[reads].any() and not tr.gc[reads].any()


@pytest.mark.parametrize("precondition", [False, True],
                         ids=["fresh", "preconditioned"])
@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_jesd219_stream_with_gc_inside_rmw_bursts(precondition, seed):
    """A JESD219A stream long enough for GC: GC cycles fire in bursts
    that hold RMW pairs, and both translators are the reference's."""
    spec = dataclasses.replace(SPEC, precondition=precondition)
    stream = jesd219_stream(800, spec.logical_pages, SPP, seed=seed)
    tr = both(stream, spec)
    assert_matches_reference(tr, stream, spec)
    assert tr.stats.rmw_reads > 0 and tr.stats.gc_reads > 0
    # some write right after its RMW read fires a GC cycle
    rd = np.flatnonzero((tr.op_cls[:-2] == ftl.FTL_READ)
                        & ~tr.payload[:-2])
    assert np.isin(tr.op_cls[rd + 2], (ftl.GC_READ, ftl.ERASE)).any()


def test_chained_chunks_match_one_shot():
    """Chained translation of a sector stream, chunk by chunk, decides
    each RMW read on the drive the chunk starts from."""
    spec = dataclasses.replace(SPEC, precondition=True)
    stream = jesd219_stream(300, spec.logical_pages, SPP, seed=5)
    whole = ftl_scan.translate_scan(stream, spec, page_bytes=PAGE_BYTES)
    state, parts = None, []
    for chunk in iter_request_chunks(stream, 70):
        tr = ftl_scan.translate_scan(chunk, spec, state=state,
                                     page_bytes=PAGE_BYTES)
        state = tr.state
        parts.append(tr.op_cls)
    assert np.array_equal(np.concatenate(parts), whole.op_cls)
    assert state.stats == whole.stats


def _sim():
    return api.Simulator(SSDConfig(cell=CellType.SLC, channels=2, ways=4))


def test_run_reports_rmw_counters_and_request_latency():
    spec = dataclasses.replace(SPEC, precondition=True)
    stream = jesd219_stream(200, spec.logical_pages, SPP, seed=9)
    res = _sim().run(stream, ftl=spec)
    tr = ftl.translate(stream, spec, page_bytes=PAGE_BYTES)
    assert res.ftl_stats == tr.stats
    assert res.ftl_stats.rmw_reads > 0
    assert res.n_ops == tr.n_ops
    assert res.request_lat_us.shape == (200,)
    assert res.ftl_stats.waf_bytes(PAGE_BYTES) > res.ftl_stats.waf
    with pytest.raises(ValueError, match="page-addressed"):
        _sim().sweep(None, stream, ftl=[spec])


def test_sector_stream_validation():
    with pytest.raises(ValueError, match="together"):
        dataclasses.replace(sector_stream([0], [4]), n_sectors=None)
    bad = dataclasses.replace(sector_stream([1], [4]),
                              n_pages=np.ones(1, np.int32))
    with pytest.raises(ValueError, match="n_pages must equal"):
        request_partial(bad, SPP)
    with pytest.raises(ValueError, match="first_sector must be <"):
        request_partial(sector_stream([5], [2], spp=8), SPP)
    with pytest.raises(ValueError, match="sectors_per_page"):
        ftl.FTLSpec(sectors_per_page=0)
    # sectors at one a page: whole pages, no partial page, no read
    one = sector_stream([7, 9], [2, 1], spp=1)
    assert not request_partial(one, 1).any()


def test_jesd219_stream_follows_the_standard():
    n, logical = 131072, 50000
    st = jesd219_stream(n, logical, SPP, seed=2**31 + 1)
    start = st.lpn * SPP + st.first_sector
    assert (start + st.n_sectors <= logical * SPP).all()
    st.check_sectors(SPP)
    sizes = dict(zip(*np.unique(st.n_sectors, return_counts=True)))
    for kib, share in JESD219_ENTERPRISE_SIZES_KIB:
        got = sizes.get(int(kib * 2), 0) / n
        assert abs(got - share) < 5 * np.sqrt(share * (1 - share) / n)
    assert (start[st.n_sectors >= 8] % 8 == 0).all()
    # registered with the stream builders (lowered by stripe); 2 KiB SLC
    # pages hold 4 sectors
    cfg = SSDConfig(cell=CellType.SLC, channels=2, ways=4)
    assert "jesd219" in WORKLOAD_KINDS
    t = build_workload("jesd219", cfg, n_requests=128, logical_pages=1024,
                       seed=4)
    assert t.n_ops == jesd219_stream(128, 1024, 4, seed=4).total_pages


def test_merges_carry_sector_addresses():
    a, b = sector_stream([1, 9], [2, 3]), sector_stream([4], [8])
    m = multi_tenant([a, b])
    assert np.array_equal(m.n_sectors, [2, 3, 8])
    assert np.array_equal(m.first_sector, [1, 1, 0])
    with pytest.raises(ValueError, match="sector-addressed"):
        multi_tenant([a, overwrite_stream(2, 64)])


#: sha256 of the page-granular translation of a 2,048-write uniform
#: overwrite stream on a preconditioned 64 x 64-page drive, as it was
#: before sector addressing (ops, arrivals, payloads, request ids, GC
#: flags, final L2P/P2L and the stats that existed then), for the host
#: oracle and the scan translator alike.
PAGE_GRANULAR_DIGEST = \
    "a144afda7bb46f89749f9c73cb324937394beb4111aeba1bcd75c6ccab60e2cb"
OLD_STATS = ("host_pages_written", "total_pages_written", "gc_pages_moved",
             "gc_reads", "gc_writes", "erases", "prog_fails",
             "blocks_retired", "free_page_low_watermark", "max_erase_count",
             "mean_erase_count")


@pytest.mark.parametrize("translator", [ftl.translate,
                                        ftl_scan.translate_scan],
                         ids=["oracle", "scan"])
def test_page_granular_translation_is_pinned(translator):
    spec = ftl.FTLSpec(blocks=64, pages_per_block=64, overprovision=0.12,
                       precondition=True)
    tr = translator(overwrite_stream(2048, spec.logical_pages, seed=12),
                    spec)
    h = hashlib.sha256()
    for a in (tr.op_cls, tr.arrival_us, tr.payload, tr.request_id, tr.gc,
              tr.state.l2p, tr.state.p2l):
        h.update(np.ascontiguousarray(a).tobytes())
    h.update(repr(tuple(getattr(tr.stats, f) for f in OLD_STATS)).encode())
    assert h.hexdigest() == PAGE_GRANULAR_DIGEST
    assert tr.stats.rmw_reads == 0


def test_sector_draws_near_a_bucket_edge_share_compiled_folds():
    """Two sector streams whose op counts straddle a power of two fold
    in the same padded buckets: the second compiles nothing."""
    sim = _sim()
    spec = dataclasses.replace(SPEC, precondition=False)
    # whole-page 4 KiB writes to distinct pages: 2 page writes each, no
    # RMW read, no GC; 1,020 and 1,030 host ops around 1,024
    first = sector_stream(8 * np.arange(510), np.full(510, 8))
    second = sector_stream(8 * np.arange(515), np.full(515, 8))
    assert first.total_pages < 1024 < second.total_pages
    sim.run(first, ftl=spec)
    sess = sim._ftl_session(spec)
    misses = sess.cache_info().misses
    sim.run(second, ftl=spec)
    assert sess.cache_info().misses == misses
