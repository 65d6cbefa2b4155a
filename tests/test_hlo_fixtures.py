"""Regression: HLO collective parsing against checked-in text in two
spellings, so the roofline's data source can't silently drift when JAX
changes its HLO spelling.  The texts test the parser, not the JAX that
wrote them.

* ``hlo_legacy_0437.txt`` — captured from jax 0.4.37 / jaxlib 0.4.36 (a
  fully-manual shard_map program): synchronous collectives, explicit
  ``replica_groups={{...}}`` lists, f32.
* ``hlo_current.txt`` — the explicit-sharding generation's spelling
  (partial-manual path): async ``-start``/``-done`` pairs (whose result
  is a (operand, result) tuple), iota ``replica_groups=[n,m]<=[k]``
  (with and without a ``T(...)`` transpose), bf16, and a scan lowered to
  a ``while`` carrying ``known_trip_count`` in its backend_config.

The expected numbers are hand-derived from the shapes in the fixtures;
see the inline arithmetic.
"""
import os

import pytest

from repro.analysis.hlo_costs import analyze, parse_hlo
from repro.analysis.roofline import (collective_bytes_from_hlo,
                                     weighted_collective_bytes)

FIXDIR = os.path.join(os.path.dirname(__file__), "fixtures")


def _read(name):
    with open(os.path.join(FIXDIR, name)) as f:
        return f.read()


def test_legacy_0437_collective_bytes():
    """f32 program: ppermute f32[4,8] (128 B), all-reduce f32[4,8]
    (128 B), all-gather f32[32,8] (1024 B)."""
    by_kind = collective_bytes_from_hlo(_read("hlo_legacy_0437.txt"))
    assert by_kind["collective-permute"] == 4 * 8 * 4
    assert by_kind["all-reduce"] == 4 * 8 * 4
    assert by_kind["all-gather"] == 32 * 8 * 4
    assert by_kind["reduce-scatter"] == 0
    assert by_kind["all-to-all"] == 0
    # ring all-reduce weighted 2x
    assert weighted_collective_bytes(by_kind) == 2 * 128 + 128 + 1024


def test_legacy_0437_static_analysis():
    """Three f32 dots: [4,16]@[16,8], [4,8]@[16,8]^T, [4,16]@[16,8] —
    1024 FLOPs each; no while loops on this snippet."""
    res = analyze(_read("hlo_legacy_0437.txt"))
    assert res["flops"] == pytest.approx(3 * 2 * 4 * 8 * 16)
    assert res["n_while"] == 0
    assert res["coll_by_kind"]["collective-permute"] == 128.0
    assert res["coll_bytes"] == 2 * 128 + 128 + 1024


def test_current_collective_bytes():
    """bf16 + async spelling: the -start result tuple carries operand AND
    result buffers (64*64 + 128*64 halves = 24576 B all-gather); the
    -done lines must NOT be double-counted; ppermute/all-reduce
    bf16[8,64] = 1024 B each.  This parser is trip-count-unaware by
    design (it feeds the quick per-kind breakdown, not the roofline)."""
    by_kind = collective_bytes_from_hlo(_read("hlo_current.txt"))
    assert by_kind["all-gather"] == (64 * 64 + 128 * 64) * 2
    assert by_kind["collective-permute"] == 8 * 64 * 2
    assert by_kind["all-reduce"] == 8 * 64 * 2
    assert weighted_collective_bytes(by_kind) == 2 * 1024 + 24576 + 1024


def test_current_static_analysis_trip_counts():
    """The while's backend_config known_trip_count (9) multiplies the
    scan-body dot FLOPs and the in-loop ppermute bytes; entry-level
    collectives stay x1."""
    res = analyze(_read("hlo_current.txt"))
    assert res["n_while"] == 1
    assert res["flops"] == pytest.approx(9 * 2 * 8 * 64 * 64)
    assert res["coll_by_kind"]["collective-permute"] == 9 * 1024.0
    assert res["coll_by_kind"]["all-gather"] == 24576.0
    assert res["coll_by_kind"]["all-reduce"] == 1024.0
    assert res["coll_bytes"] == 2 * 1024 + 24576 + 9 * 1024


def test_current_fixture_parses_all_computations():
    comps = parse_hlo(_read("hlo_current.txt"))
    # entry first, then the add region, while cond + body
    names = list(comps)
    assert names[0].startswith("main")
    assert any("while_body" in n for n in names)
    assert any("while_cond" in n for n in names)


def test_iota_replica_groups_cross_pod_detection():
    """The iota form [2,2]<=[4] groups {0,1},{2,3}: crosses a pod
    boundary at pod_size=2, not at pod_size=4."""
    res2 = analyze(_read("hlo_current.txt"), pod_size=2)
    res4 = analyze(_read("hlo_current.txt"), pod_size=4)
    assert res2["coll_dcn_bytes"] > 0
    # at pod_size=4 all four devices share one pod -> nothing crosses
    assert res4["coll_dcn_bytes"] == 0


def test_tpu_tiled_layout_tuple_parses():
    """TPU layouts spell tiles inside the layout braces
    (``{3,1,2,0:T(8,128)(4,1)S(1)}``), so an async ``-start`` tuple type
    nests parentheses one level deep; the instruction must still parse,
    or the hop it carries vanishes from the audit."""
    layout = "{3,1,2,0:T(8,128)(4,1)S(1)}"
    text = (
        "ENTRY %main (p: s8[1,16,10,256]) -> s8[1,16,10,256] {\n"
        f"  %p = s8[1,16,10,256]{layout} parameter(0)\n"
        f"  %cp-start = (s8[1,16,10,256]{layout}, s8[1,16,10,256]{layout}, "
        "u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%p), "
        "channel_id=1, source_target_pairs={{1,0},{2,1},{3,2}}\n"
        f"  ROOT %cp-done = s8[1,16,10,256]{layout} "
        "collective-permute-done(%cp-start)\n"
        "}\n")
    ops = [ins.opcode for ins in parse_hlo(text)["main"]]
    assert ops == ["parameter", "collective-permute-start",
                   "collective-permute-done"]
    assert analyze(text)["coll_by_kind"]["collective-permute"] > 0
