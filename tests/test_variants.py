"""The kernel's tiles are one function of the dispatch shape (PR 43).

``ops.pallas_extract.resolve_variant(kc, b, qb, a)`` is the only thing
that picks ``(tile_q, ne, tile_n, fold)``: ``tuned_variant`` by list
width, then alignment, the row width's data block and the VMEM bound,
then ``fold_slabs`` of that block (PR 47: the lane vectors a bucket of
the two-level selection folds, so that the folded array keeps ten; the
same PR re-measured ``tuned_variant``'s wide-list row with the pass in:
tile_q 128 at every list width, not 64 past 64 slots). The table
below is that function written out at the shapes the benchmark's
configurations dispatch and at the rule's edges, the expected tiles
taken as literals from PR 42's tree with no tune-cache file (PR 47's
for ``fold`` and for ``tile_q`` past 64 slots). A tile
retuned for one width shows here as the row that changed, before it
shows in another cell's ``qps``.
"""

from __future__ import annotations

import pytest

from dmlp_tpu.ops.pallas_distance import _tile
from dmlp_tpu.ops.pallas_extract import (_TN, resolve_variant, supports,
                                         variant_supports)
from dmlp_tpu.serve.engine import _kernel_statics

#: (qb, b, a, kc) -> (tile_q, ne, tile_n, fold)
TABLE = {
    # what the cells dispatch: chunks of 51 200 rows, on one chip and on
    # a mesh shard alike
    "bigann-4m.bulk": ((1024, 51200, 128, 32), (128, 2, 12800, 10)),
    "bigann-4m.steady128": ((128, 51200, 128, 32), (128, 2, 12800, 10)),
    "bigann-4m.steady256": ((256, 51200, 128, 32), (128, 2, 12800, 10)),
    "bigann-4m.steady512": ((512, 51200, 128, 32), (128, 2, 12800, 10)),
    "gist-1m.bulk": ((1024, 51200, 1024, 40), (128, 2, 6400, 5)),
    "gist-1m.q256": ((256, 51200, 1024, 40), (128, 2, 6400, 5)),
    "gist-1m.unpadded960": ((1024, 51200, 960, 32), (128, 2, 6400, 5)),
    # bigann-10m, and msturing-10m at its staged 128 lanes
    "bigann-10m.bulk": ((1024, 51200, 128, 120), (128, 4, 12800, 10)),
    "msturing-10m.unpadded100": ((1024, 51200, 100, 120),
                                 (128, 4, 12800, 10)),
    # text2image-10m at its staged 256 lanes (PR 46), and its retry
    "text2image-10m.bulk": ((1024, 51200, 256, 120), (128, 4, 12800, 10)),
    "text2image-10m.unpadded200": ((1024, 51200, 200, 120),
                                   (128, 4, 12800, 10)),
    "text2image-10m.retry": ((16, 51200, 256, 512), (128, 4, 12800, 10)),
    "bigann-gt1000.pass": ((1024, 51200, 128, 512), (128, 4, 12800, 10)),
    "bigann-gt1000.sweep": ((1024, 82 * 51200, 128, 512),
                            (128, 4, 12800, 10)),
    "retry.q16": ((16, 51200, 128, 512), (128, 4, 12800, 10)),
    # tuned_variant's edge: the list width picks (tile_q, ne)
    "kc8": ((8, 51200, 128, 8), (128, 2, 12800, 10)),
    "kc64": ((1024, 51200, 128, 64), (128, 2, 12800, 10)),
    "kc72": ((1024, 51200, 128, 72), (128, 4, 12800, 10)),
    "kc128": ((1024, 51200, 128, 128), (128, 4, 12800, 10)),
    "kc136": ((1024, 51200, 128, 136), (128, 4, 12800, 10)),
    "kc256": ((1024, 51200, 128, 256), (128, 4, 12800, 10)),
    "batch.config4": ((10112, 51200, 64, 144), (128, 4, 12800, 10)),
    # the row width picks the data block
    "a64": ((128, 51200, 64, 32), (128, 2, 12800, 10)),
    "a512": ((1024, 51200, 512, 32), (128, 2, 12800, 10)),
    "a640": ((1024, 51200, 640, 32), (128, 2, 10240, 8)),
    "a2048": ((1024, 51200, 2048, 32), (128, 2, 2560, 2)),
    "a2048.kc120": ((1024, 51200, 2048, 120), (128, 4, 2560, 2)),
    "a4096": ((1024, 51200, 4096, 32), (128, 2, 1280, 2)),
    # rows that 512-row sub-blocks cannot tile: back to the default
    "b768.kc120": ((64, 768, 64, 120), (128, 2, 12800, 2)),
    "b1280.kc120": ((64, 1280, 64, 120), (128, 2, 12800, 2)),
    # one block a chunk, and the smallest shapes
    "b12800": ((1024, 12800, 128, 32), (128, 2, 12800, 10)),
    "b12800.toy16": ((128, 12800, 16, 32), (128, 2, 12800, 10)),
    "b65536": ((256, 65536, 128, 40), (128, 2, 12800, 4)),
    "tiny": ((8, 256, 8, 8), (128, 2, 12800, 2)),
}


@pytest.mark.parametrize("shape,tiles", TABLE.values(), ids=TABLE.keys())
def test_resolve_variant_table(shape, tiles):
    qb, b, a, kc = shape
    v = resolve_variant(kc, b, qb, a)
    assert (v["tile_q"], v["ne"], v.get("tile_n", _TN), v["fold"]) == tiles
    assert v["unroll"] == 1
    # the key is there only where the width shortened the block
    assert ("tile_n" in v) == (tiles[2] != _TN)
    # the fold pass's lane vectors divide the block the tiles make of
    # THIS dispatch (a short b tiles by less than tile_n)
    if tiles[3]:
        tn = _tile(b, tiles[2], 128 * tiles[1])
        assert tn % (128 * tiles[3]) == 0
        # ... and the folded array keeps ten of them where the block
        # has twenty or more
        assert tn // (128 * tiles[3]) >= min(10, tn // 256)
    assert variant_supports(qb, b, a, kc, v) and supports(qb, b, a, kc)
    # a pure function: the same dict again, and no state to carry
    assert resolve_variant(kc, b, qb, a) == v
    # both kernel forms key their jit on the same tiles: the MXU gate
    # is the one static that tells them apart
    fused = _kernel_statics("fused", kc, b, qb, a, "f32", False)
    assert (fused["tile_q"], fused["ne"], fused["tile_n"],
            fused["fold"]) == tiles
    assert fused["mxu_gate"] is True
    assert _kernel_statics("extract", kc, b, qb, a, "f32", False) == {
        **fused, "mxu_gate": False}
    # the score keys the jit beside them and picks no tile
    assert fused["score"] == "l2"
    assert _kernel_statics("fused", kc, b, qb, a, "f32", False, "ip") == {
        **fused, "score": "ip"}
