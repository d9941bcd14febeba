"""The full coupled decode of a piggyback volume (ec/encoder
.rebuild_ec_files_piggyback), small and on the CPU, against the plain
reference that shares nothing with the program
(`benchmarks/lib/reference_piggyback.py`):

  (a) every loss the holder-loss deployment meets — each of the four
      holder sets of a 4+4+3+3 spread — and a lone parity shard, two data
      shards, data + parity: rebuilt bit-identical through
      PipelinedMatmul, from local files and through the store's streaming
      gather, on the one-device codec and on a 4-device CPU mesh;
  (b) such a loss is the full decode's own route: no `repair_fallback`
      in the reply, no `repair_fallbacks` counted, `lost` and the byte
      account of k whole shards in the reply;
  (c) a stripe that fails leaves no partial output behind, pipelined or
      not;
  (d) the stages of a stripe hang under the stream's root, each on the
      thread that does the work.
"""

import hashlib
import os
import shutil
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from lib import reference_piggyback  # noqa: E402

from seaweedfs_tpu.ec import encoder as ec_encoder  # noqa: E402
from seaweedfs_tpu.ec import to_ext, write_ec_files  # noqa: E402
from seaweedfs_tpu.ec.layout import write_layout_sidecars  # noqa: E402
from seaweedfs_tpu.ops import telemetry  # noqa: E402
from seaweedfs_tpu.ops.codec import NumpyCodec  # noqa: E402
from seaweedfs_tpu.util import tracing  # noqa: E402

# stripes of 256 sub-chunk columns: exact widths below the smallest width
# bucket, which other files' tiny geometries share (conftest.py)
pytestmark = pytest.mark.usefixtures("private_packed_programs")

K, M, PAIRS, ALPHA = 10, 4, 5, 32
# windows of 4 KiB (128-byte sub-chunks): one large-block row, small rows,
# a short tail; a shard is 20 windows, rebuilt in stripes of two
LB, SB = 1 << 16, 1 << 12
DAT_BYTES = K * LB + 3 * K * SB + 12_345
SHARD_BYTES = LB + 4 * SB
SLAB = 2 * SB
STRIPES = SHARD_BYTES // SLAB
LOSSES = {"holder-A": [0, 4, 8, 12], "holder-B": [1, 5, 9, 13],
          "holder-C": [2, 6, 10], "holder-D": [3, 7, 11],
          "lone-parity": [12], "two-data": [2, 7], "data+parity": [3, 11]}


def _shas(base) -> list:
    out = []
    for i in range(K + M):
        with open(base + to_ext(i), "rb") as f:
            out.append(hashlib.sha256(f.read()).hexdigest())
    return out


@pytest.fixture(scope="module")
def encoded(tmp_path_factory):
    """One piggyback volume with the sidecars `ec.encode` leaves, and the
    reference's shas of its 14 shards."""
    d = tmp_path_factory.mktemp("coupled")
    base = os.path.join(str(d), "1")
    rng = np.random.default_rng(32)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, DAT_BYTES, dtype=np.uint8).tobytes())
    want = reference_piggyback.shard_shas(base + ".dat", K, M,
                                          large_block=LB, small_block=SB)
    write_ec_files(base, codec=NumpyCodec(K, M), large_block=LB,
                   small_block=SB, slab=SLAB, layout="piggyback")
    os.remove(base + ".dat")
    open(base + ".ecx", "wb").close()
    write_layout_sidecars(base, "piggyback", window=SB, pairs=PAIRS,
                          version=3, offset_width=4)
    assert _shas(base) == want and \
        os.path.getsize(base + to_ext(0)) == SHARD_BYTES
    return str(d), want


def _volume_without(encoded, tmp_path, lost) -> str:
    src, _ = encoded
    for name in os.listdir(src):
        if name not in {"1" + to_ext(i) for i in lost}:
            shutil.copy(os.path.join(src, name), tmp_path / name)
    return str(tmp_path / "1")


def _codec(backend, stack):
    if backend == "tpu":
        from seaweedfs_tpu.ops.rs_tpu import TpuCodec
        return TpuCodec(K, M)
    from test_mesh_codec import _private_programs
    from seaweedfs_tpu.parallel.mesh import make_codec_mesh
    from seaweedfs_tpu.parallel.mesh_codec import MeshCodec
    stack.enter_context(_private_programs())
    return MeshCodec(K, M, mesh=make_codec_mesh(width_devices=4),
                     mesh_shard_min_bytes=0, small_dispatch_bytes=0)


# -- (a), (b) ----------------------------------------------------------------

@pytest.mark.parametrize("backend", ["tpu", "mesh"])
@pytest.mark.parametrize("how", ["local", "streaming"])
@pytest.mark.parametrize("lost", LOSSES.values(), ids=LOSSES.keys())
def test_pipelined_coupled_decode_gives_the_references_shards(
        encoded, tmp_path, monkeypatch, lost, how, backend):
    import contextlib
    from seaweedfs_tpu.storage.store import Store
    base = _volume_without(encoded, tmp_path, lost)
    with contextlib.ExitStack() as stack:
        codec = _codec(backend, stack)
        assert codec.pipelined

        def synchronous(*_):
            raise AssertionError("the coupled decode left the pipeline")

        monkeypatch.setattr(codec, "_matmul", synchronous)
        store = Store([str(tmp_path)], codec=codec)
        before = telemetry.STATS.snapshot()
        stats = {}
        if how == "local":
            rebuilt = ec_encoder.rebuild_ec_files(
                base, codec=codec, slab=SLAB, stats=stats,
                layout=store._volume_layout(base))
        else:
            rebuilt = store.rebuild_ec_shards_streaming(
                1, "", stats=stats, slab=SLAB)
        after = telemetry.STATS.snapshot()
    assert rebuilt == lost
    assert _shas(base) == encoded[1]            # bit-identical, all 14
    assert stats["operand"] == [ALPHA * len(lost), ALPHA * K]
    assert stats["lost"] == lost and stats["layout"] == "piggyback"
    assert stats["dispatches"] == STRIPES
    assert stats["rebuilt_bytes"] == len(lost) * SHARD_BYTES
    assert stats["repair_bytes"] == stats["repair_baseline_bytes"] == \
        stats["survivor_bytes"] == K * SHARD_BYTES
    # every reader filled its own row of every stripe's block
    assert stats["rows_in_place"] == K * STRIPES
    assert stats["rows_copied"] == 0
    assert set(stats["phases"]) == {"gather", "plan", "dispatch", "drain",
                                    "write"}
    assert after["coupled_decodes"] - before["coupled_decodes"] == 1
    assert after["repair_fallbacks"] == before["repair_fallbacks"]
    assert "repair_fallback" not in stats
    if how == "streaming":
        assert stats["repair_mode"] == "full"
        moved = {r: after["repair_route"][r] - before["repair_route"][r]
                 for r in after["repair_route"]}
        assert moved == {"piggyback": 0, "trace": 0, "full": 1}


@pytest.mark.parametrize("backend", ["numpy", "tpu"])
@pytest.mark.parametrize("slab,copied", [(SLAB, True), (SB, False)],
                         ids=["two-windows", "one-window"])
def test_a_block_goes_back_once_the_split_has_copied_it(
        encoded, tmp_path, monkeypatch, slab, copied, backend):
    """`pb_split` copies a stripe of several windows, and the gather's
    block goes back to the pool right after it: a second decode is
    gathered into the first's memory. Of a stripe one window wide the
    split is a view, and that block is never handed back under it."""
    from seaweedfs_tpu.ec import transport
    from seaweedfs_tpu.storage.store import Store
    lost = LOSSES["holder-A"]
    base = _volume_without(encoded, tmp_path, lost)
    if backend == "tpu":
        from seaweedfs_tpu.ops.rs_tpu import TpuCodec
        codec = TpuCodec(K, M)
    else:
        codec = NumpyCodec(K, M)
    store = Store([str(tmp_path)], codec=codec)
    given, real_give = [], ec_encoder._give_slab

    def noting_give(block):
        given.append(block.base)
        real_give(block)

    monkeypatch.setattr(ec_encoder, "_give_slab", noting_give)
    transport._SLAB_POOL.clear()
    try:
        replies = []
        for _ in range(2):
            stats = {}
            assert store.rebuild_ec_shards_streaming(
                1, "", stats=stats, slab=slab) == lost
            assert _shas(base) == encoded[1]
            replies.append(stats)
            for i in lost:
                os.remove(base + to_ext(i))
        stripes = SHARD_BYTES // slab
        assert len(given) == (2 * stripes if copied else 0)
        assert replies[0]["slab_fresh_bytes"] > 0
        assert (replies[1]["slab_fresh_bytes"] == 0) == copied
        assert replies[1]["rows_in_place"] == K * stripes
    finally:
        transport._SLAB_POOL.clear()


def test_one_lost_data_shard_still_takes_the_plane_route(encoded, tmp_path):
    from seaweedfs_tpu.storage.store import Store
    base = _volume_without(encoded, tmp_path, [5])
    stats = {}
    store = Store([str(tmp_path)], codec=NumpyCodec(K, M))
    assert store.rebuild_ec_shards_streaming(1, "", stats=stats) == [5]
    # (the plane repair's reply, not the full decode's: PR 48 gave every
    # route `lost`; k whole survivors read stays the full decodes' own)
    assert stats["repair_mode"] == "piggyback" and stats["lost"] == [5]
    assert "survivor_bytes" not in stats and stats["coupled_decodes"] == 0
    assert _shas(base) == encoded[1]


def test_a_forced_plane_route_refuses_a_holders_loss(encoded, tmp_path):
    from seaweedfs_tpu.storage.store import Store, VolumeError
    _volume_without(encoded, tmp_path, [2, 6, 10])
    store = Store([str(tmp_path)], codec=NumpyCodec(K, M))
    before = telemetry.STATS.snapshot()
    with pytest.raises(VolumeError, match="3 shards lost"):
        store.rebuild_ec_shards_streaming(1, "", repair="piggyback")
    assert telemetry.STATS.snapshot()["repair_fallbacks"] == \
        before["repair_fallbacks"]


def test_a_host_codec_decodes_on_the_consumer(encoded, tmp_path):
    """`codec.pipelined` false: the same body, the synchronous matmul."""
    from seaweedfs_tpu.storage.store import Store
    base = _volume_without(encoded, tmp_path, LOSSES["holder-A"])
    store = Store([str(tmp_path)], codec=NumpyCodec(K, M))
    stats = {}
    assert store.rebuild_ec_shards_streaming(
        1, "", stats=stats, slab=SLAB) == LOSSES["holder-A"]
    assert _shas(base) == encoded[1]
    assert stats["dispatches"] == 0 and stats["operand"] == [128, 320]
    assert stats["phases"]["drain"] == 0 and stats["phases"]["dispatch"] > 0


# -- (c) ---------------------------------------------------------------------

class _CutSource:
    """A gather whose second stripe fails."""

    def __init__(self, source):
        self.source = source
        self.shard_size, self.slab = source.shard_size, source.slab
        self.stats = source.stats

    def slabs(self):
        for n, stripe in enumerate(self.source.slabs()):
            if n == 1:
                raise IOError("holder went away")
            yield stripe


@pytest.mark.parametrize("backend", ["numpy", "tpu"])
def test_a_failed_stripe_removes_the_partial_outputs(encoded, tmp_path,
                                                     backend):
    from seaweedfs_tpu.ec.gather import GatherStats, LocalShardReader, \
        StripedGatherSource
    from seaweedfs_tpu.storage.store import Store
    lost = LOSSES["holder-D"]
    base = _volume_without(encoded, tmp_path, lost)
    if backend == "numpy":
        codec = NumpyCodec(K, M)
    else:
        from seaweedfs_tpu.ops.rs_tpu import TpuCodec
        codec = TpuCodec(K, M)
    layout = Store([str(tmp_path)], codec=codec)._volume_layout(base)
    present = [i not in lost for i in range(K + M)]
    gstats = GatherStats()

    def cut(src):
        return _CutSource(StripedGatherSource(
            [LocalShardReader(base + to_ext(i), gstats) for i in src],
            SHARD_BYTES, slab=SLAB, stats=gstats))

    with pytest.raises(IOError, match="holder went away"):
        ec_encoder.rebuild_ec_files_piggyback(base, present, lost, layout,
                                              cut, codec=codec)
    assert not [i for i in lost if os.path.exists(base + to_ext(i))]
    # and the survivors are untouched: the same call, whole, succeeds
    assert ec_encoder.rebuild_ec_files(base, codec=codec, slab=SLAB,
                                       layout=layout) == lost
    assert _shas(base) == encoded[1]


# -- (d) ---------------------------------------------------------------------

def test_a_stripes_stages_hang_under_the_streams_root(encoded, tmp_path):
    from seaweedfs_tpu.ops.rs_tpu import TpuCodec
    from seaweedfs_tpu.storage.store import Store
    lost = LOSSES["holder-B"]
    _volume_without(encoded, tmp_path, lost)
    store = Store([str(tmp_path)], codec=TpuCodec(K, M))
    spans = []
    tracing.add_finish_hook(spans.append)
    try:
        stats = {}
        store.rebuild_ec_shards_streaming(1, "", stats=stats, slab=SLAB)
    finally:
        tracing.remove_finish_hook(spans.append)
    root, = [s for s in spans if s["name"] == "ec.rebuild.stream"]
    under = {}
    for s in spans:
        # stages carry their thread; the phase totals and the gather's
        # per-stripe record under the same root do not
        if s.get("parent_id") == root["span_id"] and "thread" in s["tags"]:
            under.setdefault(s["name"], []).append(s)
    # one span a stripe and stage, never one a window; one plan
    for name in ("ec.rebuild.assemble", "ec.rebuild.pb_split", "ec.h2d",
                 "ec.d2h", "ec.rebuild.pb_merge", "ec.rebuild.write"):
        assert len(under[name]) == STRIPES, name
    assert len(under["ec.rebuild.plan"]) == 1
    assert len(under["ec.rebuild.fetch.local"]) == K * STRIPES
    threads = {name: {s["tags"]["thread"] for s in got}
               for name, got in under.items()}
    consumer = threads["ec.rebuild.plan"]
    assert len(consumer) == 1
    assert threads["ec.rebuild.pb_split"] == \
        threads["ec.rebuild.assemble"] == {"pipeline-producer"}
    assert threads["ec.rebuild.pb_merge"] == threads["ec.rebuild.write"] \
        == threads["ec.h2d"] == consumer
    assert all(t.startswith("pipeline-drain") for t in threads["ec.d2h"])
    assert all(t.startswith("ec-pull")
               for t in threads["ec.rebuild.fetch.local"])
    total = {name: sum(s["tags"]["bytes"] for s in got)
             for name, got in under.items()}
    assert total["ec.rebuild.pb_split"] == K * SHARD_BYTES
    assert total["ec.rebuild.pb_merge"] == total["ec.rebuild.write"] == \
        stats["rebuilt_bytes"] == len(lost) * SHARD_BYTES
    # the consumer's account holds the merge with the write
    merged = sum(s["duration_s"] for s in under["ec.rebuild.pb_merge"])
    written = sum(s["duration_s"] for s in under["ec.rebuild.write"])
    assert stats["phases"]["write"] >= merged + written - 1e-3
