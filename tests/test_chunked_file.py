"""Client-side chunk-manifest large files (reference operation/submit.go:114-230, chunked_file.go)."""

import numpy as np
import pytest

from seaweedfs_tpu.client import operation as op
from seaweedfs_tpu.client.chunked import (ChunkManifest, read_chunked_file,
                                          submit_chunked)
from seaweedfs_tpu.server.http_util import HttpError, http_call
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer


@pytest.fixture
def cluster(tmp_path):
    # 1MB volumes: a multi-MB file cannot fit any single volume's free
    # space — exactly the case the manifest indirection exists for
    master = MasterServer(port=0, volume_size_limit_mb=1,
                          pulse_seconds=1).start()
    servers = []
    for i in range(2):
        vs = VolumeServer(port=0, directories=[str(tmp_path / f"v{i}")],
                          master_url=master.url, pulse_seconds=1,
                          max_volume_counts=[40],
                          ec_backend="numpy").start()
        servers.append(vs)
    yield master, servers
    for vs in servers:
        vs.stop()
    master.stop()


def test_manifest_json_roundtrip():
    from seaweedfs_tpu.client.chunked import ChunkInfo
    m = ChunkManifest("f.bin", "video/mp4", 10,
                      [ChunkInfo("1,ab", 0, 6), ChunkInfo("2,cd", 6, 4)])
    again = ChunkManifest.from_json(m.to_json())
    assert again.name == "f.bin" and again.size == 10
    assert [(c.fid, c.offset, c.size) for c in again.chunks] == \
        [("1,ab", 0, 6), ("2,cd", 6, 4)]


def test_chunked_upload_read_delete(cluster):
    master, servers = cluster
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, int(2.5 * (1 << 20))
                        ).astype(np.uint8).tobytes()
    fid = submit_chunked(master.url, data, filename="big.bin",
                         chunk_size=1 << 20, content_type="video/mp4")

    # the manifest fid must resolve server-side to the whole file
    vid = int(fid.split(",")[0])
    url = op.lookup(master.url, vid)[0]
    got = http_call("GET", f"http://{url}/{fid}")
    assert got == data

    # raw read shows the manifest json; chunks span multiple volumes
    # (no single 1MB volume could have held the 2.5MB file)
    raw = http_call("GET", f"http://{url}/{fid}?cm=false")
    manifest = ChunkManifest.from_json(raw)
    assert manifest.size == len(data) and len(manifest.chunks) == 3
    chunk_vids = {int(c.fid.split(",")[0]) for c in manifest.chunks}
    assert len(chunk_vids | {vid}) >= 2

    # client-side reader agrees
    assert read_chunked_file(master.url, fid) == data

    # range read through the manifest
    piece = http_call("GET", f"http://{url}/{fid}",
                      headers={"Range": "bytes=1048570-1048585"})
    assert piece == data[1048570:1048586]

    # delete cascades to the chunk needles
    assert op.delete_file(master.url, fid)
    for c in manifest.chunks:
        with pytest.raises(HttpError):
            op.read_file(master.url, c.fid)
    with pytest.raises(HttpError):
        op.read_file(master.url, fid)


def test_cli_upload_chunked_path(cluster, tmp_path):
    """weed upload -maxMB routes big files through submit_chunked."""
    import subprocess
    import sys
    master, _ = cluster
    p = tmp_path / "file.bin"
    rng = np.random.default_rng(5)
    p.write_bytes(rng.integers(0, 256, 3 << 20).astype(np.uint8).tobytes())
    out = subprocess.run(
        [sys.executable, "-m", "seaweedfs_tpu.command.cli", "upload",
         "-master", master.url, "-maxMB", "1", str(p)],
        capture_output=True, text=True, timeout=120,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    fid = out.stdout.strip().split(" -> ")[-1]
    assert read_chunked_file(master.url, fid) == p.read_bytes()
