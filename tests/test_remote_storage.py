"""Remote-storage tiering tests: mount a cloud path onto a filer dir,
sync metadata, read through, cache/uncache, and push writes back.

In-process analogue of the reference's remote-mount flow
(weed/shell/command_remote_*.go + weed/command/filer_remote_sync.go),
using the local-directory client for determinism plus one S3 round-trip
against the framework's own gateway.
"""
import json
import os
import time

import pytest
import requests

from seaweedfs_tpu.remote_storage import (LocalRemoteClient,
                                          S3RemoteClient, make_client)
from seaweedfs_tpu.remote_storage.sync import RemoteSyncWorker
from seaweedfs_tpu.server.cluster import Cluster
from seaweedfs_tpu.shell.env import CommandEnv
from seaweedfs_tpu.shell.repl import run_command


class TestClients:
    def test_local_roundtrip(self, tmp_path):
        c = LocalRemoteClient(root=str(tmp_path / "r"))
        c.write_file("a/b.txt", b"hello")
        assert c.read_file("a/b.txt") == b"hello"
        assert c.read_file("a/b.txt", offset=1, size=3) == b"ell"
        keys = [e.key for e in c.traverse()]
        assert keys == ["a/b.txt"]
        assert c.head("a/b.txt").size == 5
        assert c.head("missing") is None
        c.delete_file("a/b.txt")
        assert c.head("a/b.txt") is None

    def test_local_escape_forbidden(self, tmp_path):
        c = LocalRemoteClient(root=str(tmp_path / "r"))
        with pytest.raises(PermissionError):
            c.read_file("../../etc/passwd")

    def test_make_client_errors(self):
        with pytest.raises(KeyError, match="unknown"):
            make_client({"type": "nope"})
        with pytest.raises(KeyError, match="cloud SDK"):
            make_client({"type": "hdfs"})
        # gcs/azure are real in-tree REST clients now: they fail on
        # missing required config, not on a missing SDK
        with pytest.raises(ValueError, match="bucket"):
            make_client({"type": "gcs"})
        with pytest.raises(ValueError, match="account"):
            make_client({"type": "azure"})


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    c = Cluster(str(tmp_path_factory.mktemp("remote_cluster")),
                n_volume_servers=1, volume_size_limit=8 << 20,
                with_s3=True)
    yield c
    c.stop()


@pytest.fixture()
def env(cluster):
    e = CommandEnv(cluster.master_url, filer_url=cluster.filer_url)
    e.acquire_lock()
    yield e
    e.close()


@pytest.fixture(scope="module")
def remote_dir(tmp_path_factory):
    root = tmp_path_factory.mktemp("cloud")
    (root / "photos").mkdir()
    (root / "photos" / "a.jpg").write_bytes(b"JPEG" * 100)
    (root / "photos" / "b.jpg").write_bytes(b"PNG" * 200)
    (root / "readme.txt").write_bytes(b"top-level")
    return str(root)


class TestMountFlow:
    def test_configure_mount_sync_read_cache(self, cluster, env,
                                             remote_dir):
        out = run_command(
            env, f"remote.configure -name=cloud1 -type=local "
                 f"-root={remote_dir}")
        assert out == {"cloud1": "local"}
        out = run_command(env,
                          "remote.mount -dir=/clouddata -remote=cloud1")
        assert out["mounted"] == "/clouddata"
        assert out["created"] == 3

        # placeholders: metadata only, no chunks
        meta = requests.get(f"{cluster.filer_url}/clouddata/photos/a.jpg",
                            params={"meta": "1"}).json()
        assert "chunks" not in meta or not meta["chunks"]
        assert json.loads(meta["extended"]["remote"])["size"] == 400

        # read-through GET serves the cloud bytes
        r = requests.get(f"{cluster.filer_url}/clouddata/photos/a.jpg")
        assert r.status_code == 200 and r.content == b"JPEG" * 100
        # ranged read-through
        r = requests.get(f"{cluster.filer_url}/clouddata/readme.txt",
                         headers={"Range": "bytes=4-8"})
        assert r.status_code == 206 and r.content == b"level"

        # cache: bytes become cluster chunks
        out = run_command(env, "remote.cache -dir=/clouddata")
        assert out["cached"] == 3
        meta = requests.get(f"{cluster.filer_url}/clouddata/photos/a.jpg",
                            params={"meta": "1"}).json()
        assert meta["chunks"]
        r = requests.get(f"{cluster.filer_url}/clouddata/photos/a.jpg")
        assert r.content == b"JPEG" * 100

        # uncache: chunks dropped, read-through again
        out = run_command(env, "remote.uncache -dir=/clouddata")
        assert out["uncached"] == 3
        meta = requests.get(f"{cluster.filer_url}/clouddata/photos/b.jpg",
                            params={"meta": "1"}).json()
        assert not meta.get("chunks")
        r = requests.get(f"{cluster.filer_url}/clouddata/photos/b.jpg")
        assert r.content == b"PNG" * 200

    def test_meta_sync_detects_changes(self, cluster, env, remote_dir):
        # new + changed + deleted upstream
        with open(os.path.join(remote_dir, "new.bin"), "wb") as f:
            f.write(b"fresh")
        with open(os.path.join(remote_dir, "readme.txt"), "wb") as f:
            f.write(b"rewritten!")
        os.remove(os.path.join(remote_dir, "photos", "b.jpg"))
        out = run_command(env, "remote.meta.sync -dir=/clouddata")
        assert out["created"] == 1
        assert out["updated"] >= 1
        assert out["removed"] == 1
        r = requests.get(f"{cluster.filer_url}/clouddata/readme.txt")
        assert r.content == b"rewritten!"
        assert requests.get(
            f"{cluster.filer_url}/clouddata/photos/b.jpg").status_code \
            == 404

    def test_unmount(self, cluster, env, remote_dir):
        out = run_command(env, "remote.unmount -dir=/clouddata")
        assert out == {"unmounted": "/clouddata"}
        assert run_command(env, "remote.mount") == {}


class TestRemoteSyncBack:
    def test_local_writes_pushed(self, cluster, env, tmp_path):
        root = tmp_path / "push-cloud"
        root.mkdir()
        run_command(env, f"remote.configure -name=pc -type=local "
                         f"-root={root}")
        run_command(env, "remote.mount -dir=/pushed -remote=pc")
        w = RemoteSyncWorker(cluster.filer_url, "/pushed")
        w.start()
        try:
            requests.put(f"{cluster.filer_url}/pushed/doc.txt",
                         data=b"written locally").raise_for_status()
            deadline = time.monotonic() + 10
            target = root / "doc.txt"
            while time.monotonic() < deadline and not target.exists():
                time.sleep(0.05)
            assert target.read_bytes() == b"written locally"

            requests.delete(
                f"{cluster.filer_url}/pushed/doc.txt").raise_for_status()
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and target.exists():
                time.sleep(0.05)
            assert not target.exists()
        finally:
            w.stop()
            run_command(env, "remote.unmount -dir=/pushed")


class TestEdgeCases:
    def test_empty_remote_file_read_through(self, cluster, env,
                                            tmp_path):
        root = tmp_path / "empty-cloud"
        root.mkdir()
        (root / "zero.bin").write_bytes(b"")
        run_command(env, f"remote.configure -name=ec -type=local "
                         f"-root={root}")
        run_command(env, "remote.mount -dir=/emptymnt -remote=ec")
        r = requests.get(f"{cluster.filer_url}/emptymnt/zero.bin")
        assert r.status_code == 200 and r.content == b""
        run_command(env, "remote.unmount -dir=/emptymnt")

    def test_rename_of_uncached_placeholder_keeps_bytes(self, cluster,
                                                        env, tmp_path):
        """Renaming an uncached placeholder must copy the remote object
        to the new key before removing the old one."""
        root = tmp_path / "ren-cloud"
        root.mkdir()
        (root / "orig.txt").write_bytes(b"remote-only bytes")
        run_command(env, f"remote.configure -name=rn -type=local "
                         f"-root={root}")
        run_command(env, "remote.mount -dir=/renmnt -remote=rn")
        w = RemoteSyncWorker(cluster.filer_url, "/renmnt")
        w.start()
        try:
            requests.put(f"{cluster.filer_url}/renmnt/moved.txt",
                         params={"mv.from": "/renmnt/orig.txt"},
                         ).raise_for_status()
            deadline = time.monotonic() + 10
            target, orig = root / "moved.txt", root / "orig.txt"
            # the worker copies, then deletes: wait for both steps
            while time.monotonic() < deadline and \
                    (not target.exists() or orig.exists()):
                time.sleep(0.05)
            assert target.read_bytes() == b"remote-only bytes"
            assert not orig.exists()
        finally:
            w.stop()
            run_command(env, "remote.unmount -dir=/renmnt")


class TestS3RemoteClient:
    def test_s3_roundtrip_against_gateway(self, cluster):
        requests.put(f"{cluster.s3_url}/rsc").raise_for_status()
        c = S3RemoteClient(endpoint=cluster.s3_url, bucket="rsc")
        c.write_file("x/one.bin", b"payload-1")
        c.write_file("x/two.bin", b"payload-22")
        assert c.read_file("x/one.bin") == b"payload-1"
        assert c.read_file("x/two.bin", offset=8, size=2) == b"22"
        keys = sorted(e.key for e in c.traverse(prefix="x/"))
        assert keys == ["x/one.bin", "x/two.bin"]
        sizes = {e.key: e.size for e in c.traverse()}
        assert sizes["x/two.bin"] == 10
        assert c.head("x/one.bin").size == 9
        c.delete_file("x/one.bin")
        assert c.head("x/one.bin") is None
