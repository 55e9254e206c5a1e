"""filer.remote.gateway: bucket lifecycle + content write-back.

Mirrors weed/command/filer_remote_gateway_buckets.go semantics: bucket
mkdir under /buckets creates a remote bucket + mount mapping, bucket
rmdir deletes both, and object writes inside a mapped bucket land in
the remote storage. Uses the deterministic local-directory storage.
"""
import os
import time

import pytest
import requests

from seaweedfs_tpu.remote_storage.gateway import RemoteGateway
from seaweedfs_tpu.remote_storage.mount import (RemoteConf, load_conf,
                                                save_conf)
from seaweedfs_tpu.server.cluster import Cluster


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    c = Cluster(str(tmp_path_factory.mktemp("gw_cluster")),
                n_volume_servers=1, volume_size_limit=8 << 20,
                with_filer=True)
    yield c
    c.stop()


@pytest.fixture(scope="module")
def gateway(cluster, tmp_path_factory):
    cloud = tmp_path_factory.mktemp("gw_cloud")
    conf = RemoteConf(storages={
        "cloud1": {"type": "local", "root": str(cloud)}})
    save_conf(cluster.filer_url, conf)
    g = RemoteGateway(cluster.filer_url)
    g.start()
    yield g, str(cloud)
    g.stop()


def _wait(pred, timeout=15, msg="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.2)
    raise TimeoutError(f"{msg} never became true")


def test_primary_storage_autodetected(gateway):
    g, _ = gateway
    assert g.create_bucket_at == "cloud1"


def test_bucket_create_mirrors_and_mounts(cluster, gateway):
    g, cloud = gateway
    requests.post(f"{cluster.filer_url}/buckets/media/",
                  params={"mkdir": "1"}).raise_for_status()
    _wait(lambda: os.path.isdir(os.path.join(cloud, "media")),
          msg="remote bucket dir")
    # the gateway makes the remote directory, then saves the conf
    _wait(lambda: "/buckets/media" in load_conf(cluster.filer_url).mounts,
          msg="mount")
    conf = load_conf(cluster.filer_url)
    assert conf.mounts["/buckets/media"].remote_path == "media"


def test_object_writes_mirror_to_remote(cluster, gateway):
    g, cloud = gateway
    requests.post(f"{cluster.filer_url}/buckets/media/pic.jpg",
                  data=b"JPEGDATA" * 64).raise_for_status()
    target = os.path.join(cloud, "media", "pic.jpg")
    _wait(lambda: os.path.exists(target), msg="mirrored object")
    with open(target, "rb") as f:
        assert f.read() == b"JPEGDATA" * 64


def test_object_delete_mirrors(cluster, gateway):
    g, cloud = gateway
    requests.post(f"{cluster.filer_url}/buckets/media/tmp.bin",
                  data=b"x" * 10).raise_for_status()
    target = os.path.join(cloud, "media", "tmp.bin")
    _wait(lambda: os.path.exists(target), msg="mirrored object")
    requests.delete(
        f"{cluster.filer_url}/buckets/media/tmp.bin").raise_for_status()
    _wait(lambda: not os.path.exists(target), msg="remote delete")


def test_bucket_delete_removes_remote_and_mount(cluster, gateway):
    g, cloud = gateway
    requests.post(f"{cluster.filer_url}/buckets/scratch/",
                  params={"mkdir": "1"}).raise_for_status()
    _wait(lambda: os.path.isdir(os.path.join(cloud, "scratch")),
          msg="remote bucket dir")
    requests.delete(f"{cluster.filer_url}/buckets/scratch/",
                    params={"recursive": "true"}).raise_for_status()
    _wait(lambda: not os.path.isdir(os.path.join(cloud, "scratch")),
          msg="remote bucket removal")
    # the gateway removes the remote directory, then saves the conf
    _wait(lambda: "/buckets/scratch" not in
          load_conf(cluster.filer_url).mounts, msg="mount removal")


def test_include_exclude_filters():
    g = RemoteGateway.__new__(RemoteGateway)
    g.include, g.exclude = "s3*", ""
    assert g._name_allowed("s3-media") and not g._name_allowed("local1")
    g.include, g.exclude = "", "local*"
    assert g._name_allowed("s3-media") and not g._name_allowed("local1")


def test_bucket_path_parsing():
    g = RemoteGateway.__new__(RemoteGateway)
    g.buckets_dir = "/buckets"
    assert g._bucket_of("/buckets/media") == "media"
    assert g._bucket_of("/buckets/media/obj") is None
    assert g._bucket_of("/other/media") is None
    assert g._bucket_of("/buckets") is None
