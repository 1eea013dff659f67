"""Distributed integration tests without a cluster: real servers as
in-process threads on loopback ports + temp discovery files.

Mirrors the reference's strategy (tests/test_integration.py:51-115) and its
key assertions: threshold training honored end-to-end, golden equality of a
4-server cluster vs a single flat server, exact round-robin balance, config
persistence, centroid export.
"""

import socket
import threading
import time

import numpy as np
import pytest

from distributed_faiss_tpu import IndexClient, IndexCfg, IndexServer, IndexState
from distributed_faiss_tpu.parallel import rpc


def free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def wait_listening(port, timeout=10.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        try:
            s = socket.create_connection(("localhost", port), timeout=1)
            s.close()
            return True
        except OSError:
            time.sleep(0.05)
    return False


def start_cluster(n, storage_dir, selector=False):
    servers, ports = [], []
    for rank in range(n):
        port = free_port()
        srv = IndexServer(rank, str(storage_dir))
        target = srv.start if selector else srv.start_blocking
        threading.Thread(target=target, args=(port,), daemon=True).start()
        servers.append(srv)
        ports.append(port)
    for port in ports:
        assert wait_listening(port)
    return servers, ports


def write_discovery(tmp_path, ports, name):
    p = tmp_path / name
    p.write_text("\n".join([str(len(ports))] + [f"localhost,{port}" for port in ports]) + "\n")
    return str(p)


def wait_trained(client, index_id, timeout=60.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if client.get_state(index_id) == IndexState.TRAINED:
            return True
        time.sleep(0.1)
    return False


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    """4-server cluster + 1 single server, shared across tests in this module
    (per-test isolation via index ids, like the reference's setUpClass)."""
    base = tmp_path_factory.mktemp("cluster")
    multi_servers, multi_ports = start_cluster(4, base / "multi")
    single_servers, single_ports = start_cluster(1, base / "single")
    disc_dir = tmp_path_factory.mktemp("disc")
    multi_list = write_discovery(disc_dir, multi_ports, "multi.txt")
    single_list = write_discovery(disc_dir, single_ports, "single.txt")
    yield {"multi": multi_list, "single": single_list}


def flat_cfg(**kw):
    kw.setdefault("index_builder_type", "flat")
    kw.setdefault("dim", 16)
    kw.setdefault("metric", "l2")
    kw.setdefault("train_num", 64)
    return IndexCfg(**kw)


def fill(client, index_id, x, meta, bs=100):
    for s in range(0, x.shape[0], bs):
        client.add_index_data(index_id, x[s : s + bs], meta[s : s + bs])


def test_train_num_honored_cluster(cluster, rng, request):
    index_id = request.node.name
    client = IndexClient(cluster["multi"])
    cfg = flat_cfg(train_num=100)
    client.create_index(index_id, cfg)
    x = rng.standard_normal((396, 16)).astype(np.float32)
    meta = [("d", i) for i in range(396)]
    fill(client, index_id, x[:320], meta[:320], bs=80)  # 80/server < 100
    assert client.get_state(index_id) == IndexState.NOT_TRAINED
    fill(client, index_id, x[320:], meta[320:], bs=19)  # pushes each past 100
    client.sync_train(index_id)
    assert wait_trained(client, index_id)
    assert client.get_ntotal(index_id) == 396
    client.close()


def test_golden_single_vs_multi(cluster, rng, request):
    """Same corpus into 4-shard cluster and 1 flat server: merged results
    must match exactly (reference test_search_quality..., :205-265)."""
    index_id = request.node.name
    x = rng.standard_normal((800, 16)).astype(np.float32)
    meta = [("doc", i) for i in range(800)]
    q = rng.standard_normal((12, 16)).astype(np.float32)

    results = {}
    for name in ("multi", "single"):
        client = IndexClient(cluster[name])
        cfg = flat_cfg(train_num=10)
        client.create_index(index_id, cfg)
        fill(client, index_id, x, meta, bs=50)
        client.sync_train(index_id)
        assert wait_trained(client, index_id)
        assert client.get_ntotal(index_id) == 800
        results[name] = client.search(q, 10, index_id)
        client.close()

    d_multi, m_multi = results["multi"]
    d_single, m_single = results["single"]
    np.testing.assert_allclose(d_multi, d_single, rtol=1e-4, atol=1e-5)
    assert m_multi == m_single


def test_golden_single_vs_multi_dot(cluster, rng, request):
    index_id = request.node.name
    x = rng.standard_normal((600, 16)).astype(np.float32)
    meta = [i for i in range(600)]
    q = rng.standard_normal((8, 16)).astype(np.float32)
    results = {}
    for name in ("multi", "single"):
        client = IndexClient(cluster[name])
        client.create_index(index_id, flat_cfg(metric="dot", train_num=10))
        fill(client, index_id, x, meta, bs=50)
        client.sync_train(index_id)
        assert wait_trained(client, index_id)
        results[name] = client.search(q, 7, index_id)
        client.close()
    np.testing.assert_allclose(results["multi"][0], results["single"][0], rtol=1e-4, atol=1e-5)
    assert results["multi"][1] == results["single"][1]
    # dot D is negated similarity, ascending (reference heap semantics)
    assert np.all(np.diff(results["multi"][0], axis=1) >= 0)


def test_round_robin_balance(cluster, rng, request):
    index_id = request.node.name
    client = IndexClient(cluster["multi"])
    client.create_index(index_id, flat_cfg(train_num=25))
    x = rng.standard_normal((400, 16)).astype(np.float32)
    meta = list(range(400))
    fill(client, index_id, x, meta, bs=25)  # 16 batches over 4 servers
    client.sync_train(index_id)
    assert wait_trained(client, index_id)
    # every server holds exactly total/num_servers vectors
    # (reference test_integration.py:308-313)
    per_server = [c.get_ntotal(index_id) for c in client.sub_indexes]
    assert per_server == [100, 100, 100, 100]
    assert client.get_ntotal("missing-index-id") == 0
    d, m = client.search(x[:3], 5, index_id)
    assert d.shape == (3, 5) and len(m) == 3 and len(m[0]) == 5
    client.close()


def test_save_drop_load(cluster, rng, request):
    index_id = request.node.name
    client = IndexClient(cluster["multi"])
    client.create_index(index_id, flat_cfg(train_num=20))
    x = rng.standard_normal((200, 16)).astype(np.float32)
    meta = [("m", i) for i in range(200)]
    fill(client, index_id, x, meta, bs=50)  # one batch per server
    client.sync_train(index_id)
    assert wait_trained(client, index_id)
    q = x[:4]
    d0, m0 = client.search(q, 5, index_id)
    client.save_index(index_id)
    client.drop_index(index_id)
    with pytest.raises(rpc.ServerException):
        client.search(q, 5, index_id)
    assert client.load_index(index_id, flat_cfg(train_num=20)) is True
    assert wait_trained(client, index_id)
    d1, m1 = client.search(q, 5, index_id)
    np.testing.assert_allclose(d0, d1, rtol=1e-5)
    assert m0 == m1
    client.close()


def test_config_persisted_and_recovered(cluster, rng, request):
    """cfg.json lands at {save_dir}/{index_id}/{rank}/ and reload without an
    explicit cfg restores it (reference test_config_to_file :332-385)."""
    import os

    index_id = request.node.name
    client = IndexClient(cluster["multi"])
    cfg = flat_cfg(train_num=30, metric="dot")
    client.create_index(index_id, cfg)
    x = rng.standard_normal((160, 16)).astype(np.float32)
    fill(client, index_id, x, list(range(160)), bs=40)
    client.sync_train(index_id)
    assert wait_trained(client, index_id)
    client.save_index(index_id)
    cfg_path = client.sub_indexes[0].get_config_path(index_id)
    assert os.path.isfile(cfg_path)
    assert f"{index_id}/0/cfg.json" in cfg_path.replace("\\", "/")
    client.drop_index(index_id)
    assert client.load_index(index_id, cfg=None) is True
    assert client.cfg.metric == "dot"
    assert client.cfg.train_num == 30
    client.close()


def test_get_centroids_and_nprobe(cluster, rng, request):
    index_id = request.node.name
    client = IndexClient(cluster["multi"])
    cfg = IndexCfg(index_builder_type="ivf_simple", dim=16, metric="l2",
                   train_num=100, centroids=4, nprobe=4)
    client.create_index(index_id, cfg)
    x = rng.standard_normal((480, 16)).astype(np.float32)
    fill(client, index_id, x, list(range(480)), bs=60)
    client.sync_train(index_id)
    assert wait_trained(client, index_id)
    cents = client.get_centroids(index_id)
    assert len(cents) == 4
    for c in cents:
        assert c.shape == (4, 16)
    client.set_nprobe(index_id, 2)
    d, m = client.search(x[:2], 3, index_id)
    assert d.shape == (2, 3)
    client.close()


def test_search_with_filter(cluster, rng, request):
    index_id = request.node.name
    client = IndexClient(cluster["multi"])
    client.create_index(index_id, flat_cfg(train_num=20))
    x = rng.standard_normal((200, 16)).astype(np.float32)
    meta = [("even" if i % 2 == 0 else "odd", i) for i in range(200)]
    fill(client, index_id, x, meta)
    client.sync_train(index_id)
    assert wait_trained(client, index_id)
    scores, m = client.search_with_filter(x[:4], 5, index_id, filter_pos=0,
                                          filter_value="even")
    for row in m:
        assert len(row) <= 5
        for entry in row:
            assert entry[0] == "odd"
    client.close()


def test_search_with_filter_requery(cluster, rng, request):
    """Heavily-filtered corpus: the re-query loop (our fix of the
    reference's TODO, client.py:254-257) must fill rows the first
    over-fetch couldn't."""
    index_id = request.node.name
    client = IndexClient(cluster["multi"])
    client.create_index(index_id, flat_cfg(train_num=20))
    x = rng.standard_normal((400, 16)).astype(np.float32)
    # 95% of entries filtered out: 3x over-fetch of k=5 won't find 5 rares
    meta = [("rare" if i % 20 == 0 else "common", i) for i in range(400)]
    fill(client, index_id, x, meta)
    client.sync_train(index_id)
    assert wait_trained(client, index_id)
    _, no_requery = client.search_with_filter(
        x[:4], 5, index_id, filter_pos=0, filter_value="common", max_requery=0)
    _, with_requery = client.search_with_filter(
        x[:4], 5, index_id, filter_pos=0, filter_value="common", max_requery=3)
    assert all(len(row) == 5 for row in with_requery)
    assert all(e[0] == "rare" for row in with_requery for e in row)
    # reference behavior returns short rows here
    assert any(len(row) < 5 for row in no_requery)
    client.close()


def test_get_ids_and_embeddings(cluster, rng, request):
    index_id = request.node.name
    client = IndexClient(cluster["multi"])
    client.create_index(index_id, flat_cfg(train_num=20, custom_meta_id_idx=1))
    x = rng.standard_normal((120, 16)).astype(np.float32)
    meta = [("m", 1000 + i) for i in range(120)]
    fill(client, index_id, x, meta, bs=30)
    client.sync_train(index_id)
    assert wait_trained(client, index_id)
    assert client.get_ids(index_id) == set(range(1000, 1120))
    d, m, embs = client.search(x[:2], 3, index_id, return_embeddings=True)
    assert len(embs) == 2 and len(embs[0]) == 3
    # top-1 for x[i] is itself; returned embedding must reconstruct it
    np.testing.assert_allclose(np.asarray(embs[0][0]), x[0], rtol=1e-4, atol=1e-5)
    client.close()


def test_selector_server_mode(tmp_path, rng):
    """The reference's selector loop is broken (test skipped); ours serves."""
    servers, ports = start_cluster(1, tmp_path / "sel", selector=True)
    lst = write_discovery(tmp_path, ports, "sel.txt")
    client = IndexClient(lst)
    client.create_index("sel-idx", flat_cfg(train_num=10))
    x = rng.standard_normal((50, 16)).astype(np.float32)
    client.add_index_data("sel-idx", x, list(range(50)))
    client.sync_train("sel-idx")
    assert wait_trained(client, "sel-idx")
    d, m = client.search(x[:2], 3, "sel-idx")
    assert m[0][0] == 0 and m[1][0] == 1
    client.close()


def test_buffer_depth(cluster, rng, request):
    index_id = request.node.name
    client = IndexClient(cluster["multi"])
    client.create_index(index_id, flat_cfg(train_num=10_000))  # never auto-trains
    x = rng.standard_normal((120, 16)).astype(np.float32)
    fill(client, index_id, x, list(range(120)), bs=30)
    assert client.get_buffer_depth(index_id) == 120  # all buffered, none indexed
    client.sync_train(index_id)
    assert wait_trained(client, index_id)
    assert client.get_buffer_depth(index_id) == 0
    assert client.get_ntotal(index_id) == 120
    client.close()


def test_ping_health(cluster, rng, request):
    index_id = request.node.name
    client = IndexClient(cluster["multi"])
    client.create_index(index_id, flat_cfg(train_num=10))
    client.add_index_data(index_id, rng.standard_normal((20, 16)).astype(np.float32), None)
    client.sync_train(index_id)
    assert wait_trained(client, index_id)
    health = client.ping()
    assert len(health) == 4
    assert sorted(h["rank"] for h in health) == [0, 1, 2, 3]
    # every server must report the index (add only hit one, create hit all)
    assert all(h["indexes"].get(index_id) == "TRAINED" for h in health)
    # kernel observability: no demotions on a healthy interpreter run
    assert all(h["kernels"] == {"pallas_degraded": []} for h in health)
    client.close()


def test_missing_index_raises_server_exception(cluster):
    client = IndexClient(cluster["multi"])
    # no cfg yet: the client itself refuses to merge-search
    with pytest.raises(RuntimeError, match="no cfg"):
        client.search(np.zeros((1, 16), np.float32), 3, "never-created")
    client.create_index("exists-but-not-the-target", flat_cfg())
    with pytest.raises(rpc.ServerException) as ei:
        client.search(np.zeros((1, 16), np.float32), 3, "never-created")
    assert "no index with id" in str(ei.value)
    client.close()
