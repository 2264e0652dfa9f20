"""GCS fault tolerance: restart with file-backed snapshot.

Mirrors the reference's test_gcs_fault_tolerance.py (SURVEY.md §4.3): kill
the GCS, restart it on the same address, and assert clients/nodelets
reconnect, KV and named actors survive, and new work schedules.
"""

import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster


@pytest.fixture
def ft_cluster(tmp_path):
    cluster = Cluster(initialize_head=False, system_config={
        "health_check_period_s": 0.2,
        "health_check_failure_threshold": 10,
        "gcs_storage": "file",
        "gcs_file_storage_path": str(tmp_path),
    })
    yield cluster
    cluster.shutdown()


def test_gcs_restart_preserves_state(ft_cluster):
    cluster = ft_cluster
    cluster.add_node(resources={"CPU": 4.0})
    cluster.connect()

    from ray_tpu.core.runtime import get_runtime

    rt = get_runtime()
    rt.kv_put("test", b"k1", b"v1")

    @ray_tpu.remote
    class Reg:
        def __init__(self):
            self.items = {}

        def put(self, k, v):
            self.items[k] = v
            return len(self.items)

        def get(self, k):
            return self.items.get(k)

    reg = Reg.options(name="registry", max_restarts=1).remote()
    assert ray_tpu.get(reg.put.remote("a", 1), timeout=30) == 1
    time.sleep(1.0)  # let the debounced snapshot land

    cluster.restart_gcs()
    time.sleep(1.0)

    # KV survived the restart.
    assert rt.kv_get("test", b"k1") == b"v1"
    # The named-actor registry survived; the actor itself never died, so
    # its state is intact and calls keep working.
    h = ray_tpu.get_actor("registry")
    assert ray_tpu.get(h.get.remote("a"), timeout=30) == 1
    # New tasks schedule (nodelet re-registered via heartbeat reply).
    @ray_tpu.remote
    def f(x):
        return x + 1

    assert ray_tpu.get(f.remote(41), timeout=60) == 42


def test_gcs_wal_survives_kill_between_snapshots(ft_cluster):
    """Writes acked AFTER the last debounced snapshot must survive a
    SIGKILL — the append-WAL's whole purpose (round-1 file snapshots lost
    everything between snapshot points; ref: redis_store_client.h:33
    persists per mutation)."""
    cluster = ft_cluster
    cluster.add_node(resources={"CPU": 4.0})
    cluster.connect()

    from ray_tpu.core.runtime import get_runtime

    rt = get_runtime()
    rt.kv_put("wal", b"settled", b"old")
    time.sleep(1.2)            # let the debounced snapshot cover ^this

    # burst of acked writes, then kill before the 0.5 s debounce can fire
    for i in range(20):
        rt.kv_put("wal", f"k{i}".encode(), f"v{i}".encode())
    rt.gcs_call("kv_del", ns="wal", key=b"settled")
    cluster.restart_gcs()          # SIGKILL + restart on the same address
    time.sleep(1.0)

    for i in range(20):
        assert rt.kv_get("wal", f"k{i}".encode()) == f"v{i}".encode(), \
            f"acked write k{i} lost between snapshots"
    assert rt.kv_get("wal", b"settled") is None, "WAL delete not replayed"


def test_gcs_restart_mid_actor_creation(ft_cluster):
    """Actors pending creation when the GCS dies are re-driven after
    restart (ref: gcs_actor_manager failover reconstruction)."""
    cluster = ft_cluster
    cluster.add_node(resources={"CPU": 4.0})
    cluster.connect()

    @ray_tpu.remote
    class A:
        def ping(self):
            return "pong"

    # Create, then immediately bounce the GCS: creation may land before or
    # mid-flight; either way the actor must come up after the restart.
    a = A.options(name="survivor").remote()
    cluster.restart_gcs()
    assert ray_tpu.get(a.ping.remote(), timeout=60) == "pong"


def test_gcs_restart_task_events_and_new_nodes(ft_cluster):
    cluster = ft_cluster
    cluster.add_node(resources={"CPU": 2.0})
    cluster.connect()

    @ray_tpu.remote
    def g():
        return np.ones(10).sum()

    assert ray_tpu.get(g.remote(), timeout=30) == 10.0
    cluster.restart_gcs()
    time.sleep(0.5)
    # A node added after the restart joins the rebuilt membership.
    cluster.add_node(resources={"CPU": 2.0, "late": 1.0})

    @ray_tpu.remote(resources={"late": 0.5})
    def h():
        return "on-late-node"

    assert ray_tpu.get(h.remote(), timeout=60) == "on-late-node"


@pytest.mark.slow
def test_gcs_restart_actor_lost_during_downtime(ft_cluster):
    """An ALIVE actor whose node dies while the GCS is down is detected at
    failover reconciliation and restarted elsewhere (ref: failover
    reconstruction + max_restarts FSM)."""
    cluster = ft_cluster
    cluster.add_node(resources={"CPU": 2.0})
    doomed = cluster.add_node(resources={"CPU": 2.0, "b": 1.0})
    cluster.connect()

    @ray_tpu.remote(resources={"b": 0.5}, max_restarts=2)
    class A:
        def ping(self):
            return os.getpid()

    a = A.options(name="phoenix").remote()
    pid1 = ray_tpu.get(a.ping.remote(), timeout=30)
    time.sleep(1.0)                       # snapshot captures ALIVE state
    cluster.kill_gcs()
    cluster.remove_node(doomed)           # dies during GCS downtime
    # Orphaned workers self-exit when their nodelet stops answering pings
    # (worker supervision loop, 5s period); wait out that window so the
    # old instance is really gone.
    time.sleep(7.0)
    cluster.restart_gcs()
    time.sleep(0.5)
    cluster.add_node(resources={"CPU": 2.0, "b": 1.0})  # somewhere to go
    deadline = time.time() + 60
    pid2 = pid1
    while time.time() < deadline:
        try:
            pid2 = ray_tpu.get(a.ping.remote(), timeout=20)
            if pid2 != pid1:
                break
        except Exception:
            time.sleep(0.5)
    assert pid2 != pid1                   # restarted on the new node


def test_gcs_held_up_itself_does_not_declare_its_nodes_dead():
    """Opening a TPU freezes the whole host for seconds (gcs and nodelet
    held up at the same instant, PERF.md §6): when the GCS wakes, the
    heartbeats of that time are still queued behind its health check. The
    silence it would measure is its own; it must let them in first."""
    import asyncio

    from ray_tpu.core.common import NodeInfo, ResourceSet
    from ray_tpu.core.config import Config
    from ray_tpu.core.gcs import GcsServer
    from ray_tpu.core.ids import NodeID
    from ray_tpu.core.rpc import RpcClient

    async def scenario():
        cfg = Config()
        cfg.health_check_period_s = 0.1
        cfg.health_check_failure_threshold = 5      # 0.5 s of silence
        gcs = GcsServer(cfg)
        host, port = await gcs.start()
        info = NodeInfo(NodeID.from_random(), ("127.0.0.1", 1),
                        ResourceSet({"CPU": 1.0}))
        client = RpcClient(host, port)
        await client.call("register_node", info=info)
        stop = False
        # count the verdicts, not info.alive: the next heartbeat brings a
        # node back, but by then its actors have been restarted elsewhere
        deaths = []
        on_node_death = gcs._on_node_death

        async def counting(node_id, reason):
            deaths.append(reason)
            await on_node_death(node_id, reason)

        gcs._on_node_death = counting

        async def nodelet_heartbeats():
            seq = 0
            while not stop:
                seq += 1
                await client.call("heartbeat", node_id=info.node_id,
                                  seqno=seq, available=info.resources_total)
                await asyncio.sleep(0.05)

        beats = asyncio.ensure_future(nodelet_heartbeats())
        await asyncio.sleep(0.3)
        time.sleep(0.8)            # the host freezes: every loop at once
        await asyncio.sleep(0.4)
        deaths_after_freeze = list(deaths)
        stop = True
        await beats
        await asyncio.sleep(0.8)   # a node that really falls silent
        gcs._stopping = True
        await client.close()
        await gcs.server.stop()
        return deaths_after_freeze, deaths

    deaths_after_freeze, deaths = asyncio.run(scenario())
    assert deaths_after_freeze == []
    assert len(deaths) == 1 and "no heartbeat for" in deaths[0]
