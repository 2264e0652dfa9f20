"""Checkpoint layer: orbax-backed sharded save/restore + URI storage tier.

Covers VERDICT r2 item 1 (ref: python/ray/air/checkpoint.py +
air/_internal/remote_storage.py + SURVEY §5.4): sharded restore onto a
NamedSharding target on the 8-device virtual mesh, a true 2-process
jax.distributed save where each process writes only its addressable shards,
the fsspec URI tier (memory:// in tests, same code path as gs://"s3://), and
Trainer failure-restart resuming through a URI storage_path.
"""

import os
import pickle
import shutil
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.train import storage
from ray_tpu.train.checkpoint import Checkpoint, CheckpointManager


def _mesh():
    return jax.sharding.Mesh(np.array(jax.devices()[:8]).reshape(4, 2),
                             ("dp", "tp"))


def test_sharded_roundtrip(tmp_path):
    mesh = _mesh()
    sh = jax.sharding.NamedSharding(mesh,
                                    jax.sharding.PartitionSpec("dp", "tp"))
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    x = jax.device_put(jnp.arange(64, dtype=jnp.float32).reshape(8, 8), sh)
    state = {"params": {"w": x, "b": jax.device_put(jnp.ones(8), repl)},
             "step": jnp.int32(7)}
    ck = Checkpoint.from_state(state, str(tmp_path / "ck"))

    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=getattr(a, "sharding", None)),
        state)
    r = ck.load_state(abstract)
    assert r["params"]["w"].sharding == sh
    assert r["params"]["b"].sharding == repl
    assert jnp.allclose(r["params"]["w"], x)
    assert int(r["step"]) == 7


def test_nonarray_leaves_and_host_restore(tmp_path):
    state = {"w": jnp.arange(4.0), "name": "run1", "fn": len}
    ck = Checkpoint.from_state(state, str(tmp_path / "ck"))
    r = ck.load_state()
    assert r["name"] == "run1" and r["fn"] is len
    assert np.allclose(np.asarray(r["w"]), np.arange(4.0))


def test_legacy_pickle_format(tmp_path):
    d = tmp_path / "old"
    d.mkdir()
    with open(d / "state.pkl", "wb") as f:
        pickle.dump({"step": 3}, f)
    assert Checkpoint(str(d)).load_state() == {"step": 3}


def test_uri_roundtrip(tmp_path):
    state = {"w": jnp.arange(8.0), "step": jnp.int32(2)}
    ck = Checkpoint.from_state(state, str(tmp_path / "ck"))
    uri = "memory://ckpt-test/roundtrip"
    ck.to_uri(uri)
    back = Checkpoint.from_uri(uri, local_dir=str(tmp_path / "back"))
    r = back.load_state()
    assert int(r["step"]) == 2
    assert np.allclose(np.asarray(r["w"]), np.arange(8.0))
    storage.delete_at_uri(uri)
    assert not storage.exists_at_uri(uri)


def test_manager_uri_eviction_and_fresh_node_resume():
    uri = "memory://ckpt-test/mgr"
    storage.delete_at_uri(uri)
    shutil.rmtree(storage.local_staging_dir(uri), ignore_errors=True)
    mgr = CheckpointManager(uri, num_to_keep=2)
    for i in range(3):
        p = mgr.new_dir()
        Checkpoint.from_state({"step": jnp.int32(i)}, p)
        mgr.register(p)
    # num_to_keep evicted the oldest both locally and remotely
    assert storage.list_at_uri(uri) == ["checkpoint_000001",
                                        "checkpoint_000002"]
    # fresh node: local staging wiped, manager resumes from the URI
    shutil.rmtree(mgr.run_dir)
    mgr2 = CheckpointManager(uri, num_to_keep=2)
    latest = mgr2.latest()
    assert latest is not None and int(latest.load_state()["step"]) == 2
    assert mgr2.new_dir().endswith("checkpoint_000003")
    storage.delete_at_uri(uri)


def test_scalar_leaf_with_abstract_target(tmp_path):
    """Python-scalar leaves (int step counters) restore with an abstract
    target (regression: _abstract used to assume .shape on every leaf)."""
    state = {"w": jnp.arange(4.0), "step": 3}
    ck = Checkpoint.from_state(state, str(tmp_path / "ck"))
    r = ck.load_state({"w": jnp.zeros(4), "step": 0})
    assert int(r["step"]) == 3
    assert np.allclose(np.asarray(r["w"]), np.arange(4.0))


def test_pickled_checkpoint_redownloads_from_uri(tmp_path):
    """A pickled Checkpoint carries its URI; unpickling where the local
    path does not exist re-downloads (a worker restarted on another node
    resuming from cloud storage)."""
    state = {"step": jnp.int32(9)}
    ck = Checkpoint.from_state(state, str(tmp_path / "ck"))
    uri = "memory://ckpt-test/xnode"
    ck.to_uri(uri)
    blob = pickle.dumps(ck)
    shutil.rmtree(ck.path)  # "other node": local path gone
    ck2 = pickle.loads(blob)
    assert int(ck2.load_state()["step"]) == 9
    storage.delete_at_uri(uri)


def test_manager_partial_staging_falls_back_to_remote():
    """A half-written local checkpoint (crash mid-save) is not trusted:
    latest() re-downloads the complete remote copy."""
    uri = "memory://ckpt-test/partial"
    storage.delete_at_uri(uri)
    shutil.rmtree(storage.local_staging_dir(uri), ignore_errors=True)
    mgr = CheckpointManager(uri, num_to_keep=None)
    p = mgr.new_dir()
    Checkpoint.from_state({"w": jnp.arange(4.0), "step": jnp.int32(1)}, p)
    mgr.register(p)
    # simulate a crash mid-save: aux.pkl present, orbax arrays dir gone
    shutil.rmtree(os.path.join(p, "arrays"))
    assert os.path.exists(os.path.join(p, "aux.pkl"))
    mgr2 = CheckpointManager(uri, num_to_keep=None)
    latest = mgr2.latest()
    assert latest is not None
    assert int(latest.load_state()["step"]) == 1  # came back from the URI
    storage.delete_at_uri(uri)


def test_manager_unmarked_remote_falls_back_to_older(tmp_path):
    """A remote mirror without the completion marker (crash mid-upload) is
    never restored from; latest() returns the older complete checkpoint."""
    from ray_tpu.train.checkpoint import _REMOTE_MARKER

    uri = "memory://ckpt-test/unmarked"
    storage.delete_at_uri(uri)
    shutil.rmtree(storage.local_staging_dir(uri), ignore_errors=True)
    mgr = CheckpointManager(uri)
    p0 = mgr.new_dir()
    Checkpoint.from_state({"step": jnp.int32(0)}, p0)
    mgr.register(p0)
    # a later "crashed" upload: files present remotely, marker missing
    p1 = mgr.new_dir()
    Checkpoint.from_state({"step": jnp.int32(1)}, p1)
    Checkpoint(p1).to_uri(storage.join_uri(uri, os.path.basename(p1)),
                          write_marker=False)
    mgr._kept.append(p1)
    shutil.rmtree(p1)  # local gone too: only the partial remote remains
    latest = mgr.latest()
    assert latest is not None
    assert int(latest.load_state()["step"]) == 0
    # stray download temps + marker files never break a resuming manager
    os.makedirs(os.path.join(mgr.run_dir, ".dl-checkpoint_000001-123"),
                exist_ok=True)
    mgr2 = CheckpointManager(uri)
    assert mgr2.new_dir().endswith("checkpoint_000002")
    storage.delete_at_uri(uri)


def test_storage_helpers(tmp_path):
    assert storage.is_uri("gs://b/p") and storage.is_uri("memory://x")
    assert not storage.is_uri("/tmp/x") and not storage.is_uri(None)
    assert not storage.is_uri("relative/path")
    # file:// URIs hit the same code path as cloud schemes
    src = tmp_path / "src"
    (src / "sub").mkdir(parents=True)
    (src / "a.txt").write_text("A")
    (src / "sub" / "b.txt").write_text("B")
    uri = f"file://{tmp_path}/dst"
    storage.upload_to_uri(str(src), uri)
    assert sorted(storage.list_at_uri(uri)) == ["a.txt", "sub"]
    out = storage.download_from_uri(uri, str(tmp_path / "out"))
    assert (tmp_path / "out" / "sub" / "b.txt").read_text() == "B"
    storage.delete_at_uri(uri)
    assert storage.list_at_uri(uri) == []


def _uri_loop(config):
    from ray_tpu.train import session

    ck = session.get_checkpoint()
    start = 0
    if ck is not None:
        start = int(ck.load_state(None)["step"])
    w = jnp.arange(4.0) + start
    for i in range(start, config["steps"]):
        w = w + 1.0
        session.report({"step": i, "w0": float(w[0])},
                       state={"step": i + 1, "w": w})
        if config.get("die_at") == i and ck is None:
            os._exit(1)
    return {"done": True}


def test_trainer_uri_storage_path_crash_resume(ray_start_regular, tmp_path):
    """RunConfig.storage_path as a URI: checkpoints mirror to remote
    storage and a crashed worker group resumes from it (ref: air
    RunConfig.storage_path cloud URIs + FailureConfig)."""
    from ray_tpu.train import FailureConfig, JaxTrainer, RunConfig, ScalingConfig

    uri = f"file://{tmp_path}/remote"
    trainer = JaxTrainer(
        _uri_loop, train_loop_config={"steps": 5, "die_at": 2},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=False),
        run_config=RunConfig(name="urirun", storage_path=uri,
                             failure_config=FailureConfig(max_failures=2)))
    result = trainer.fit()
    assert result.ok, result.error
    assert result.metrics["step"] == 4
    # checkpoints landed at the remote URI
    run_uri = f"{uri}/urirun"
    names = [n for n in storage.list_at_uri(run_uri)
             if n.startswith("checkpoint_")]
    assert names, storage.list_at_uri(run_uri)
    assert result.checkpoint is not None
    assert int(result.checkpoint.load_state(None)["step"]) == 5


def _collective_loop(config):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.train import session

    # 2 workers x 8 virtual CPU devices = one 16-device global mesh
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("dp",))
    sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("dp"))
    rank = session.world_rank()
    arrs = [jax.device_put(jnp.full((1, 2), float(rank * 8 + i), jnp.float32),
                           jax.sharding.SingleDeviceSharding(d))
            for i, d in enumerate(jax.local_devices())]
    w = jax.make_array_from_single_device_arrays((16, 2), sh, arrs)
    # every rank calls report(state=...); orbax saves collectively
    session.report({"rank": session.world_rank()},
                   state={"w": w, "step": jnp.int32(1)})
    return {"nd": len(jax.devices())}


def test_trainer_collective_sharded_checkpoint(ray_start_regular, tmp_path):
    """2-worker gang under jax.distributed: session.report(state=...) runs
    the orbax save collectively on all ranks (regression: rank 0 alone
    deadlocked on the multihost barrier)."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    trainer = JaxTrainer(
        _collective_loop, train_loop_config={},
        scaling_config=ScalingConfig(num_workers=2, use_tpu=False),
        run_config=RunConfig(name="collective", storage_path=str(tmp_path)))
    result = trainer.fit()
    assert result.ok, result.error
    assert result.checkpoint is not None
    r = result.checkpoint.load_state()  # host restore on the driver
    assert np.asarray(r["w"]).shape == (16, 2)
    # shard d wrote value d: the global array concatenates all 16 shards
    assert sorted(np.asarray(r["w"])[:, 0].tolist()) == [float(i)
                                                         for i in range(16)]
    assert int(r["step"]) == 1


_MP_WORKER = textwrap.dedent("""
    import os, sys
    pid, nproc, port, outdir = (int(sys.argv[1]), int(sys.argv[2]),
                                sys.argv[3], sys.argv[4])
    import jax
    jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                               num_processes=nproc, process_id=pid)
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.train.checkpoint import Checkpoint

    assert len(jax.devices()) == 8
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("dp",))
    sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("dp"))
    arrs = [jax.device_put(jnp.full((2, 4), float(d.id), jnp.float32),
                           jax.sharding.SingleDeviceSharding(d))
            for d in jax.local_devices()]
    x = jax.make_array_from_single_device_arrays((16, 4), sh, arrs)
    state = {"w": x, "step": jnp.int32(5), "tag": "mh"}
    ck = Checkpoint.from_state(state, os.path.join(outdir, "ck"))
    abstract = {"w": jax.ShapeDtypeStruct((16, 4), jnp.float32, sharding=sh),
                "step": jax.ShapeDtypeStruct((), jnp.int32), "tag": "mh"}
    r = ck.load_state(abstract)
    assert not r["w"].is_fully_addressable      # still globally sharded
    for s in r["w"].addressable_shards:         # each shard has its own value
        assert bool(jnp.all(s.data == float(s.device.id)))
    assert int(r["step"]) == 5 and r["tag"] == "mh"
    print(f"proc {pid} ok", flush=True)
""")


def test_multiprocess_sharded_save_restore(tmp_path):
    """Two jax.distributed processes x 4 CPU devices: a 16x4 array sharded
    over the global 8-device mesh is saved by both processes (orbax writes
    only addressable shards per process) and restored sharded."""
    script = tmp_path / "worker.py"
    script.write_text(_MP_WORKER)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.dirname(os.path.dirname(__file__)))
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(i), "2", str(port),
         str(tmp_path / "out")],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for i in range(2)]
    outs = [p.communicate(timeout=300)[0] for p in procs]
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out}"
        assert f"proc {i} ok" in out
