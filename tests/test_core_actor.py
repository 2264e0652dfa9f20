"""Actor semantics: creation, ordering, named actors, restart, async actors.

Reference test model: python/ray/tests/test_actor*.py.
"""

import os
import time

import pytest

import ray_tpu
from ray_tpu.core.status import ActorDiedError, TaskError


@ray_tpu.remote
class Counter:
    def __init__(self, start=0):
        self.n = start

    def incr(self, by=1):
        self.n += by
        return self.n

    def value(self):
        return self.n

    def fail(self):
        raise RuntimeError("actor method failure")

    def pid(self):
        import os

        return os.getpid()


def test_actor_basic(ray_start_regular):
    c = Counter.remote(10)
    assert ray_tpu.get(c.incr.remote()) == 11
    assert ray_tpu.get(c.incr.remote(5)) == 16
    assert ray_tpu.get(c.value.remote()) == 16


def test_actor_ordering(ray_start_regular):
    c = Counter.remote()
    refs = [c.incr.remote() for _ in range(20)]
    assert ray_tpu.get(refs) == list(range(1, 21))


def test_actor_method_error(ray_start_regular):
    c = Counter.remote()
    with pytest.raises(TaskError):
        ray_tpu.get(c.fail.remote())
    # actor still alive after method error
    assert ray_tpu.get(c.value.remote()) == 0


def test_two_actors_isolated(ray_start_regular):
    a, b = Counter.remote(), Counter.remote(100)
    ray_tpu.get([a.incr.remote(), b.incr.remote()])
    assert ray_tpu.get(a.value.remote()) == 1
    assert ray_tpu.get(b.value.remote()) == 101


def test_named_actor(ray_start_regular):
    Counter.options(name="counter1").remote(5)
    h = ray_tpu.get_actor("counter1")
    assert ray_tpu.get(h.value.remote()) == 5


def test_actor_handle_passed_to_task(ray_start_regular):
    @ray_tpu.remote
    def bump(handle):
        return ray_tpu.get(handle.incr.remote())

    c = Counter.remote()
    assert ray_tpu.get(bump.remote(c)) == 1
    assert ray_tpu.get(c.value.remote()) == 1


def test_kill_actor(ray_start_regular):
    c = Counter.remote()
    assert ray_tpu.get(c.value.remote()) == 0
    ray_tpu.kill(c)
    time.sleep(0.5)
    with pytest.raises((ActorDiedError, ray_tpu.exceptions.ActorUnavailableError)):
        ray_tpu.get(c.value.remote())


def test_actor_restart(ray_start_regular):
    @ray_tpu.remote
    class Flaky:
        def __init__(self):
            self.calls = 0

        def die(self):
            import os

            os._exit(1)

        def ping(self):
            return "pong"

    # max_task_retries=0: the `die` call must NOT be re-executed after the
    # restart (it would kill the fresh instance and exhaust the budget —
    # matching the reference's retry semantics, actor.py:332-351).
    a = Flaky.options(max_restarts=1, max_task_retries=0).remote()
    assert ray_tpu.get(a.ping.remote()) == "pong"
    try:
        ray_tpu.get(a.die.remote())
    except Exception:
        pass
    # GCS restarts the actor; later calls land on the new instance
    deadline = time.time() + 30
    ok = False
    while time.time() < deadline:
        try:
            if ray_tpu.get(a.ping.remote()) == "pong":
                ok = True
                break
        except (ray_tpu.exceptions.ActorUnavailableError,
                ray_tpu.exceptions.ActorDiedError):
            time.sleep(0.3)
    assert ok, "actor did not come back after restart"


def test_async_actor(ray_start_regular):
    @ray_tpu.remote
    class AsyncWorker:
        async def work(self, x):
            import asyncio

            await asyncio.sleep(0.05)
            return x * 2

    a = AsyncWorker.options(max_concurrency=4).remote()
    t0 = time.time()
    refs = [a.work.remote(i) for i in range(8)]
    assert ray_tpu.get(refs) == [i * 2 for i in range(8)]
    # 8 calls of 50ms at concurrency 4 should take well under 8*50ms
    assert time.time() - t0 < 3.0


def test_actor_in_placement_context_gets_big_object(ray_start_regular):
    import numpy as np

    @ray_tpu.remote
    class Holder:
        def __init__(self):
            self.arr = None

        def load(self, arr):
            self.arr = arr
            return float(arr.sum())

    h = Holder.remote()
    big = np.ones(400_000, dtype=np.float64)
    ref = ray_tpu.put(big)
    assert ray_tpu.get(h.load.remote(ref)) == 400_000.0


@pytest.fixture
def ray_short_start_deadline():
    # worker start and lease bounds far below the actors' __init__ times
    # below: one GCS creation attempt lasts 1 + 1 + 10 s
    ray_tpu.init(num_cpus=4, _system_config={
        "health_check_period_s": 0.2, "worker_start_timeout_s": 1.0,
        "worker_lease_timeout_s": 1.0})
    yield
    ray_tpu.shutdown()


def test_slow_init_is_not_bounded_by_worker_start(ray_short_start_deadline,
                                                  tmp_path):
    """An __init__ that outlasts the worker-start bound AND one GCS
    creation attempt (a replica loading a model) runs once, to its end:
    it is not killed and created again."""
    log = tmp_path / "inits"

    @ray_tpu.remote(num_cpus=1)
    class Slow:
        def __init__(self, path):
            import os

            with open(path, "a") as f:
                f.write(f"{os.getpid()}\n")
            time.sleep(14.0)

        def ping(self):
            return "pong"

    a = Slow.remote(str(log))
    assert ray_tpu.get(a.ping.remote(), timeout=60) == "pong"
    assert len(log.read_text().split()) == 1, log.read_text()


def test_kill_reaches_an_actor_still_in_init(ray_short_start_deadline,
                                             tmp_path):
    """The deadline on __init__ is its caller's: ray_tpu.kill on an actor
    that has not come up stops the worker running it, frees what it held,
    and the actor is dead — not created again."""
    log = tmp_path / "pid"

    @ray_tpu.remote(num_cpus=4)
    class Hangs:
        def __init__(self, path):
            import os

            with open(path, "a") as f:
                f.write(f"{os.getpid()}\n")
            time.sleep(600)

        def ping(self):
            return "pong"

    a = Hangs.remote(str(log))
    deadline = time.time() + 30
    while not log.exists() and time.time() < deadline:
        time.sleep(0.1)
    pid = int(log.read_text().split()[0])
    ray_tpu.kill(a)
    with pytest.raises((ActorDiedError,
                        ray_tpu.exceptions.ActorUnavailableError)):
        ray_tpu.get(a.ping.remote(), timeout=30)
    deadline = time.time() + 10
    while os.path.exists(f"/proc/{pid}") and time.time() < deadline:
        time.sleep(0.1)
    assert not os.path.exists(f"/proc/{pid}"), "worker in __init__ survived"
    # all four CPUs are free again, and nothing re-ran __init__
    b = Counter.options(num_cpus=4).remote(1)
    assert ray_tpu.get(b.value.remote(), timeout=30) == 1
    assert len(log.read_text().split()) == 1
