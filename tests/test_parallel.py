"""Parallel layer tests on the virtual 8-device CPU mesh: mesh construction,
logical shardings, ring/Ulysses attention vs oracle, pipeline vs serial."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel.mesh import MeshSpec, cpu_mesh, make_mesh, mesh_shape
from ray_tpu.parallel.pipeline import make_pipeline
from ray_tpu.parallel.ring_attention import (
    make_ring_attention,
    make_ulysses_attention,
    reference_attention,
)
from ray_tpu.parallel.sharding import ShardingRules, logical_sharding, shard_pytree


def test_mesh_construction(cpu_mesh_devices):
    mesh = make_mesh(MeshSpec(data=2, tensor=4), cpu_mesh_devices)
    shape = mesh_shape(mesh)
    assert shape["data"] == 2 and shape["tensor"] == 4
    assert int(np.prod(list(shape.values()))) == 8


def test_mesh_wildcard(cpu_mesh_devices):
    mesh = make_mesh(MeshSpec(data=-1, tensor=2), cpu_mesh_devices)
    assert mesh_shape(mesh)["data"] == 4


def test_mesh_mismatch_raises(cpu_mesh_devices):
    with pytest.raises(ValueError, match="devices"):
        make_mesh(MeshSpec(data=3, tensor=5), cpu_mesh_devices)


def test_best_devices_raises_rather_than_substituting(monkeypatch):
    """One chip and eight CPU devices: asking for four is an error, not a
    CPU mesh (the silent swap is how a 4-chip program 'ran' on no chips)."""
    from ray_tpu.parallel import mesh as mesh_mod

    class Chip:
        platform = "tpu"

    chip, cpus = Chip(), jax.devices("cpu")
    monkeypatch.setattr(
        mesh_mod.jax, "devices",
        lambda backend=None: cpus if backend == "cpu" else [chip])
    assert mesh_mod.best_devices() == [chip]
    assert mesh_mod.best_devices(1) == [chip]
    with pytest.raises(ValueError, match="need 4 tpu devices, have 1"):
        mesh_mod.best_devices(4)


def test_logical_sharding_rules():
    mesh = cpu_mesh(MeshSpec(data=2, tensor=4))
    rules = ShardingRules()
    s = logical_sharding(mesh, rules, ("embed", "mlp"))
    assert s.spec == P("fsdp", "tensor")
    s2 = logical_sharding(mesh, rules, ("batch", None, "heads"))
    assert s2.spec == P(("data", "fsdp"), None, "tensor")
    with pytest.raises(ValueError, match="unknown logical axis"):
        logical_sharding(mesh, rules, ("bogus",))


def test_shard_pytree_places_arrays():
    mesh = cpu_mesh(MeshSpec(data=2, tensor=4))
    rules = ShardingRules()
    params = {"w": jnp.ones((16, 32)), "b": jnp.ones((32,))}
    logical = {"w": ("embed", "mlp"), "b": ("mlp",)}
    placed = shard_pytree(params, logical, mesh, rules)
    assert placed["w"].sharding.spec == P("fsdp", "tensor")
    np.testing.assert_array_equal(np.asarray(placed["w"]), np.ones((16, 32)))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_oracle(causal):
    mesh = cpu_mesh(MeshSpec(seq=8))
    rng = np.random.default_rng(0)
    b, l, h, d = 2, 32, 4, 16
    q = jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.float32)
    ring = make_ring_attention(mesh, causal=causal)
    out = jax.jit(ring)(q, k, v)
    expect = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5)


def test_ring_attention_with_tensor_heads():
    """Ring over seq composes with head sharding on the tensor axis."""
    mesh = cpu_mesh(MeshSpec(seq=4, tensor=2))
    rng = np.random.default_rng(1)
    b, l, h, d = 2, 16, 4, 8
    q, k, v = (
        jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.float32) for _ in range(3)
    )
    out = jax.jit(make_ring_attention(mesh, causal=True))(q, k, v)
    expect = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_oracle(causal):
    mesh = cpu_mesh(MeshSpec(seq=4))
    rng = np.random.default_rng(2)
    b, l, h, d = 2, 16, 4, 8  # heads divisible by seq axis
    q, k, v = (
        jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.float32) for _ in range(3)
    )
    out = jax.jit(make_ulysses_attention(mesh, causal=causal))(q, k, v)
    expect = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5)


def test_pipeline_matches_serial():
    mesh = cpu_mesh(MeshSpec(pipe=4, data=2))
    n_stages, n_mb, mb, dim = 4, 6, 4, 8
    rng = np.random.default_rng(3)
    weights = jnp.asarray(rng.normal(size=(n_stages, dim, dim)) * 0.3, jnp.float32)
    biases = jnp.asarray(rng.normal(size=(n_stages, dim)) * 0.1, jnp.float32)
    # Layout contract: [microbatch, num_microbatches, ...] — the microbatch
    # INDEX trails the batch-sharded dim (parallel.pipeline docstring).
    x = jnp.asarray(rng.normal(size=(mb, n_mb, dim)), jnp.float32)

    def stage_fn(params, h):
        w, b = params
        return jnp.tanh(h @ w + b)

    pipeline = make_pipeline(stage_fn, mesh, num_microbatches=n_mb)
    out = jax.jit(pipeline)((weights, biases), x)

    expect = x
    for s in range(n_stages):
        expect = jnp.tanh(expect @ weights[s] + biases[s])
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-5)


def test_collectives_between_actors(ray_start_regular):
    """The §5.8 eager collective contract, exercised from real actors."""
    rt = ray_start_regular
    from ray_tpu.parallel import collectives as col

    world = 4

    @rt.remote(max_concurrency=1)
    class Rank:
        def __init__(self, rank):
            self.rank = rank
            col.init_collective_group(world, rank, backend="local", group_name="g1")

        def do_allreduce(self):
            return col.allreduce(np.full(4, self.rank + 1.0), op="sum", group_name="g1")

        def do_broadcast(self):
            return col.broadcast(np.arange(3.0) if self.rank == 0 else None, 0, "g1")

        def do_allgather(self):
            return col.allgather(np.full(2, float(self.rank)), "g1")

        def do_reducescatter(self):
            return col.reducescatter(np.arange(8.0), op="sum", group_name="g1")

        def do_alltoall(self):
            return col.alltoall(np.full(4, float(self.rank)), "g1")

    ranks = [Rank.remote(i) for i in range(world)]
    out = rt.get([r.do_allreduce.remote() for r in ranks])
    for o in out:
        np.testing.assert_array_equal(o, np.full(4, 1.0 + 2 + 3 + 4))
    out = rt.get([r.do_broadcast.remote() for r in ranks])
    for o in out:
        np.testing.assert_array_equal(o, np.arange(3.0))
    out = rt.get([r.do_allgather.remote() for r in ranks])
    for o in out:
        assert len(o) == world
        np.testing.assert_array_equal(o[2], np.full(2, 2.0))
    out = rt.get([r.do_reducescatter.remote() for r in ranks])
    np.testing.assert_array_equal(out[1], np.array([2.0 * world * 1, 3.0 * world]))
    out = rt.get([r.do_alltoall.remote() for r in ranks])
    np.testing.assert_array_equal(out[3], np.array([0.0, 1.0, 2.0, 3.0]))


def test_collectives_send_recv(ray_start_regular):
    rt = ray_start_regular
    from ray_tpu.parallel import collectives as col

    @rt.remote
    class Peer:
        def __init__(self, rank):
            col.init_collective_group(2, rank, group_name="p2p")
            self.rank = rank

        def send_it(self):
            col.send(np.array([7.0, 8.0]), dst_rank=1, group_name="p2p")
            return True

        def recv_it(self):
            return col.recv(src_rank=0, group_name="p2p", timeout=10)

    a, b = Peer.remote(0), Peer.remote(1)
    r = b.recv_it.remote()
    rt.get(a.send_it.remote())
    np.testing.assert_array_equal(rt.get(r), np.array([7.0, 8.0]))


class TestCollectiveRoundStress:
    def test_back_to_back_allreduce_rounds(self, ray_start_regular):
        """Regression: a fast rank re-entering round k+1 while a straggler
        withdraws from round k must not corrupt slots (mixed-epoch race)."""
        import numpy as np

        import ray_tpu
        from ray_tpu.parallel import collectives

        @ray_tpu.remote
        class Member:
            def __init__(self, rank, world):
                from ray_tpu.parallel import collectives as c

                c.init_collective_group(world, rank, group_name="stress")
                self.rank = rank

            def run_rounds(self, n):
                from ray_tpu.parallel import collectives as c

                out = []
                for i in range(n):
                    # different shape per round: mixing rounds would blow up
                    shape = (2 + i % 3, 4)
                    val = np.full(shape, float(self.rank + 1))
                    out.append(float(c.allreduce(val, group_name="stress").sum()))
                return out

        world = 3
        members = [Member.remote(r, world) for r in range(world)]
        results = ray_tpu.get([m.run_rounds.remote(40) for m in members])
        assert results[0] == results[1] == results[2]
        # sum of (1+2+3) over each round's element count
        expected = [6.0 * ((2 + i % 3) * 4) for i in range(40)]
        assert results[0] == expected
        collectives.destroy_collective_group("stress")


@pytest.mark.skipif(not hasattr(jax, "shard_map"),
                    reason="backend='device' compiles a jax.shard_map psum; "
                           "without it one rank dies at compile and the rest "
                           "burn the full collective timeout")
def test_device_backend_allreduce_stays_on_device():
    """backend="device": the eager NCCL-tier analog (§5.8) — actor-held
    DEVICE arrays are reduced by a COMPILED psum over the devices they
    already live on; each rank's result lands on its own device, no host
    round trip. Exercised over 4 of the virtual CPU devices."""
    import threading

    from ray_tpu.parallel import collectives as col

    world = 4
    devices = jax.devices()[:world]
    results = {}
    errors = []

    def member(rank):
        try:
            col.init_collective_group(world, rank, backend="device",
                                      group_name="dev-g")
            x = jax.device_put(
                jnp.full((8,), float(rank + 1)), devices[rank])
            out = col.allreduce(x, op="sum", group_name="dev-g")
            results[rank] = out
        except Exception as e:  # noqa: BLE001
            errors.append((rank, e))

    threads = [threading.Thread(target=member, args=(r,), daemon=True)
               for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    # daemon=True: a wedged member must FAIL here, not hang interpreter
    # exit at threading._shutdown.
    assert not any(t.is_alive() for t in threads), "member thread hung"
    assert not errors, errors
    expect = sum(range(1, world + 1))  # 1+2+3+4
    for rank in range(world):
        out = results[rank]
        assert isinstance(out, jax.Array)
        np.testing.assert_allclose(np.asarray(out), np.full((8,), expect))
        # The result shard lives on the rank's OWN device.
        assert list(out.devices())[0] == devices[rank], (
            rank, out.devices())
    col.destroy_collective_group("dev-g")


def test_device_backend_mean_and_colocated_fallback():
    import threading

    from ray_tpu.parallel import collectives as col

    world = 2
    dev = jax.devices()[0]  # BOTH ranks on one device: compiled fallback
    results = {}

    def member(rank):
        col.init_collective_group(world, rank, backend="device",
                                  group_name="dev-co")
        x = jax.device_put(jnp.full((4,), float(rank)), dev)
        results[rank] = col.allreduce(x, op="mean", group_name="dev-co")

    threads = [threading.Thread(target=member, args=(r,), daemon=True)
               for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads), "member thread hung"
    for rank in range(world):
        np.testing.assert_allclose(np.asarray(results[rank]),
                                   np.full((4,), 0.5))
    col.destroy_collective_group("dev-co")


@pytest.mark.skipif(not hasattr(jax, "shard_map"),
                    reason="backend='device' compiles a jax.shard_map psum; "
                           "without it one rank dies at compile and the rest "
                           "burn the full collective timeout")
def test_device_backend_from_actors(ray_start_regular):
    """backend="device" through REAL actors (in-process runtime: actors
    share the process, each pins its array to a different virtual
    device) — the eager §5.8 device-tier contract end-to-end."""
    rt = ray_start_regular
    from ray_tpu.parallel import collectives as col

    world = 4

    @rt.remote(max_concurrency=1)
    class DeviceRank:
        def __init__(self, rank):
            self.rank = rank
            self.dev = jax.devices()[rank]
            col.init_collective_group(world, rank, backend="device",
                                      group_name="adev")

        def reduce(self):
            x = jax.device_put(jnp.full((16,), float(self.rank + 1)),
                               self.dev)
            out = col.allreduce(x, op="sum", group_name="adev")
            return (np.asarray(out),
                    list(out.devices())[0] == self.dev)

    ranks = [DeviceRank.remote(i) for i in range(world)]
    results = rt.get([r.reduce.remote() for r in ranks], timeout=300)
    expect = np.full((16,), float(sum(range(1, world + 1))))
    for arr, on_own_device in results:
        np.testing.assert_allclose(arr, expect)
        assert on_own_device
    col.destroy_collective_group("adev")


class TestBroadcastSubtreeAcks:
    """_broadcast republisher ack accounting (ADVICE r5): a non-root rank
    that publishes the payload to shm must expect acks from its binomial
    SUBTREE only — publishing with consumers=n-1 would leave shm_done
    forever short of zero and leak the backing object."""

    def test_subtree_consumer_counts(self):
        from ray_tpu.parallel.collectives import _DistributedGroup

        f = _DistributedGroup._bc_subtree_consumers

        def children(rel, n):
            out, k = [], 1
            while k < n:  # mirrors _broadcast's child enumeration
                if rel < k and rel + k < n:
                    out.append(rel + k)
                k *= 2
            return out

        for n in range(1, 33):
            # Root's subtree covers the whole tree: n-1 descendants.
            assert f(0, n) == n - 1
            for r in range(n):
                # Recursive consistency: my acks = each child's delivery
                # plus everything that child forwards.
                assert f(r, n) == sum(1 + f(c, n) for c in children(r, n))
        # Spot checks in the n=8 binomial tree: 1 -> {3, 5}, 3 -> {7}.
        assert f(1, 8) == 3
        assert f(2, 8) == 1  # 2 -> {6}
        assert f(4, 8) == 0  # leaf

    def test_republisher_publishes_with_subtree_count(self):
        """Rank 1 of 4 (src=0) receives by socket (root's publish failed),
        republishes to shm for its children: consumers must equal its
        subtree size (1 = rank 3), not n-1 = 3."""
        from ray_tpu.parallel.collectives import _DistributedGroup

        g = object.__new__(_DistributedGroup)
        g.world_size = 4
        g.rank = 1
        g._addrs = {i: f"addr{i}" for i in range(4)}
        g._stores = {i: "storeA" for i in range(4)}
        g._all_same_store = True
        g._shm = object()  # only truthiness is checked on this path

        published = {}

        def publish(arr, consumers):
            published["consumers"] = consumers
            return b"k" * 16

        g._publish_shm = publish

        class _Fut:
            def result(self, timeout=None):
                return True

        sent = []

        class _Peer:
            def call_async(self, method, *args):
                sent.append((method, args))
                return _Fut()

        class _Peers:
            def get(self, addr):
                return _Peer()

        g._peers = _Peers()
        payload = np.ones(_DistributedGroup.SHM_MIN_BYTES // 8 + 16,
                          dtype=np.float64)
        g._service = None  # not used on this path
        g._recv = lambda tag, timeout=120.0: payload  # socket delivery
        out = g._broadcast(seq=9, value=None, src=0)
        assert np.array_equal(out, payload)
        assert published["consumers"] == \
            _DistributedGroup._bc_subtree_consumers(1, 4) == 1
        # The forward to the child went by shm key.
        assert sent and sent[0][0] == "deliver_shm"
