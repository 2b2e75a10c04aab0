"""ReplicaRouter + emit-thread + mesh-shape derivation: the single-device
lane of the mesh-sharded serving stack (``tests/test_sharded_serve.py``
is the multi-device lane).

The router is pure host-side orchestration — engines on ONE device
exercise every routing/merging/stats path it has, so these run in tier-1.
"""

import threading

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import mesh as MX
from repro.models import transformer as T
from repro.serve import (
    FinishEvent,
    ReplicaRouter,
    Request,
    ServeConfig,
    ServeEngine,
    TokenEvent,
    stream_async,
)

# ---------------------------------------------------------------------------
# mesh-shape derivation (satellite: no hardcoded (16, 16))
# ---------------------------------------------------------------------------


def test_derive_mesh_shape_reproduces_production_defaults():
    assert MX.derive_mesh_shape(256) == ((16, 16), ("data", "model"))
    assert MX.derive_mesh_shape(512, multi_pod=True) == \
        ((2, 16, 16), ("pod", "data", "model"))


@pytest.mark.parametrize("n,shape", [
    (1, (1, 1)), (2, (1, 2)), (8, (1, 8)), (16, (1, 16)),
    (32, (2, 16)), (48, (3, 16)), (512, (32, 16)),
    (6, (3, 2)), (12, (3, 4)),
])
def test_derive_mesh_shape_any_device_count(n, shape):
    got, axes = MX.derive_mesh_shape(n)
    assert got == shape
    assert axes == ("data", "model")
    assert int(np.prod(got)) == n


def test_derive_mesh_shape_odd_counts():
    # odd counts get model=1 (no power of two divides them)
    assert MX.derive_mesh_shape(7) == ((7, 1), ("data", "model"))
    with pytest.raises(ValueError, match="even device count"):
        MX.derive_mesh_shape(7, multi_pod=True)
    with pytest.raises(ValueError, match="at least one device"):
        MX.derive_mesh_shape(0)


def test_make_production_mesh_derives_and_validates():
    n = jax.device_count()
    mesh = MX.make_production_mesh()
    assert mesh.size == n
    with pytest.raises(ValueError, match="devices"):
        MX.make_production_mesh(shape=(n + 1, 1))
    with pytest.raises(ValueError, match="one entry per axis"):
        MX.make_production_mesh(shape=(n,))


def test_serve_meshes_partitions_devices():
    meshes = MX.serve_meshes(1, 1)
    assert len(meshes) == 1 and meshes[0].axis_names == ("model",)
    need = jax.device_count() + 1
    with pytest.raises(ValueError, match="needs"):
        MX.serve_meshes(need, 1)
    with pytest.raises(ValueError, match="must be >= 1"):
        MX.serve_meshes(0, 1)


# ---------------------------------------------------------------------------
# router over single-device engines
# ---------------------------------------------------------------------------


def _model():
    cfg = get_config("smollm-360m", smoke=True)
    return cfg, T.init_params(cfg, jax.random.PRNGKey(0))


def _reqs():
    # seeds pinned: routing changes session-local rids, and the default
    # sampling-key id is the rid (see the ReplicaRouter docstring)
    specs = [([3, 5, 7], 6, 0.0), ([11, 13, 2, 9], 2, 0.8),
             ([17, 19, 23], 4, 0.0), ([29, 31], 3, 0.9),
             ([37, 41, 43, 47, 53], 5, 0.0)]
    return [Request(np.asarray(p, np.int32), max_new=m, temperature=t,
                    seed=i)
            for i, (p, m, t) in enumerate(specs)]


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def single(model):
    cfg, params = model
    return ServeEngine(cfg, params, ServeConfig(max_batch=2, max_seq=64))


@pytest.fixture(scope="module")
def router(model):
    cfg, params = model
    return ReplicaRouter([
        ServeEngine(cfg, params, ServeConfig(max_batch=2, max_seq=64))
        for _ in range(2)])


def _results(obj):
    return {r.rid: tuple(int(t) for t in r.tokens)
            for r in obj.last_results}


def test_router_matches_single_engine(single, router):
    single.serve(_reqs())
    ref = _results(single)
    outs = router.serve(_reqs())
    assert _results(router) == ref
    assert [tuple(int(t) for t in o) for o in outs] == \
        [ref[i] for i in range(len(ref))]
    # both replicas actually served work and the merged stats add up
    st = router.last_serve_stats
    assert st["replicas"] == 2
    assert st["requests"] == len(ref)
    assert all(p["requests"] >= 1 for p in st["per_replica"])
    assert sum(st["finish_reasons"].values()) == len(ref)


def test_router_second_session_resets_global_rids(single, router):
    single.serve(_reqs())
    ref = _results(single)
    router.serve(_reqs())
    assert _results(router) == ref, \
        "second router session must restart global rids at 0"


def test_router_least_loaded_balances(model):
    cfg, params = model
    router = ReplicaRouter([
        ServeEngine(cfg, params, ServeConfig(max_batch=2, max_seq=64))
        for _ in range(2)])
    for r in _reqs()[:4]:
        router.submit(r)
    # 4 submissions to idle 2-slot replicas: least-loaded alternates
    assert router.loads() == [2, 2]
    for _ in router.serve_stream():
        pass
    assert router.loads() == [0, 0]


def test_router_round_robin_policy(model):
    cfg, params = model
    router = ReplicaRouter([
        ServeEngine(cfg, params, ServeConfig(max_batch=2, max_seq=64))
        for _ in range(2)], policy="round_robin")
    gids = [router.submit(r) for r in _reqs()[:4]]
    assert gids == [0, 1, 2, 3]
    assert [router._map[g][0] for g in gids] == [0, 1, 0, 1]
    for _ in router.serve_stream():
        pass


def test_router_rejects_bad_args(model):
    cfg, params = model
    with pytest.raises(ValueError, match="at least one engine"):
        ReplicaRouter([])
    eng = ServeEngine(cfg, params, ServeConfig(max_batch=2, max_seq=64))
    with pytest.raises(ValueError, match="unknown policy"):
        ReplicaRouter([eng], policy="random")


def test_unsharded_replica_on_its_device(model, single):
    """``device=`` commits an unsharded replica's params and cache to that
    device without changing its tokens."""
    cfg, params = model
    dev = jax.devices()[-1]
    eng = ServeEngine(cfg, params, ServeConfig(max_batch=2, max_seq=64),
                      device=dev)
    assert {d for a in jax.tree.leaves(eng.params) for d in a.devices()} \
        == {dev}
    single.serve(_reqs())
    eng.serve(_reqs())
    assert _results(eng) == _results(single)
    assert {d for a in jax.tree.leaves(eng._st.cache)
            for d in a.devices()} == {dev}
    with pytest.raises(ValueError, match="not both"):
        ServeEngine(cfg, params, mesh=MX.serve_meshes(1, 1)[0], device=dev)


def test_router_mid_stream_submit(single, router):
    """Submissions made while consuming the merged stream are routed and
    finish with the same tokens as a single engine serving them all."""
    first, late = _reqs()[:3], _reqs()[3:]
    single.serve(_reqs())
    ref = _results(single)

    gids = [router.submit(r) for r in first]
    out, n, added = {}, 0, False
    stream = router.serve_stream()
    for ev in stream:
        if isinstance(ev, TokenEvent):
            n += 1
            if n == 3 and not added:
                added = True
                gids += [router.submit(r) for r in late]
        elif isinstance(ev, FinishEvent):
            out[ev.rid] = tuple(int(t) for t in ev.result.tokens)
    assert len(out) == len(ref)
    assert [out[g] for g in gids] == [ref[i] for i in range(len(ref))]


# ---------------------------------------------------------------------------
# emit worker thread
# ---------------------------------------------------------------------------


def test_stream_async_same_events_as_sync(single):
    single.serve(_reqs())
    ref = _results(single)
    for r in _reqs():
        single.submit(r)
    main_thread = threading.current_thread()
    seen_threads = set()
    out = {}
    for ev in stream_async(single, backlog=4):
        seen_threads.add(threading.current_thread())
        if isinstance(ev, FinishEvent):
            out[ev.rid] = tuple(int(t) for t in ev.result.tokens)
    assert out == ref
    # events were CONSUMED on the caller's thread (production on worker)
    assert seen_threads == {main_thread}


def test_stream_async_tiny_backlog_backpressures_not_drops(single):
    single.serve(_reqs())
    ref = _results(single)
    for r in _reqs():
        single.submit(r)
    events = list(stream_async(single, backlog=1))
    finals = {ev.rid: tuple(int(t) for t in ev.result.tokens)
              for ev in events if isinstance(ev, FinishEvent)}
    assert finals == ref
    n_tokens = sum(isinstance(ev, TokenEvent) for ev in events)
    assert n_tokens == sum(len(v) for v in ref.values())


def test_stream_async_propagates_errors():
    class Exploding:
        def serve_stream(self, strict=None):
            yield TokenEvent(0, 1)
            raise RuntimeError("engine fault mid-stream")

    it = stream_async(Exploding(), backlog=2)
    assert next(it) == TokenEvent(0, 1)
    with pytest.raises(RuntimeError, match="engine fault mid-stream"):
        next(it)


def test_stream_async_rejects_bad_backlog(single):
    with pytest.raises(ValueError, match="backlog"):
        next(stream_async(single, backlog=0))


def test_stream_async_abandoned_consumer_stops_worker(single):
    single.serve(_reqs())          # leaves the engine drained
    for r in _reqs():
        single.submit(r)
    it = stream_async(single, backlog=2)
    next(it)
    it.close()                     # abandon: worker must stop, not leak
    live = [t for t in threading.enumerate() if t.name == "serve-emit"]
    for t in live:
        t.join(timeout=10.0)
    assert not any(t.is_alive() for t in live)
    # drain the engine so the module leaves no half-open session
    for _ in single.serve_stream():
        pass
