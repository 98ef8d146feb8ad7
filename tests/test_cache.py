"""SchedulerCache assume/confirm/forget/expire state machine tests —
modeled on the reference's cache_test.go (878 lines: TestAssumePodScheduled,
TestExpirePod, TestAddPodWillConfirm, TestForgetPod, ...)."""

from kubernetes_tpu.api.types import make_node, make_pod
from kubernetes_tpu.state.cache import SchedulerCache
from tests.helpers import Gi


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def make_cache(ttl=30.0):
    clock = FakeClock()
    cache = SchedulerCache(ttl_seconds=ttl, now=clock)
    cache.add_node(make_node("n1"))
    cache.add_node(make_node("n2"))
    return cache, clock


def test_assume_adds_resources():
    cache, _ = make_cache()
    pod = make_pod("p1", cpu=1000, memory=1 * Gi)
    pod.node_name = "n1"
    cache.assume_pod(pod)
    infos = cache.node_infos()
    assert infos["n1"].requested.milli_cpu == 1000
    assert len(infos["n1"].pods) == 1
    assert cache.is_assumed("default/p1")


def test_expire_releases_assumed():
    cache, clock = make_cache(ttl=30.0)
    pod = make_pod("p1", cpu=1000)
    pod.node_name = "n1"
    cache.assume_pod(pod)
    cache.finish_binding(pod)
    clock.t = 31.0
    expired = cache.cleanup_assumed()
    assert expired == ["default/p1"]
    assert cache.node_infos()["n1"].requested.milli_cpu == 0


def test_unfinished_binding_never_expires():
    cache, clock = make_cache(ttl=30.0)
    pod = make_pod("p1", cpu=1000)
    pod.node_name = "n1"
    cache.assume_pod(pod)  # no finish_binding
    clock.t = 1e9
    assert cache.cleanup_assumed() == []
    assert cache.node_infos()["n1"].requested.milli_cpu == 1000


def test_add_confirms_assumed():
    cache, clock = make_cache(ttl=30.0)
    pod = make_pod("p1", cpu=1000)
    pod.node_name = "n1"
    cache.assume_pod(pod)
    cache.finish_binding(pod)
    cache.add_pod(pod)  # informer confirmation
    assert not cache.is_assumed("default/p1")
    clock.t = 1e9
    assert cache.cleanup_assumed() == []  # confirmed pods never expire
    assert cache.node_infos()["n1"].requested.milli_cpu == 1000


def test_cleanup_assumed_walks_the_states_in_place():
    """The expiry walk copies nothing per cached pod (a copy of the items
    was one young tuple per pod, on every empty loop step), and still
    expires exactly the assumed pods past their deadline."""
    import tracemalloc
    cache, clock = make_cache(ttl=30.0)
    for i in range(20_000):
        pod = make_pod(f"c{i}", cpu=1)
        pod.node_name = "n1"
        cache.add_pod(pod)
    late = make_pod("late", cpu=1000)
    late.node_name = "n2"
    cache.assume_pod(late)
    cache.finish_binding(late)
    clock.t = 31.0
    tracemalloc.start()
    try:
        base = tracemalloc.get_traced_memory()[0]
        expired = cache.cleanup_assumed()
        peak = tracemalloc.get_traced_memory()[1] - base
    finally:
        tracemalloc.stop()
    assert expired == ["default/late"]
    assert cache.pod_count() == 20_000
    assert peak < 64 * 1024, peak      # the copy read 1.28 MB


def test_add_moves_pod_when_bound_elsewhere():
    cache, _ = make_cache()
    pod = make_pod("p1", cpu=1000)
    pod.node_name = "n1"
    cache.assume_pod(pod)
    confirmed = make_pod("p1", cpu=1000)
    confirmed.node_name = "n2"  # another scheduler won
    cache.add_pod(confirmed)
    assert cache.node_infos()["n1"].requested.milli_cpu == 0
    assert cache.node_infos()["n2"].requested.milli_cpu == 1000


def test_forget_undoes_assume():
    cache, _ = make_cache()
    pod = make_pod("p1", cpu=1000)
    pod.node_name = "n1"
    cache.assume_pod(pod)
    cache.forget_pod(pod)
    assert cache.node_infos()["n1"].requested.milli_cpu == 0
    assert cache.pod_count() == 0


def test_remove_pod():
    cache, _ = make_cache()
    pod = make_pod("p1", cpu=500)
    pod.node_name = "n1"
    cache.add_pod(pod)
    cache.remove_pod(pod)
    assert cache.node_infos()["n1"].requested.milli_cpu == 0


def test_update_pod_moves_resources():
    cache, _ = make_cache()
    p_old = make_pod("p1", cpu=500)
    p_old.node_name = "n1"
    cache.add_pod(p_old)
    p_new = make_pod("p1", cpu=800)
    p_new.node_name = "n1"
    cache.update_pod(p_old, p_new)
    assert cache.node_infos()["n1"].requested.milli_cpu == 800


def test_generation_counters_drive_deltas():
    cache, _ = make_cache()
    g0 = cache.node_infos()["n1"].generation
    pod = make_pod("p1", cpu=100)
    pod.node_name = "n1"
    cache.add_pod(pod)
    infos = cache.node_infos()
    assert infos["n1"].generation > g0
    # untouched node unchanged
    assert infos["n2"].generation == cache.node_infos()["n2"].generation
