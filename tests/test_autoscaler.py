"""Autoscaler reconciler: scale-up from demand, floors, idle scale-down.

Reference: ``python/ray/autoscaler/v2/scheduler.py:624`` and
``autoscaler/v2/tests/test_scheduler.py`` style — but end-to-end: the
LocalNodeProvider launches REAL raylets that join the GCS and run the
queued work.
"""

import time

import pytest

import ray_tpu
from ray_tpu.autoscaler import Autoscaler, LocalNodeProvider, NodeTypeConfig
from ray_tpu.autoscaler.sdk import REQUEST_KEY
from ray_tpu.cluster_utils import Cluster


class _FakeProvider:
    def __init__(self):
        self.launched = []
        self.terminated = []

    def create_node(self, node_type, resources):
        self.launched.append(node_type)
        return f"i-{len(self.launched)}"

    def terminate_node(self, iid):
        self.terminated.append(iid)

    def non_terminated_nodes(self):
        return {f"i-{i+1}": t for i, t in enumerate(self.launched)
                if f"i-{i+1}" not in self.terminated}

    def node_id_of(self, iid):
        return None


def test_reconcile_unit_launches_for_unmet_demand():
    """Pure decision logic: pending shape with no capacity -> launch the
    smallest fitting type, respecting max_workers."""
    nodes = [{
        "node_id": "a", "state": "ALIVE",
        "resources": {"available": {"CPU": 0.0}, "total": {"CPU": 1.0}},
        "pending_demand": [{"shape": {"CPU": 2.0}, "count": 3}],
    }]

    def gcs_call(method, payload):
        if method == "GetAllNodes":
            return {"nodes": nodes}
        if method == "ListPlacementGroups":
            return {"placement_groups": []}
        if method == "KvGet":
            return {"value": None}
        raise AssertionError(method)

    provider = _FakeProvider()
    scaler = Autoscaler(
        gcs_call, provider,
        [NodeTypeConfig("small", {"CPU": 2.0}, max_workers=2),
         NodeTypeConfig("big", {"CPU": 8.0}, max_workers=1)],
        launch_cooldown_s=0.0,
    )
    decision = scaler.reconcile_once()
    # 3x CPU:2 demand -> two "small" (cap) then one "big" absorbs the rest.
    assert decision.launch == ["small", "small", "big"]
    assert provider.launched == ["small", "small", "big"]


@pytest.fixture()
def scaling_cluster():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    c = Cluster(
        initialize_head=True,
        head_node_args={"num_cpus": 1},
        _system_config={"health_check_failure_threshold": 5},
    )
    ray_tpu.init(address=c.address, num_cpus=0)
    provider = LocalNodeProvider(c)

    def gcs_call(method, payload):
        return c._loop.run_sync(getattr(c.gcs, f"handle_{method}")(payload))

    yield c, provider, gcs_call
    ray_tpu.shutdown()
    c.shutdown()


def test_scale_up_runs_infeasible_tasks_then_scales_down(scaling_cluster):
    """Tasks too big for any live node report demand via heartbeats; the
    reconciler launches fitting nodes, the tasks run there, and the nodes
    are terminated once idle."""
    c, provider, gcs_call = scaling_cluster
    scaler = Autoscaler(
        gcs_call, provider,
        [NodeTypeConfig("cpu-4", {"CPU": 4.0}, min_workers=0, max_workers=2)],
        idle_timeout_s=2.0, launch_cooldown_s=0.5,
    )
    scaler.start(period_s=0.5)
    try:

        @ray_tpu.remote(resources={"CPU": 4.0})
        def heavy(i):
            return i * 10

        results = ray_tpu.get([heavy.remote(i) for i in range(3)], timeout=60)
        assert sorted(results) == [0, 10, 20]
        assert provider.non_terminated_nodes(), "autoscaler never launched a node"

        deadline = time.monotonic() + 40
        while provider.non_terminated_nodes() and time.monotonic() < deadline:
            time.sleep(0.5)
        assert not provider.non_terminated_nodes(), "idle nodes were not terminated"
    finally:
        scaler.stop()


def test_request_resources_floor(scaling_cluster):
    """An explicit capacity floor launches nodes with zero load, and
    clearing it lets them scale back down."""
    from ray_tpu.autoscaler import request_resources

    c, provider, gcs_call = scaling_cluster
    scaler = Autoscaler(
        gcs_call, provider,
        [NodeTypeConfig("cpu-2", {"CPU": 2.0}, max_workers=4)],
        idle_timeout_s=1.5, launch_cooldown_s=0.2,
    )
    scaler.start(period_s=0.4)
    try:
        request_resources([{"CPU": 2.0}, {"CPU": 2.0}])
        deadline = time.monotonic() + 30
        while len(provider.non_terminated_nodes()) < 2 and time.monotonic() < deadline:
            time.sleep(0.3)
        assert len(provider.non_terminated_nodes()) >= 2

        # Floor-held nodes must persist well past idle_timeout (no
        # launch/terminate churn while the floor stands).
        held = set(provider.non_terminated_nodes())
        time.sleep(3 * 1.5)
        assert held <= set(provider.non_terminated_nodes()), "floor nodes churned"

        request_resources([])  # clear the floor
        deadline = time.monotonic() + 40
        while provider.non_terminated_nodes() and time.monotonic() < deadline:
            time.sleep(0.5)
        assert not provider.non_terminated_nodes()
    finally:
        scaler.stop()
