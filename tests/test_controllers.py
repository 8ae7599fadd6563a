"""Controller-fleet tests: the envtest-ring analog — pods flow through
admission -> podgrouper -> scheduler -> binder over the in-memory API
(reference: pkg/env-tests/, pkg/binder|podgrouper integration_tests)."""

import pytest

from kai_scheduler_tpu.controllers import (Admission, AdmissionError,
                                           InMemoryKubeAPI, System,
                                           SystemConfig, make_pod, owner_ref)
from kai_scheduler_tpu.controllers.resourcereservation import (
    GPU_DEVICE_ANNOTATION, ReservationAgent)
from kai_scheduler_tpu.models import group_workload


def make_node(api, name, gpu=8, cpu="32", mem="256Gi", labels=None):
    api.create({"kind": "Node",
                "metadata": {"name": name, "labels": labels or {}},
                "spec": {},
                "status": {"allocatable": {"cpu": cpu, "memory": mem,
                                           "nvidia.com/gpu": gpu,
                                           "pods": 110}}})


def make_queue(api, name, deserved=None, parent=None):
    api.create({"kind": "Queue", "metadata": {"name": name},
                "spec": {"deserved": deserved, "parentQueue": parent}})


class TestGroupers:
    def test_pytorch_job_gang(self):
        owner = {"kind": "PyTorchJob", "apiVersion": "kubeflow.org/v1",
                 "metadata": {"name": "train", "uid": "u1",
                              "labels": {"kai.scheduler/queue": "team-a"}},
                 "spec": {"pytorchReplicaSpecs": {
                     "Master": {"replicas": 1},
                     "Worker": {"replicas": 3}}}}
        meta = group_workload(owner)
        assert meta.min_member == 4
        assert meta.queue == "team-a"
        assert {ps.name: ps.min_available for ps in meta.pod_sets} == \
            {"master": 1, "worker": 3}

    def test_ray_cluster_min_replicas(self):
        owner = {"kind": "RayCluster", "apiVersion": "ray.io/v1",
                 "metadata": {"name": "rc", "uid": "u2"},
                 "spec": {"workerGroupSpecs": [
                     {"minReplicas": 2, "replicas": 4},
                     {"minReplicas": 1}]}}
        meta = group_workload(owner)
        assert meta.min_member == 4  # head + 2 + 1

    def test_jobset(self):
        owner = {"kind": "JobSet", "apiVersion": "jobset.x-k8s.io/v1alpha2",
                 "metadata": {"name": "js", "uid": "u3"},
                 "spec": {"replicatedJobs": [
                     {"name": "driver", "replicas": 1},
                     {"name": "workers", "replicas": 2,
                      "template": {"spec": {"parallelism": 4}}}]}}
        meta = group_workload(owner)
        assert meta.min_member == 9

    def test_deployment_per_pod_groups(self):
        owner = {"kind": "Deployment", "apiVersion": "apps/v1",
                 "metadata": {"name": "web", "uid": "u4"},
                 "spec": {"replicas": 3}}
        pod = make_pod("web-abc123", owner=owner_ref("Deployment", "web"))
        meta = group_workload(owner, pod)
        assert meta.min_member == 1
        assert "web-abc123" in meta.name
        assert not meta.preemptible  # inference default

    def test_grove_hierarchical(self):
        owner = {"kind": "PodGangSet", "apiVersion": "grove.io/v1alpha1",
                 "metadata": {"name": "gang", "uid": "u5"},
                 "spec": {"template": {"cliques": [
                     {"name": "prefill", "spec": {"minReplicas": 2}},
                     {"name": "decode", "spec": {"minReplicas": 4}}]}}}
        meta = group_workload(owner)
        assert meta.min_member == 6
        assert [ps.name for ps in meta.pod_sets] == ["prefill", "decode"]

    def test_skip_top_owner_argo(self):
        api = InMemoryKubeAPI()
        wf = {"kind": "Workflow", "apiVersion": "argoproj.io/v1alpha1",
              "metadata": {"name": "wf", "uid": "u6",
                           "labels": {"kai.scheduler/queue": "batch"}},
              "spec": {}}
        pod = make_pod("wf-step-1", owner=owner_ref("Pod", "step"))
        pod["metadata"]["ownerReferences"] = [
            owner_ref("Job", "wf-step", api_version="batch/v1")]
        meta = group_workload(wf, pod, api)
        # Grouped by the inner Job, but the workflow's queue propagates.
        assert meta.queue == "batch"


class TestAdmission:
    def test_fraction_normalization(self):
        adm = Admission()
        pod = make_pod("p1", gpu=1, annotations={"gpu-fraction": "0.5"})
        adm.mutate(pod)
        reqs = pod["spec"]["containers"][0]["resources"]["requests"]
        assert "nvidia.com/gpu" not in reqs
        assert pod["spec"]["schedulerName"] == "kai-scheduler"

    def test_invalid_fraction_rejected(self):
        adm = Admission()
        for bad in ("1.5", "0", "abc"):
            pod = make_pod("p1", annotations={"gpu-fraction": bad})
            with pytest.raises(AdmissionError):
                adm.validate(pod)

    def test_fraction_and_memory_exclusive(self):
        adm = Admission()
        pod = make_pod("p1", annotations={"gpu-fraction": "0.5",
                                          "gpu-memory": "8Gi"})
        with pytest.raises(AdmissionError):
            adm.validate(pod)


class TestEndToEnd:
    def _system(self):
        system = System(SystemConfig())
        make_node(system.api, "n1", gpu=8)
        make_node(system.api, "n2", gpu=8)
        make_queue(system.api, "team-a",
                   deserved=dict(cpu="64", memory="512Gi", gpu=16))
        return system

    def test_pytorch_job_flows_to_bound_pods(self):
        system = self._system()
        api = system.api
        job = {"kind": "PyTorchJob", "apiVersion": "kubeflow.org/v1",
               "metadata": {"name": "train", "uid": "tj1",
                            "labels": {"kai.scheduler/queue": "team-a"}},
               "spec": {"pytorchReplicaSpecs": {"Master": {"replicas": 1},
                                                "Worker": {"replicas": 2}}}}
        api.create(job)
        ref = owner_ref("PyTorchJob", "train", uid="tj1",
                        api_version="kubeflow.org/v1")
        for i, role in enumerate(["master", "worker", "worker"]):
            pod = make_pod(f"train-{role}-{i}", owner=ref, gpu=2,
                           labels={"training.kubeflow.org/replica-type":
                                   role})
            api.create(pod)

        system.run_cycle()

        pgs = api.list("PodGroup")
        assert len(pgs) == 1
        assert pgs[0]["spec"]["minMember"] == 3
        bound = [p for p in api.list("Pod")
                 if p["spec"].get("nodeName")
                 and p["metadata"]["namespace"] == "default"]
        assert len(bound) == 3
        brs = api.list("BindRequest")
        assert all(br["status"]["phase"] == "Succeeded" for br in brs)
        # PodGroup status converges to Running.
        system.run_cycle()
        assert api.list("PodGroup")[0]["status"]["phase"] == "Running"

    def test_gang_too_big_stays_pending(self):
        system = self._system()
        api = system.api
        job = {"kind": "PyTorchJob", "apiVersion": "kubeflow.org/v1",
               "metadata": {"name": "big", "uid": "tj2",
                            "labels": {"kai.scheduler/queue": "team-a"}},
               "spec": {"pytorchReplicaSpecs": {"Worker": {"replicas": 3}}}}
        api.create(job)
        ref = owner_ref("PyTorchJob", "big", uid="tj2",
                        api_version="kubeflow.org/v1")
        for i in range(3):
            api.create(make_pod(f"big-worker-{i}", owner=ref, gpu=8,
                                labels={"training.kubeflow.org/"
                                        "replica-type": "worker"}))
        system.run_cycle()
        bound = [p for p in api.list("Pod") if p["spec"].get("nodeName")]
        # 3x8 GPUs > 16 available: gang must not partially bind.
        assert bound == []

    def test_fractional_pod_creates_reservation(self):
        system = self._system()
        agent = ReservationAgent(system.api)
        api = system.api
        pod = make_pod("frac-1", annotations={"gpu-fraction": "0.5"},
                       queue="team-a")
        api.create(pod)
        system.run_cycle()
        reservations = api.list("Pod",
                                namespace="kai-resource-reservation")
        assert len(reservations) == 1
        assert GPU_DEVICE_ANNOTATION in \
            reservations[0]["metadata"]["annotations"]
        p = api.get("Pod", "frac-1")
        assert p["spec"].get("nodeName")
        assert p["metadata"]["annotations"].get("kai.scheduler/gpu-group")

    def test_queue_status_aggregation(self):
        system = self._system()
        api = system.api
        api.create(make_pod("solo", queue="team-a", gpu=1))
        system.run_cycle()
        system.run_cycle()
        q = api.get("Queue", "team-a")
        assert q["status"]["allocated"].get("pods") == 1

    def test_scale_adjuster_creates_scaling_pod(self):
        system = self._system()
        api = system.api
        # A fractional pod that can't schedule (no GPUs at all).
        for node in api.list("Node"):
            node["status"]["allocatable"]["nvidia.com/gpu"] = 0
            api.update(node)
        api.create(make_pod("frac-stuck",
                            annotations={"gpu-fraction": "0.5"},
                            queue="team-a"))
        system.run_cycle()
        scaling = api.list("Pod", namespace="kai-scale-adjust")
        assert len(scaling) == 1
        reqs = scaling[0]["spec"]["containers"][0]["resources"]["requests"]
        assert reqs["nvidia.com/gpu"] == 1


class TestShards:
    def test_node_pool_partition(self):
        from kai_scheduler_tpu.controllers import ShardSpec
        config = SystemConfig(shards=[
            ShardSpec("pool-a", "pool", "a"),
            ShardSpec("pool-b", "pool", "b"),
        ])
        system = System(config)
        api = system.api
        make_node(api, "a1", labels={"pool": "a"})
        make_node(api, "b1", labels={"pool": "b"})
        make_queue(api, "q")
        api.create(make_pod("pod-a", queue="q", gpu=1,
                            labels={"kai.scheduler/node-pool": "a"},
                            node_selector={"pool": "a"}))
        # An unlabeled pod belongs to no pool shard: it must NOT be bound
        # by either shard (no cross-shard double scheduling).
        api.create(make_pod("pod-free", queue="q", gpu=1))
        system.run_cycle()
        p = api.get("Pod", "pod-a")
        assert p["spec"].get("nodeName") == "a1"
        assert not api.get("Pod", "pod-free")["spec"].get("nodeName")


class TestExplainabilityAndUsage:
    def test_unschedulable_condition_on_podgroup(self):
        system = System(SystemConfig())
        api = system.api
        make_node(api, "n1", gpu=2)
        make_queue(api, "q")
        api.create(make_pod("toolarge", queue="q", gpu=8))
        system.run_cycle()
        pgs = api.list("PodGroup")
        conds = pgs[0]["status"].get("conditions", [])
        assert any(c["type"] == "Unschedulable"
                   and ("Resources" in c["message"]
                        or "node-pool" in c["message"])
                   for c in conds)

    def test_usage_db_records_allocations(self):
        system = System(SystemConfig(usage_db="memory://"))
        api = system.api
        make_node(api, "n1", gpu=8)
        make_queue(api, "q")
        api.create(make_pod("p1", queue="q", gpu=4))
        system.run_cycle()
        system.run_cycle()
        usage = system.usage_db.queue_usage(0.0)
        assert usage["q"][2] > 0  # GPU usage recorded for the queue

    def test_feature_gate_accessor(self):
        cfg = SystemConfig(feature_gates={"newThing": False})
        assert not cfg.gate("newThing")
        assert cfg.gate("defaultOn")


class TestGroveEndToEnd:
    def test_podgangset_cliques_flow_to_rack_pinned_pods(self):
        """Grove PodGangSet with per-clique rack constraints: pods group
        into one gang with podSets, and each clique lands in one rack."""
        system = System(SystemConfig())
        api = system.api
        for i in range(4):
            make_node(api, f"n{i}", gpu=8,
                      labels={"rack": f"r{i}"})
        api.create({"kind": "Topology", "metadata": {"name": "dc"},
                    "spec": {"levels": [{"nodeLabel": "rack"}]}})
        make_queue(api, "q")
        gang = {"kind": "PodGangSet", "apiVersion": "grove.io/v1alpha1",
                "metadata": {"name": "dynamo", "uid": "dg1",
                             "labels": {"kai.scheduler/queue": "q"}},
                "spec": {"template": {"cliques": [
                    {"name": "prefill",
                     "spec": {"minReplicas": 2,
                              "topologyConstraint": {
                                  "topology": "dc",
                                  "requiredLevel": "rack"}}},
                    {"name": "decode",
                     "spec": {"minReplicas": 2,
                              "topologyConstraint": {
                                  "topology": "dc",
                                  "requiredLevel": "rack"}}},
                ]}}}
        api.create(gang)
        ref = owner_ref("PodGangSet", "dynamo", uid="dg1",
                        api_version="grove.io/v1alpha1")
        for clique in ("prefill", "decode"):
            for i in range(2):
                api.create(make_pod(f"dynamo-{clique}-{i}", owner=ref,
                                    gpu=4))
        system.run_cycle()
        pg = api.list("PodGroup")[0]
        assert pg["spec"]["minMember"] == 4
        podsets = {ps["name"]: ps for ps in pg["spec"]["podSets"]}
        assert podsets["prefill"]["topology"]["required"] == "rack"
        bound = {p["metadata"]["name"]: p["spec"].get("nodeName")
                 for p in api.list("Pod") if p["spec"].get("nodeName")}
        assert len(bound) == 4
        prefill_racks = {bound[f"dynamo-prefill-{i}"] for i in range(2)}
        decode_racks = {bound[f"dynamo-decode-{i}"] for i in range(2)}
        assert len(prefill_racks) == 1 and len(decode_racks) == 1


class TestTimeAwareFairness:
    def test_usage_penalty_shifts_shares_over_cycles(self):
        """Multi-cycle time-aware fairness (env-tests/
        time_aware_fairness_test.go analog): a queue that monopolized the
        cluster accrues usage, and the k-value penalty tilts future fair
        shares toward the idle queue."""
        from kai_scheduler_tpu.utils.usagedb import UsageParams
        clock = {"now": 0.0}
        cfg = SystemConfig(usage_db="memory://",
                           usage_params=UsageParams(
                               half_life_period_seconds=600.0,
                               window_size_seconds=100000.0),
                           now_fn=lambda: clock["now"])
        system = System(cfg)
        api = system.api
        make_node(api, "n1", gpu=8)
        make_queue(api, "greedy")
        make_queue(api, "patient")
        system.usage_db.cluster_capacity = None  # normalize off for test
        # greedy uses the whole cluster for many cycles.
        for i in range(4):
            api.create(make_pod(f"g{i}", queue="greedy", gpu=2))
        for cycle in range(5):
            system.run_cycle()
            clock["now"] += 60.0
        usage = system.usage_db.queue_usage(clock["now"])
        assert usage["greedy"][2] > 0
        assert usage.get("patient", [0, 0, 0])[2] == 0
        # Now both queues contend; the historical usage flows into the
        # session and penalizes greedy's over-quota weight.
        ssn = system.schedulers[0].last_session
        assert ssn.queue_usage  # usage provider wired through


class TestOperatorAndConfig:
    def test_scheduling_shard_objects_drive_fleet(self):
        system = System(SystemConfig())
        api = system.api
        make_node(api, "a1", labels={"pool": "a"})
        make_node(api, "b1", labels={"pool": "b"})
        make_queue(api, "q")
        api.create({"kind": "SchedulingShard",
                    "metadata": {"name": "shard-a"},
                    "spec": {"nodePoolLabelKey": "pool",
                             "nodePoolLabelValue": "a"}})
        api.create({"kind": "SchedulingShard",
                    "metadata": {"name": "shard-b"},
                    "spec": {"nodePoolLabelKey": "pool",
                             "nodePoolLabelValue": "b",
                             "args": {"k_value": 2.0}}})
        api.create(make_pod("p-b", queue="q", gpu=1,
                            labels={"kai.scheduler/node-pool": "b"},
                            node_selector={"pool": "b"}))
        system.run_cycle()
        assert len(system.schedulers) == 2
        assert system.schedulers[1].config.k_value == 2.0
        p = api.get("Pod", "p-b")
        assert p["spec"].get("nodeName") == "b1"

    def test_scheduler_config_from_yaml(self, tmp_path):
        from kai_scheduler_tpu.framework import SchedulerConfig
        path = tmp_path / "conf.yaml"
        path.write_text("""
actions: allocate, reclaim
tiers:
  - plugins:
      - predicates
      - proportion
      - name: nodeplacement
        arguments: {gpu: spread}
k_value: 0.5
""")
        cfg = SchedulerConfig.from_file(str(path))
        assert cfg.actions == ["allocate", "reclaim"]
        assert cfg.k_value == 0.5
        assert cfg.plugin_args("nodeplacement") == {"gpu": "spread"}

    def test_stateless_restart_converges(self):
        """The scheduler holds no durable state: rebuilding the whole
        System over the same API reaches the same placements (the
        checkpoint/resume story, SURVEY.md §5)."""
        system = System(SystemConfig())
        api = system.api
        make_node(api, "n1", gpu=8)
        make_queue(api, "q")
        api.create(make_pod("p1", queue="q", gpu=2))
        system.run_cycle()
        placed = api.get("Pod", "p1")["spec"].get("nodeName")
        assert placed == "n1"
        # "Crash": build a brand-new System over the surviving API objects.
        reborn = System(SystemConfig(), api=api)
        api.create(make_pod("p2", queue="q", gpu=2))
        reborn.run_cycle()
        assert api.get("Pod", "p1")["spec"].get("nodeName") == "n1"
        assert api.get("Pod", "p2")["spec"].get("nodeName") == "n1"


class TestGpuMemoryRequests:
    def test_gpu_memory_annotation_becomes_fraction(self):
        """A gpu-memory request resolves against the node's per-device
        memory into a sharing fraction (gpu-memory flow e2e)."""
        system = System(SystemConfig())
        api = system.api
        api.create({"kind": "Node",
                    "metadata": {"name": "n1", "annotations": {
                        "nvidia.com/gpu.memory": "16Gi"}},
                    "spec": {},
                    "status": {"allocatable": {"cpu": "32",
                                               "memory": "256Gi",
                                               "nvidia.com/gpu": 2,
                                               "pods": 110}}})
        make_queue(api, "q")
        # Two 8Gi pods = two halves of one 16Gi device.
        for i in range(2):
            api.create(make_pod(f"m{i}", queue="q",
                                annotations={"gpu-memory": "8Gi"}))
        system.run_cycle()
        pods = [api.get("Pod", f"m{i}") for i in range(2)]
        assert all(p["spec"].get("nodeName") == "n1" for p in pods)
        groups = {p["metadata"]["annotations"].get(
            "kai.scheduler/gpu-group") for p in pods}
        assert len(groups) == 1 and None not in groups  # same device


class TestPipelinedAcrossCycles:
    def test_pipelined_pod_binds_after_victim_leaves(self):
        """Cycle 1 pipelines a pending pod onto a releasing node (via
        reclaim); the assignment survives in the cache and the pod binds
        on that node once the victim is gone (Cache.TaskPipelined flow)."""
        system = System(SystemConfig())
        api = system.api
        make_node(api, "n1", gpu=8)
        make_node(api, "n2", gpu=8)
        make_queue(api, "q_a", deserved=dict(cpu="32", memory="256Gi",
                                             gpu=8))
        make_queue(api, "q_b", deserved=dict(cpu="32", memory="256Gi",
                                             gpu=8))
        # q_a hogs both nodes; q_b's pod must reclaim.
        for i, node in enumerate(["n1", "n1", "n2", "n2"]):
            api.create(make_pod(f"hog{i}", queue="q_a", gpu=4,
                                node_name=node, phase="Running"))
        system.run_cycle()  # podgroups materialize for the running hogs
        api.create(make_pod("starved", queue="q_b", gpu=8))
        system.run_cycle()
        # Reclaim evicted hogs and pipelined 'starved' onto their node.
        assert any(sc.cache._pipelined for sc in system.schedulers)
        evicted = [p for p in api.list("Pod")
                   if p["metadata"].get("deletionTimestamp")]
        assert evicted
        victim_node = evicted[0]["spec"]["nodeName"]
        # The victims actually terminate (API deletion completes).
        for p in evicted:
            api.delete("Pod", p["metadata"]["name"],
                       p["metadata"].get("namespace", "default"))
        system.run_cycle()
        p = api.get("Pod", "starved")
        assert p["spec"].get("nodeName") == victim_node


class TestDeletionAndBinderFailure:
    def test_deleted_pod_mid_flight_is_gced(self):
        """Pod vanishes between scheduling and binding: the BindRequest is
        garbage-collected instead of wedging the binder
        (deletion_tests + stale BindRequest GC, cache.go:371)."""
        system = System(SystemConfig())
        api = system.api
        make_node(api, "n1")
        make_queue(api, "q")
        api.create(make_pod("ghost", queue="q", gpu=1))
        api.drain()
        # Schedule without draining the binder, then delete the pod.
        for sched in system.schedulers:
            sched.run_once()
        api.delete("Pod", "ghost")
        # Binder reconcile fails (pod gone); GC removes the request.
        api.drain()
        system.cache.gc_stale_bind_requests()
        assert api.list("BindRequest") == []

    def test_bind_failure_retries_then_fails_with_rollback(self):
        """Bind to a nonexistent node retries up to the backoff limit
        with EXPONENTIAL BACKOFF between attempts (no hot loop), ends
        Failed releasing the GPU reservation it took, and emits a
        bind_backoff_exceeded event (bindrequest_controller +
        Binder.Rollback)."""
        from kai_scheduler_tpu.controllers.binder import (
            RESERVATION_NAMESPACE)
        system = System(SystemConfig())
        api = system.api
        clock = {"t": 100.0}
        system.binder.now_fn = lambda: clock["t"]
        system.binder.backoff_base_s = 1.0
        api.create({"kind": "BindRequest",
                    "metadata": {"name": "bad-bind"},
                    "spec": {"podName": "nope", "podUid": "x",
                             "selectedNode": "missing-node",
                             "selectedGPUGroups": ["grp-1"],
                             "backoffLimit": 2},
                    "status": {"phase": "Pending"}})
        api.drain()
        br = api.get("BindRequest", "bad-bind")
        # First attempt failed; the request is backing off, NOT hot-
        # looping to Failed within one drain pass.
        assert br["status"]["phase"] == "Pending"
        assert br["status"]["attempts"] == 1
        assert br["status"]["backoffUntil"] > clock["t"]
        # Draining again before the backoff elapses must not burn an
        # attempt (the hot-loop regression this satellite fixes).
        api.drain()
        system.binder.tick()
        assert api.get("BindRequest", "bad-bind")["status"]["attempts"] == 1
        # Advance past the backoff: the retry runs, exhausts the limit.
        clock["t"] += 10.0
        system.binder.tick()
        api.drain()
        br = api.get("BindRequest", "bad-bind")
        assert br["status"]["phase"] == "Failed"
        assert br["status"]["attempts"] >= 2
        # No reservation pod survives the rollback.
        assert api.list("Pod", namespace=RESERVATION_NAMESPACE) == []
        # The exhaustion is announced loudly.
        events = [e for e in api.list("Event")
                  if e["spec"]["reason"] == "bind_backoff_exceeded"]
        assert events, "bind_backoff_exceeded event missing"


class TestAdmissionRuntimeAndMetrics:
    def test_runtime_class_enforced_for_fractions(self):
        adm = Admission(enforced_runtime_class="kai-gpu-sharing")
        pod = make_pod("p1", annotations={"gpu-fraction": "0.5"})
        adm.mutate(pod)
        assert pod["spec"]["runtimeClassName"] == "kai-gpu-sharing"
        plain = make_pod("p2", gpu=1)
        adm.mutate(plain)
        assert "runtimeClassName" not in plain["spec"]

    def test_metrics_expose_queue_gauges(self):
        from kai_scheduler_tpu.utils.metrics import METRICS
        METRICS.reset()
        system = System(SystemConfig())
        make_node(system.api, "n1")
        make_queue(system.api, "q")
        system.api.create(make_pod("p1", queue="q", gpu=1))
        system.run_cycle()
        text = METRICS.to_prometheus_text()
        assert 'queue_fair_share_gpu{queue="q"}' in text
        assert "e2e_scheduling_latency_milliseconds" in text
        # Per-phase cycle breakdown (the host-pipeline profiling surface):
        # the span histograms of the snapshot, the plugin opens and the
        # actions, one clock a phase.
        assert "cycle_span_snapshot_latency_ms" in text
        assert "cycle_span_plugin_latency_ms" in text
        assert "cycle_span_action_latency_ms" in text
        assert "cycle_phase_latency" not in text


class TestMixedWorkloadScenario:
    def test_kubeflow_ray_and_fractions_all_bind(self):
        """The final-drive scenario as regression: a PyTorchJob gang, a
        RayCluster (plural podset names vs singular pod roles), and
        fraction pods all bind in one cycle with no utility PodGroups."""
        system = System(SystemConfig())
        api = system.api
        for i in range(4):
            make_node(api, f"n{i}", gpu=8, labels={"rack": f"r{i}"})
        for q in ("prod", "research"):
            make_queue(api, q,
                       deserved=dict(cpu="128", memory="1Ti", gpu=16))
        api.create({"kind": "PyTorchJob", "apiVersion": "kubeflow.org/v1",
                    "metadata": {"name": "train", "uid": "tj",
                                 "labels": {"kai.scheduler/queue": "prod"}},
                    "spec": {"pytorchReplicaSpecs": {
                        "Master": {"replicas": 1},
                        "Worker": {"replicas": 3}}}})
        ref = owner_ref("PyTorchJob", "train", uid="tj",
                        api_version="kubeflow.org/v1")
        for i, role in enumerate(["master", "worker", "worker", "worker"]):
            api.create(make_pod(
                f"train-{role}-{i}", owner=ref, gpu=3,
                labels={"training.kubeflow.org/replica-type": role}))
        api.create({"kind": "RayCluster", "apiVersion": "ray.io/v1",
                    "metadata": {"name": "rc", "uid": "rc",
                                 "labels": {"kai.scheduler/queue":
                                            "research"}},
                    "spec": {"workerGroupSpecs": [{"minReplicas": 2}]}})
        rref = owner_ref("RayCluster", "rc", uid="rc",
                         api_version="ray.io/v1")
        for name in ("rc-head", "rc-worker-0", "rc-worker-1"):
            api.create(make_pod(name, owner=rref, gpu=2))
        for i in range(2):
            api.create(make_pod(f"frac-{i}", queue="research",
                                annotations={"gpu-fraction": "0.5"}))
        system.run_cycle()
        bound = [p for p in api.list("Pod")
                 if p["spec"].get("nodeName")
                 and p["metadata"]["namespace"] == "default"]
        assert len(bound) == 9
        pg_names = [pg["metadata"]["name"] for pg in api.list("PodGroup")]
        assert not any(n.startswith(("pg-scaling", "pg-reservation"))
                       for n in pg_names)
        phases = {pg["metadata"]["name"]: pg["status"]["phase"]
                  for pg in api.list("PodGroup")}
        system.run_cycle()
        phases = {pg["metadata"]["name"]: pg["status"]["phase"]
                  for pg in api.list("PodGroup")}
        assert all(p == "Running" for p in phases.values()), phases


class TestVolumeBinding:
    def test_pvc_binds_to_selected_node(self):
        """The binder's volume-binding pre-bind phase binds pending PVCs
        and stamps the selected node (k8s-plugins/volumebinding analog)."""
        system = System(SystemConfig())
        api = system.api
        make_node(api, "n1")
        make_queue(api, "q")
        api.create({"kind": "PersistentVolumeClaim",
                    "metadata": {"name": "data"},
                    "spec": {}, "status": {"phase": "Pending"}})
        pod = make_pod("stateful", queue="q", gpu=1)
        pod["spec"]["volumes"] = [
            {"name": "data", "persistentVolumeClaim": {"claimName": "data"}}]
        api.create(pod)
        system.run_cycle()
        pvc = api.get("PersistentVolumeClaim", "data")
        assert pvc["status"]["phase"] == "Bound"
        assert pvc["metadata"]["annotations"][
            "volume.kubernetes.io/selected-node"] == "n1"
        assert api.get("Pod", "stateful")["spec"]["nodeName"] == "n1"
