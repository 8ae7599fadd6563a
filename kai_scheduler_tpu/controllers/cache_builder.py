"""Cluster cache: API objects -> ClusterInfo snapshots.

The L1 layer (SURVEY.md §1): mirrors pkg/scheduler/cache/ +
cache/cluster_info/cluster_info.go:118 — aggregate watched objects and
build the immutable per-cycle ClusterInfo the framework schedules against.
Also executes the scheduler's side effects against the API (Bind ->
BindRequest object, Evict -> pod deletion + condition), playing the role of
cache.Bind/Evictor for the embedded deployment.
"""

from __future__ import annotations

import itertools
import re

import numpy as np

from ..api import (ClusterInfo, NodeInfo, PodGroupInfo, PodInfo, PodSet,
                   PodStatus, QueueInfo, QueueQuota, resources as rs)
from ..api.cluster_info import QueueAggregates
from ..api.resources import ResourceRequirements
from .admission import GPU_FRACTION_ANNOTATION, GPU_MEMORY_ANNOTATION
from .binder import GPU_GROUP_ANNOTATION
from .kubeapi import Conflict, InMemoryKubeAPI
from .podgrouper import POD_GROUP_LABEL, SUBGROUP_LABEL
from ..utils.lifecycle import LIFECYCLE
from ..utils.logging import LOG
from ..utils.metrics import METRICS
from ..utils.tracing import TRACER

PHASE_TO_STATUS = {
    "Pending": PodStatus.PENDING,
    "Running": PodStatus.RUNNING,
    "Succeeded": PodStatus.SUCCEEDED,
    "Failed": PodStatus.FAILED,
}

# Rank-aware gang placement (ops/rankplace.py, arxiv 2603.22691): the MPI
# rank index of a gang member, resolved in priority order from the
# explicit annotation, the workload controllers' index labels/annotations
# (indexed Jobs, StatefulSets, kubeflow replicas, LeaderWorkerSet), and
# finally the trailing ``-<int>`` pod-name convention every one of those
# controllers also follows.  -1 = unranked (rank placement skips the pod).
RANK_ANNOTATION = "kai.scheduler/rank"
_RANK_LABEL_KEYS = (
    "batch.kubernetes.io/job-completion-index",     # indexed batch Job
    "apps.kubernetes.io/pod-index",                 # StatefulSet
    "training.kubeflow.org/replica-index",          # kubeflow operators
    "leaderworkerset.sigs.k8s.io/worker-index",     # LWS
)
_RANK_NAME_RE = re.compile(r"-(\d+)$")


def _parse_rank(md: dict) -> int:
    ann = md.get("annotations") or {}
    labels = md.get("labels") or {}
    for source in (ann.get(RANK_ANNOTATION),
                   ann.get(_RANK_LABEL_KEYS[0]),
                   *(labels.get(k) for k in _RANK_LABEL_KEYS)):
        if source is None:
            continue
        try:
            rank = int(source)
        except (TypeError, ValueError):
            continue
        return rank if rank >= 0 else -1
    m = _RANK_NAME_RE.search(md.get("name", ""))
    return int(m.group(1)) if m else -1


def _requests_to_reqreq(pod: dict) -> ResourceRequirements:
    cpu_milli = mem = gpu = 0.0
    mig: dict = {}
    for c in pod.get("spec", {}).get("containers", []):
        req = c.get("resources", {}).get("requests", {})
        if "cpu" in req:
            cpu_milli += rs.parse_cpu(req["cpu"])
        if "memory" in req:
            mem += rs.parse_memory(req["memory"])
        if "nvidia.com/gpu" in req:
            gpu += float(req["nvidia.com/gpu"])
        for name, qty in req.items():
            if "mig-" in name:
                mig[name] = mig.get(name, 0) + int(qty)
    ann = pod.get("metadata", {}).get("annotations", {})
    fraction = float(ann.get(GPU_FRACTION_ANNOTATION, 0) or 0)
    gpu_memory = ann.get(GPU_MEMORY_ANNOTATION)
    return ResourceRequirements.from_spec(
        cpu=cpu_milli / 1000.0 if cpu_milli else None,
        memory=mem if mem else None,
        gpu=gpu, gpu_fraction=fraction, gpu_memory=gpu_memory, mig=mig)


# Conservative CEL subset for DeviceClass/request selectors (upstream
# classes select devices ONLY via CEL, dynamicresources.go:59-87 /
# k8s.io/dynamic-resource-allocation/cel).  Supported shapes:
#   device.attributes["<domain>"].<name> == <literal>
#   device.attributes["<domain>"].<name> in [<literals>]
#   device.capacity["<domain>"].<name> >= quantity("<q>")
#   device.capacity["<domain>"].<name>.compareTo(quantity("<q>")) >= 0
#   device.driver == "<driver>"
# AND-conjunctions (&&) of the above split into separate entries.
# Anything else stays opaque and matches NOTHING — never too-wide.
_CEL_ATTR_EQ = re.compile(
    r'^device\.attributes\["(?P<domain>[^"]+)"\]\.(?P<name>\w+)\s*==\s*'
    r'(?P<value>"[^"]*"|\d+(?:\.\d+)?|true|false)$')
_CEL_ATTR_IN = re.compile(
    r'^device\.attributes\["(?P<domain>[^"]+)"\]\.(?P<name>\w+)\s+in\s+'
    r'\[(?P<values>[^\]]*)\]$')
_CEL_CAP_GE = re.compile(
    r'^device\.capacity\["(?P<domain>[^"]+)"\]\.(?P<name>\w+)'
    r'(?:\.compareTo\(quantity\("(?P<q1>[^"]+)"\)\)\s*>=\s*0'
    r'|\s*>=\s*quantity\("(?P<q2>[^"]+)"\))$')
_CEL_DRIVER_EQ = re.compile(r'^device\.driver\s*==\s*"(?P<value>[^"]+)"$')


def _cel_literal(text: str):
    """Parse a CEL literal; raises ValueError on anything that is not a
    plain string/bool/number literal (callers translate that into a
    match-nothing selector — a non-literal must never crash the
    snapshot)."""
    text = text.strip()
    if text.startswith('"') and text.endswith('"') and len(text) >= 2:
        return text[1:-1]
    if text in ("true", "false"):
        return text == "true"
    try:
        return int(text)
    except ValueError:
        return float(text)  # ValueError propagates to the caller's guard


def _parse_cel_expression(expr: str) -> list:
    """One CEL expression -> structured entries, or a single opaque
    match-nothing entry when any conjunct falls outside the subset."""
    out = []
    for part in expr.split("&&"):
        part = part.strip()
        # One level of surrounding parens (blind strip would eat
        # quantity(...)'s closing paren).
        if part.startswith("(") and part.endswith(")"):
            part = part[1:-1].strip()
        m = _CEL_ATTR_EQ.match(part)
        if m:
            out.append({"attribute": f"{m['domain']}/{m['name']}",
                        "fallback_attribute": m["name"],
                        "value": _cel_literal(m["value"])})
            continue
        m = _CEL_ATTR_IN.match(part)
        if m:
            try:
                values = [_cel_literal(v)
                          for v in m["values"].split(",") if v.strip()]
            except ValueError:
                # Non-literal list members (or quoted commas the naive
                # split breaks): outside the subset, match nothing.
                return [{"unsupported": True, "cel": expr}]
            out.append({"attribute": f"{m['domain']}/{m['name']}",
                        "fallback_attribute": m["name"],
                        "any_of": values})
            continue
        m = _CEL_CAP_GE.match(part)
        if m:
            out.append({"capacity": f"{m['domain']}/{m['name']}",
                        "fallback_capacity": m["name"],
                        "min": rs.parse_quantity(m["q1"] or m["q2"])})
            continue
        m = _CEL_DRIVER_EQ.match(part)
        if m:
            out.append({"attribute": "driver",
                        "value": m["value"]})
            continue
        return [{"unsupported": True, "cel": expr}]
    return out


def _parse_device_selectors(raw) -> list:
    """DeviceClass/request selectors -> structured entries.

    The structured dialect ({"attribute": k, "value": v} equality,
    {"attribute": k, "any_of": [...]}, {"capacity": k, "min": quantity})
    is matched exactly; CEL expressions translate through the
    conservative subset above, and anything unparsed matches NOTHING —
    loud, never too-wide."""
    out = []
    for sel in raw or []:
        if "attribute" in sel and (sel.get("value") is not None
                                   or sel.get("any_of")):
            entry = {"attribute": sel["attribute"]}
            if sel.get("any_of"):
                entry["any_of"] = list(sel["any_of"])
            else:
                entry["value"] = sel["value"]
            out.append(entry)
        elif "capacity" in sel:
            out.append({"capacity": sel["capacity"],
                        "min": rs.parse_quantity(sel.get("min"))})
        elif "cel" in sel and isinstance(sel["cel"], dict) \
                and sel["cel"].get("expression"):
            out.extend(_parse_cel_expression(sel["cel"]["expression"]))
        else:  # unknown shape
            out.append({"unsupported": True})
    return out


def _parse_device_attributes(dev: dict) -> dict:
    """Flatten upstream device attributes ({k: {"string"|"int"|"bool"|
    "version": v}}) or our flat dialect ({k: v}) to {k: python value}."""
    raw = (dev.get("basic") or {}).get("attributes") \
        or dev.get("attributes") or {}
    out = {}
    for k, v in raw.items():
        if isinstance(v, dict):
            for typed in ("string", "int", "bool", "version"):
                if typed in v:
                    out[k] = v[typed]
                    break
        else:
            out[k] = v
    return out


def _parse_device_capacity(dev: dict) -> dict:
    """Flatten device capacity ({k: {"value": q}} or {k: q}) to
    {k: float}."""
    raw = (dev.get("basic") or {}).get("capacity") \
        or dev.get("capacity") or {}
    out = {}
    for k, v in raw.items():
        q = rs.parse_quantity(v.get("value") if isinstance(v, dict)
                              else v)
        if q is not None:
            out[k] = q
    return out


def _parse_pod_affinity(task: PodInfo, affinity: dict) -> None:
    """Parse pod (anti-)affinity terms from the manifest's
    spec.affinity.podAffinity/podAntiAffinity into AffinityTerms
    (matchLabels + topologyKey; the shape upstream InterPodAffinity
    consumes)."""
    from ..api import AffinityTerm

    def parse_term(term: dict, weight: float = 1.0):
        sel = term.get("labelSelector") or {}
        if not term.get("topologyKey"):
            return None
        # No explicit namespaces -> the pod's own namespace (upstream
        # default scoping).
        namespaces = list(term.get("namespaces") or [task.namespace])
        return AffinityTerm(dict(sel.get("matchLabels") or {}),
                            term["topologyKey"], weight,
                            [dict(e) for e in
                             sel.get("matchExpressions") or []],
                            namespaces)

    def terms(block: dict, required_key: str, preferred_key: str):
        req = [t for t in (parse_term(term)
                           for term in block.get(required_key) or [])
               if t is not None]
        pref = [t for t in (parse_term(entry.get("podAffinityTerm") or {},
                                       float(entry.get("weight", 1)))
                            for entry in block.get(preferred_key) or [])
                if t is not None]
        return req, pref

    aff = affinity.get("podAffinity") or {}
    anti = affinity.get("podAntiAffinity") or {}
    required = "requiredDuringSchedulingIgnoredDuringExecution"
    preferred = "preferredDuringSchedulingIgnoredDuringExecution"
    task.affinity_terms, task.preferred_affinity_terms = \
        terms(aff, required, preferred)
    task.anti_affinity_terms, task.preferred_anti_affinity_terms = \
        terms(anti, required, preferred)

    # Node affinity (the upstream NodeAffinity plugin's inputs,
    # k8s_internal/predicates/predicates.go:70-167): required terms are a
    # hard per-node filter (In/NotIn/Exists/DoesNotExist/Gt/Lt, OR across
    # nodeSelectorTerms); preferred terms contribute weighted scores.
    node_aff = affinity.get("nodeAffinity") or {}
    node_req = (node_aff.get(required) or {}).get("nodeSelectorTerms") or []
    task.node_affinity_required = [
        {"expressions": [dict(e) for e in t.get("matchExpressions") or []],
         "fields": [dict(f) for f in t.get("matchFields") or []]}
        for t in node_req]
    task.node_affinity_preferred = [
        {"weight": float(entry.get("weight", 1)),
         "expressions": [dict(e) for e in (entry.get("preference") or {})
                         .get("matchExpressions") or []],
         "fields": [dict(f) for f in (entry.get("preference") or {})
                    .get("matchFields") or []]}
        for entry in node_aff.get(preferred) or []]


def _parse_pod_predicates(task: PodInfo, pod: dict) -> None:
    """Upstream-predicate inputs from the manifest: hostPorts
    (nodeports adapter), required ConfigMaps (config_maps.go
    getAllRequiredConfigMapNames: env/envFrom/volumes, skipping
    optional refs), and referenced PVCs (volume_binding.go)."""
    spec = pod.get("spec", {})
    for c in spec.get("containers") or []:
        for port in c.get("ports") or []:
            host_port = port.get("hostPort")
            if host_port:
                task.host_ports.add(
                    (port.get("protocol", "TCP"), int(host_port)))
        for env_from in c.get("envFrom") or []:
            ref = env_from.get("configMapRef") or {}
            if ref.get("name") and not ref.get("optional"):
                task.required_configmaps.append(ref["name"])
        for env in c.get("env") or []:
            ref = (env.get("valueFrom") or {}).get("configMapKeyRef") or {}
            if ref.get("name") and not ref.get("optional"):
                task.required_configmaps.append(ref["name"])
    for vol in spec.get("volumes") or []:
        cm = vol.get("configMap") or {}
        if cm.get("name") and not cm.get("optional"):
            task.required_configmaps.append(cm["name"])
        claim = (vol.get("persistentVolumeClaim") or {}).get("claimName")
        if claim:
            task.pvc_names.append(claim)
        elif vol.get("ephemeral") is not None and vol.get("name"):
            # Generic ephemeral inline volume: its PVC is named
            # <pod>-<volume> (storage.go:173-176, upstream
            # ephemeral.VolumeClaimName).
            task.pvc_names.append(
                f"{pod['metadata']['name']}-{vol['name']}")
    for ref in spec.get("resourceClaims") or []:
        name = ref.get("resourceClaimName") or ref.get("name")
        if name:
            task.resource_claims.append(name)


def _quota_vec(spec: dict | None):
    if not spec:
        return None
    return dict(cpu=spec.get("cpu"), memory=spec.get("memory"),
                gpu=spec.get("gpu", 0))


class _GroupTmpl:
    """Parsed PodGroup manifest: everything ``snapshot()`` needs to build
    the per-cycle PodGroupInfo without touching the manifest again."""

    __slots__ = ("name", "namespace", "queue_id", "priority",
                 "min_available", "preemptible", "creation_ts",
                 "topology_name", "required_topology_level",
                 "preferred_topology_level", "pod_sets", "last_start_ts",
                 "node_pool")

    def instantiate(self) -> PodGroupInfo:
        pg = PodGroupInfo(
            self.name, self.name, namespace=self.namespace,
            queue_id=self.queue_id, priority=self.priority,
            min_available=self.min_available, preemptible=self.preemptible,
            creation_ts=self.creation_ts, topology_name=self.topology_name,
            required_topology_level=self.required_topology_level,
            preferred_topology_level=self.preferred_topology_level)
        if self.pod_sets:
            pg.set_pod_sets([
                PodSet(name, min_avail, topology_name=topo,
                       required_topology_level=req,
                       preferred_topology_level=pref)
                for name, min_avail, topo, req, pref in self.pod_sets])
        pg.last_start_ts = self.last_start_ts
        pg.node_pool = self.node_pool
        return pg


# Kinds the snapshot consumes.  Hot kinds have dedicated parse-template
# stores; aux kinds rebuild a parsed cache per FAMILY only when one of
# the family's kinds changed (a PVC feeds both the pvc view and the CSI
# storage snapshot, hence the tuple values).
_HOT_KINDS = ("Node", "Queue", "PodGroup", "Pod")
_AUX_FAMILIES = {
    "Topology": ("topology",),
    "ResourceClaim": ("dra",),
    "ResourceSlice": ("dra",),
    "DeviceClass": ("dra",),
    "ConfigMap": ("configmap",),
    "PersistentVolumeClaim": ("pvc", "storage"),
    "CSIDriver": ("storage",),
    "StorageClass": ("storage",),
    "CSIStorageCapacity": ("storage",),
}
_CONSUMED_KINDS = frozenset(_HOT_KINDS) | frozenset(_AUX_FAMILIES)


class ClusterCache:
    """Watches the API and snapshots ClusterInfo each cycle.

    The snapshot is INCREMENTAL: long-lived parse templates (NodeInfo /
    QueueInfo / PodGroupInfo / PodInfo, plus per-family aux caches) are
    maintained from watch deltas, and ``snapshot()`` only re-parses
    objects whose resourceVersion actually moved — the per-cycle cost is
    instantiation + wiring, not O(cluster) manifest re-parsing.  Dirty
    sets derive from the store's own change stream:

    - ``InMemoryKubeAPI`` exposes ``watch_sync`` (emit-time callbacks),
      so mutations mark keys dirty the instant they land — a snapshot
      taken without an intervening drain still sees everything;
    - substrates without the hook (HTTP/real clients) fall back to a
      full per-kind re-list each snapshot, diffed by resourceVersion, so
      the parse memoization still holds (``cluster_cache_full_refresh_
      total`` counts these);
    - a watch resync (the PR 2 relist path) invalidates WHOLESALE:
      mirrors, templates, and the device arena all rebuild from scratch.

    The correctness contract is bit-identity to a from-scratch parse
    (tests/test_incremental_cache.py drives randomized churn against it,
    mirroring how tests/test_snapshot_delta.py proved the arena)."""

    def __init__(self, api: InMemoryKubeAPI, now_fn=None,
                 status_updater=None):
        self.api = api
        self.now_fn = now_fn or (lambda: 0.0)
        # Optional async worker pool for status/event writes
        # (controllers/status_updater.py); synchronous when absent.
        self.status_updater = status_updater
        # Fenced leadership: when set (set_fence), every mutating write
        # the scheduler makes through this cache — BindRequest create,
        # evict, GC delete — carries the leader's epoch; the store
        # rejects stale epochs with kubeapi.Fenced, so a deposed leader
        # can never commit.
        self.fence: str | None = None
        self.epoch_provider = None
        # Crash-safe bind journal (utils/commitlog.py), attached by the
        # operator; Statement.commit journals intents through it and
        # startup_reconcile replays it after a restart.
        self.commitlog = None
        # Watch-gap recovery: after the HTTP client re-lists past a 410
        # GONE, derived caches keyed on resourceVersions it may have
        # missed must be rebuilt.  Registered through a weakref: shard
        # rebuilds (operator reconciles) replace caches, and the client's
        # callback list must not pin every dead cache's parse cache —
        # returning False deregisters a dead wrapper.
        self._resync_pending = False
        on_resync = getattr(api, "on_resync", None)
        if on_resync is not None:
            import weakref
            ref = weakref.ref(self)

            def _resync_cb():
                cache = ref()
                if cache is None:
                    return False  # cache replaced: deregister me
                cache._on_watch_resync()
                return True

            on_resync(_resync_cb)
        # Persistent device arena (framework/arena.py): cross-cycle
        # snapshot residency.  snapshot() feeds it the dirty set below;
        # Sessions built on this cache pack incrementally against it.
        from ..framework.arena import ClusterArena
        self.arena = ClusterArena()
        # -- incremental ClusterInfo store --------------------------------
        # Mirrors of the watched store per consumed kind ((ns, name) ->
        # manifest), maintained from watch deltas (or re-listed per
        # snapshot on substrates without a change hook).  The parse
        # layers below read ONLY the mirrors.  The mirrors and the prep
        # caches below are SINGLE-WRITER on the scheduler thread (watch
        # hooks only enqueue keys into the lock-guarded _changed_keys;
        # snapshot() applies them on its own thread) — machine-checked
        # by kairace KRC003.
        # kairace: single-writer=main
        self._mirror: dict = {k: {} for k in _CONSUMED_KINDS}
        # Deterministic iteration order (sorted by name, api.list's
        # ordering), recomputed only when a kind's membership changes.
        self._order: dict = {k: [] for k in _CONSUMED_KINDS}
        self._order_stale: dict = {k: True for k in _CONSUMED_KINDS}
        # key -> rv signature, for the fallback re-list diff.
        self._kind_sigs: dict = {k: {} for k in _CONSUMED_KINDS}
        # Parsed templates for the hot kinds: name -> (rv_sig, template).
        # Templates are immutable; snapshot() instantiates fresh
        # per-cycle objects from them (the cycle mutates its instances).
        # kairace: single-writer=main
        self._node_tmpl: dict = {}
        # kairace: single-writer=main
        self._queue_tmpl: dict = {}
        # kairace: single-writer=main
        self._group_tmpl: dict = {}
        # Aux parse caches per family, rebuilt only when dirty.
        self._aux: dict = {}
        # kairace: single-writer=main
        self._aux_dirty: dict = {f: True for f in
                                 ("topology", "dra", "configmap", "pvc",
                                  "storage")}
        # Dirty keys accumulated from the change stream; the emit-time
        # hook may fire from ANY thread (async status workers patch
        # through the same store), so the set is lock-guarded and the
        # handler does nothing but record.
        import threading
        self._changes_lock = threading.Lock()
        self._changed_keys: set = set()
        # Latest watch payload per dirty key (None = DELETED), kept only
        # on substrates whose watch events are DETACHED server-side
        # snapshots (HTTPKubeAPI sets watch_payloads_detached): the
        # snapshot then folds the payload directly — the informer-store
        # pattern — instead of paying one GET round trip per dirty key.
        # On the in-memory store the emitted dict is the LIVE object, so
        # re-reading via get_opt stays authoritative there.
        self._changed_objs: dict = {}
        self._payload_auth = bool(getattr(api, "watch_payloads_detached",
                                          False))
        self._primed = False
        self._watch_mode = False
        self.last_snapshot_stats: dict = {}
        watch_sync = getattr(api, "watch_sync", None)
        if watch_sync is not None:
            import weakref
            wref = weakref.ref(self)

            def _change_cb(event_type, obj):
                cache = wref()
                if cache is None:
                    return False  # cache replaced: deregister me
                cache._note_change(event_type, obj)
                return True

            watch_sync(_change_cb)
            self._watch_mode = True
        # Per-pod view signatures: uid -> (rv, node_name, vocab) for pods
        # in the scheduled view — the arena's pod-level dirty source.
        self._pod_sigs: dict = {}
        # In-memory pipelined assignments surviving between cycles
        # (Cache.TaskPipelined): pod uid -> (node, gpu_group).
        # kairace: single-writer=main
        self._pipelined: dict = {}
        # -- speculative view (overlapped pipeline, DESIGN §10) -----------
        # pod uid -> (seq, kind, node): placements/evictions whose commit
        # I/O is still in flight on the commit executor.  snapshot()
        # overlays these onto the parsed pods — a speculatively-bound pod
        # reads BOUND on its node, a speculatively-evicted one RELEASING —
        # so cycle N+1's world view includes cycle N's decisions BEFORE
        # the watch echo of the async writes arrives.  Entries are
        # sealed per cycle (seal_speculation) and cleared by the cycle's
        # commit epilogue once the writes + binder round trip finished
        # (by then the store echo carries the same state, so snapshots
        # are equivalent at EVERY point of the overlap).  Guarded by
        # _changes_lock: registered on the scheduler thread, cleared on
        # the commit-executor thread.
        self._speculative: dict = {}
        self._spec_unsealed: dict = {}   # uid -> seq (current cycle's)
        self._spec_seq = itertools.count(1)
        # Manifest-parse cache: pod uid -> (resourceVersion, template
        # PodInfo).  A pod whose resourceVersion hasn't moved re-parses
        # nothing; instances share the template's immutable pieces
        # (ResourceRequirements with its memoized vectors, affinity
        # terms), which dominates snapshot cost at fleet scale.
        # kairace: single-writer=main
        self._pod_cache: dict = {}
        # -- columnar manifest store (framework/columnar.py, DESIGN §11) --
        # Struct-of-arrays pod columns maintained O(delta) from the same
        # change stream as the mirrors; snapshot() takes an array-native
        # fast path over them (vectorized accounting + fast-instantiated
        # views, bit-identical to the object walk) and falls back to the
        # object path wholesale on resync / vocab overflow / feature-
        # bearing pods (columnar_fallback_total counts these).  All
        # column mutations happen in _apply_changes/_refresh_full on the
        # scheduler thread.
        # kairace: single-writer=main
        import os as _os
        self._columnar_enabled = _os.environ.get(
            "KAI_COLUMNAR", "1") not in ("0", "false", "off")
        from ..framework.columnar import ColumnarPods, VocabOverflow
        # kairace: single-writer=main
        self._columnar = ColumnarPods() if self._columnar_enabled else None
        self._vocab_overflow_exc = VocabOverflow
        # Delta events accumulated across apply attempts (uids of
        # changed/removed pods + touched PodGroup names): consumed by
        # snapshot() only after a SUCCESSFUL fold, so a re-queued batch
        # (exception mid-apply) never loses the events its completed
        # keys already recorded — the retry's sig-match skip would
        # otherwise leave them invisible to the O(delta) candidates
        # scan.
        # kairace: single-writer=main
        self._pending_col_events: dict = {
            "pods_changed": set(), "pods_removed": set(),
            "groups": set()}
        # Overlay sig components applied by the LAST snapshot (uid ->
        # ("bind"|"evict", node)): the columnar path diffs against this
        # to find pods whose effective state moved without a manifest
        # change (speculative entries appearing/expiring).
        # kairace: single-writer=main
        self._prev_overlay: dict = {}
        # Cached snapshot-order row index: (store.version, id(order
        # list)) -> np.ndarray of rows, rebuilt only on membership
        # change.
        self._col_rows_cache: tuple | None = None
        # Queue record batch (columnar fast path): stacked quota
        # matrices + precomputed children/ancestor tables, rebuilt only
        # when a Queue manifest changes — the per-cycle QueueInfo build
        # then slices rows out of three wholesale matrix copies instead
        # of copying three arrays per queue (the dominant snapshot cost
        # at the 10k-queue churn shape).
        # kairace: single-writer=main
        self._queue_cols: dict | None = None
        # Last columnar-path verdict for /debug/cycles + stats.
        self.last_columnar_stats: dict = {}
        # -- anti-entropy (utils/antientropy.py, DEGRADATION) -------------
        # Divergence between the columnar projection and the Pod mirror
        # quarantines the fast path: snapshots take the object path
        # (columnar_fallback_total, reason "anti-entropy") until TWO
        # consecutive clean digests re-promote it — one clean check
        # could be the same transient that diverged it.  All mutated on
        # the scheduler thread (anti_entropy_check runs there, with
        # snapshot()).
        # kairace: single-writer=main
        self._columnar_quarantined = False
        # kairace: single-writer=main
        self._col_clean_streak = 0
        self.last_anti_entropy: dict = {}
        # (owner, expression) pairs already warned about: an unsupported
        # CEL selector is re-parsed every snapshot, but the user should
        # see ONE loud event per expression, not one per cycle.
        self._warned_selectors: set = set()

    def set_fence(self, fence: str | None, epoch_provider) -> None:
        """Arm fencing: ``epoch_provider()`` is read at each write (the
        elector's current epoch — reading late keeps a long-running
        commit from carrying a pre-renewal epoch)."""
        self.fence = fence
        self.epoch_provider = epoch_provider

    def _fence_kwargs(self) -> dict:
        if self.fence is None or self.epoch_provider is None:
            return {}
        return {"epoch": self.epoch_provider(), "fence": self.fence}

    def _on_watch_resync(self) -> None:
        """A watch gap forced a re-list: the pod parse cache may hold
        entries whose MODIFIED events we never saw.  This runs on the
        WATCH thread while snapshot() may be iterating the cache on the
        scheduler thread, so only flip a flag here; the next snapshot
        drops the cache on its own thread."""
        # GIL-atomic bool latch, BY DESIGN lock-free on the watch hot
        # path: snapshot() rebinds to False BEFORE invalidating, so a
        # concurrent re-set here is never lost — it re-invalidates on
        # the next snapshot (see the consume-site comment).
        # kairace: disable=KRC001
        self._resync_pending = True
        # Lifecycle: open timelines survive a relist (their pods are
        # still real) but get flagged — accounting stays coherent across
        # the gap instead of leaking or double-opening.
        LIFECYCLE.note_resync()

    def _audit_device_selectors(self, owner: str, selectors: list) -> list:
        """Loud failure for selectors outside the supported CEL subset: a
        match-nothing translation surfaces as a plain fit error at
        schedule time, so without this the user debugs "doesn't fit"
        instead of "selector unsupported" (VERDICT Weak #7).  One event
        + counter per (owner, expression), not one per snapshot."""
        for sel in selectors:
            if not sel.get("unsupported"):
                continue
            expr = sel.get("cel", "<non-CEL selector shape>")
            key = (owner, expr)
            if key in self._warned_selectors:
                continue
            if len(self._warned_selectors) >= 4096:
                # Bounded memory in a long-lived daemon whose claim/owner
                # names churn: reset and accept occasional re-warns over
                # growing forever.
                self._warned_selectors.clear()
            self._warned_selectors.add(key)
            METRICS.inc("device_selector_unsupported")
            self.record_event(
                "DeviceSelectorUnsupported",
                f"{owner}: device selector outside the supported CEL "
                f"subset matches NOTHING (never too-wide): {expr!r}; "
                "supported: attribute ==/in, capacity >= quantity, "
                "device.driver ==, && conjunctions")
        return selectors

    def _parse_pod(self, pod: dict) -> PodInfo:
        """Fresh per-cycle PodInfo for ``pod`` (template-memoized)."""
        return self._parse_pod_template(pod).instantiate()

    def _parse_pod_template(self, pod: dict) -> PodInfo:
        """The IMMUTABLE parsed template for ``pod``, cached per
        uid+resourceVersion — what the columnar store keeps per row
        (``_col_upsert``); per-cycle instances derive from it via
        ``instantiate``/``instantiate_fast`` and may mutate freely."""
        md = pod["metadata"]
        uid = md.get("uid", md["name"])
        rv = md.get("resourceVersion")
        cached = self._pod_cache.get(uid)
        if cached is not None and rv is not None and cached[0] == rv:
            return cached[1]
        phase = pod.get("status", {}).get("phase", "Pending")
        status = PHASE_TO_STATUS.get(phase, PodStatus.UNKNOWN)
        if (status == PodStatus.PENDING
                and pod.get("spec", {}).get("nodeName")):
            # Bound but not yet started: on a real cluster the phase
            # stays Pending until the kubelet runs the pod (and in
            # envtest forever) — the scheduler must treat it as placed,
            # never re-place it (cluster_info.go snapshotPods does the
            # same via the scheduled-pod check).
            status = PodStatus.BOUND
        if md.get("deletionTimestamp"):
            status = PodStatus.RELEASING
        task = PodInfo(
            uid=uid,
            name=md["name"],
            namespace=md.get("namespace", "default"),
            subgroup=md.get("labels", {}).get(SUBGROUP_LABEL, "default"),
            res_req=_requests_to_reqreq(pod),
            status=status,
            node_name=pod.get("spec", {}).get("nodeName", ""),
            node_selector=pod.get("spec", {}).get("nodeSelector", {}),
            tolerations={t["key"] for t in pod.get("spec", {}).get(
                "tolerations", [])},
            rank=_parse_rank(md),
            labels=dict(md.get("labels", {})))
        _parse_pod_affinity(task, pod.get("spec", {}).get("affinity", {}))
        _parse_pod_predicates(task, pod)
        gpu_group = md.get("annotations", {}).get(GPU_GROUP_ANNOTATION)
        if gpu_group:
            task.gpu_group = gpu_group
        if rv is not None and md.get("resourceVersion") == rv:
            # The parsed object IS the template: callers receive
            # instantiate() copies, so the template never mutates.  The
            # rv re-check guards the overlapped pipeline: a
            # commit-executor patch racing this parse (live dicts,
            # in-memory store) must not persist a torn read under the
            # pre-bump resourceVersion — uncached, the next snapshot
            # re-parses the settled object.
            self._pod_cache[uid] = (rv, task)
        return task

    # -- snapshot ------------------------------------------------------------
    @staticmethod
    def _sig_rv(obj: dict):
        """Change signature for one object: its resourceVersion, or (for
        stores that don't stamp one) a sentinel unequal across snapshots
        so the object conservatively counts as always-changed."""
        rv = obj.get("metadata", {}).get("resourceVersion")
        return rv if rv is not None else object()

    # -- incremental store maintenance ---------------------------------------
    def _note_change(self, event_type: str, obj: dict) -> None:
        """Emit-time change hook (ANY thread): record the key, nothing
        else — snapshot() re-reads authoritative state on its own
        thread."""
        kind = obj.get("kind")
        if kind not in _CONSUMED_KINDS:
            return
        md = obj.get("metadata", {})
        key = (kind, md.get("namespace", "default"), md.get("name"))
        with self._changes_lock:
            self._changed_keys.add(key)
            if self._payload_auth:
                # Latest event wins per key; DELETED folds as None.
                self._changed_objs[key] = (None if event_type == "DELETED"
                                           else obj)

    def _wholesale_invalidate(self) -> None:
        """Watch resync: an unknown stretch of events was missed — every
        mirror, template, and parse cache rebuilds from scratch."""
        self._mirror = {k: {} for k in _CONSUMED_KINDS}
        self._order = {k: [] for k in _CONSUMED_KINDS}
        self._order_stale = {k: True for k in _CONSUMED_KINDS}
        self._kind_sigs = {k: {} for k in _CONSUMED_KINDS}
        self._node_tmpl = {}
        self._queue_tmpl = {}
        self._group_tmpl = {}
        self._aux = {}
        self._aux_dirty = {f: True for f in self._aux_dirty}
        self._pod_cache = {}
        if self._columnar is not None:
            # The columns rebuild with the mirrors at the next priming
            # re-list; clearing also resets the interned vocabularies
            # (the only recovery from a vocab overflow).
            self._columnar.clear()
        self._col_rows_cache = None
        self._queue_cols = None
        self._pending_col_events = {"pods_changed": set(),
                                    "pods_removed": set(),
                                    "groups": set()}
        with self._changes_lock:
            self._changed_keys = set()
            self._changed_objs = {}
        self._primed = False

    def _take_changes(self) -> tuple:
        with self._changes_lock:
            changes, self._changed_keys = self._changed_keys, set()
            objs, self._changed_objs = self._changed_objs, {}
        return changes, objs

    def _col_upsert(self, key: tuple, obj: dict,
                    events: dict) -> str | None:
        """Fold one pod manifest into the columnar store; returns the
        pod's uid.  A same-name recreate's replaced uid is accounted as
        removed (its signature must reap).  A vocab overflow latches in
        the store (the snapshot gate checks it) — the mirror fold must
        still proceed, so the object path stays authoritative."""
        store = self._columnar
        if store is None:
            return None
        tmpl = self._parse_pod_template(obj)
        group = obj["metadata"].get("labels", {}).get(POD_GROUP_LABEL)
        try:
            replaced = store.upsert(key, self._sig_rv(obj), tmpl, group)
        except self._vocab_overflow_exc:
            return tmpl.uid
        if replaced is not None:
            events["pods_removed"].add(replaced)
        return tmpl.uid

    def _col_remove(self, key: tuple, events: dict) -> None:
        store = self._columnar
        if store is None:
            return
        uid = store.remove(key)
        if uid is not None:
            events["pods_removed"].add(uid)

    def _apply_changes(self, changes: set, payloads: dict | None = None
                       ) -> dict:
        """Fold accumulated dirty keys into the mirrors (watch mode) and
        the columnar store; delta events (changed/removed pod uids +
        touched PodGroup names — the columnar snapshot's O(delta) dirty
        source) accumulate in ``_pending_col_events``.  On ANY exception
        the whole batch is re-queued (folding is idempotent): a
        half-applied delta must not vanish — an object it carried would
        stay invisible to scheduling until the next resync.  Within one
        key the columnar fold + event record happen BEFORE the
        mirror/sig write, so a retry's sig-match skip can only ever skip
        keys whose columnar state and events already landed.

        ``payloads`` (detached-payload substrates, i.e. the wire): the
        latest watch event object per key — folded directly instead of
        re-reading via get_opt, so a churn burst costs ZERO list/get
        round trips.  A key dirtied without a payload (or on the
        in-memory store, whose events reference live dicts) still
        re-reads authoritative state."""
        changed = {k: 0 for k in _HOT_KINDS}
        events = self._pending_col_events
        use_payloads = self._payload_auth and payloads is not None
        try:
            for kind, ns, name in changes:
                key = (ns, name)
                mirror = self._mirror[kind]
                full_key = (kind, ns, name)
                if use_payloads and full_key in payloads:
                    obj = payloads[full_key]
                else:
                    obj = self.api.get_opt(kind, name, ns)
                if obj is None:
                    if key not in mirror:
                        continue  # created+deleted between snapshots
                    if kind == "Pod":
                        self._col_remove(key, events)
                    elif kind == "PodGroup":
                        events["groups"].add(name)
                    mirror.pop(key, None)
                    self._kind_sigs[kind].pop(key, None)
                    self._order_stale[kind] = True
                    self._drop_template(kind, name)
                else:
                    sig = self._sig_rv(obj)
                    if key in mirror \
                            and self._kind_sigs[kind].get(key) == sig:
                        # Duplicate dirty mark (e.g. queued during the
                        # priming list): state already folded — counting
                        # it would force a spurious arena rebuild.
                        continue
                    if kind == "Pod":
                        uid = self._col_upsert(key, obj, events)
                        if uid is not None:
                            events["pods_changed"].add(uid)
                    elif kind == "PodGroup" and key not in mirror:
                        events["groups"].add(name)
                    if key not in mirror:
                        self._order_stale[kind] = True
                    mirror[key] = obj
                    self._kind_sigs[kind][key] = sig
                if kind in changed:
                    changed[kind] += 1
                else:
                    for family in _AUX_FAMILIES[kind]:
                        self._aux_dirty[family] = True
        except BaseException:
            with self._changes_lock:
                self._changed_keys |= changes
                if use_payloads:
                    for k, v in payloads.items():
                        # A newer payload recorded since the take wins.
                        self._changed_objs.setdefault(k, v)
            raise
        return changed

    def _drop_template(self, kind: str, name: str) -> None:
        """Retire the parse template of a deleted object (the per-cycle
        builds also prune on shrink, but equal-count churn — one delete
        plus one add per cycle — would otherwise never trigger it)."""
        if kind == "Node":
            self._node_tmpl.pop(name, None)
        elif kind == "Queue":
            self._queue_tmpl.pop(name, None)
        elif kind == "PodGroup":
            self._group_tmpl.pop(name, None)

    def _refresh_full(self) -> dict:
        """Fallback / priming path: re-list every consumed kind and diff
        resourceVersions.  The parse templates still memoize, so even
        this path never re-parses an unchanged manifest.  Delta events
        accumulate in ``_pending_col_events`` exactly as in
        ``_apply_changes``, so the columnar fast path works on re-list
        substrates too."""
        METRICS.inc("cluster_cache_full_refresh_total")
        changed = {k: 0 for k in _HOT_KINDS}
        events = self._pending_col_events
        for kind in _CONSUMED_KINDS:
            sigs = {}
            mirror = {}
            n_changed = 0
            old_sigs = self._kind_sigs[kind]
            for obj in self.api.list(kind):
                md = obj.get("metadata", {})
                key = (md.get("namespace", "default"), md.get("name"))
                sig = self._sig_rv(obj)
                mirror[key] = obj
                sigs[key] = sig
                if old_sigs.get(key) != sig:
                    n_changed += 1
                    if kind == "Pod":
                        uid = self._col_upsert(key, obj, events)
                        if uid is not None:
                            events["pods_changed"].add(uid)
                    elif kind == "PodGroup" and key not in old_sigs:
                        events["groups"].add(key[1])
            n_changed += sum(1 for key in old_sigs if key not in sigs)
            for key in old_sigs:
                if key not in sigs:
                    self._drop_template(kind, key[1])
                    if kind == "Pod":
                        self._col_remove(key, events)
                    elif kind == "PodGroup":
                        events["groups"].add(key[1])
            if mirror.keys() != self._mirror[kind].keys():
                self._order_stale[kind] = True
            self._mirror[kind] = mirror
            self._kind_sigs[kind] = sigs
            if n_changed:
                if kind in changed:
                    changed[kind] = n_changed
                else:
                    for family in _AUX_FAMILIES[kind]:
                        self._aux_dirty[family] = True
        return changed

    def _iter_order(self, kind: str) -> list:
        """Mirror keys in api.list order (sorted by name), cached until
        the kind's membership changes."""
        if self._order_stale[kind]:
            self._order[kind] = sorted(self._mirror[kind],
                                       key=lambda key: key[1])
            self._order_stale[kind] = False
        return self._order[kind]

    # -- anti-entropy (utils/antientropy.py, DEGRADATION "wire faults") ------
    def content_digest(self) -> dict:
        """Per-kind digest of the mirrors — the replica half of the
        anti-entropy exchange, same shape as the store's ``digest()``."""
        from ..utils.antientropy import obj_hash64
        out = {}
        for kind in sorted(_CONSUMED_KINDS):
            mirror = self._mirror[kind]
            if not mirror:
                continue
            h = 0
            for obj in mirror.values():
                h ^= obj_hash64(obj)
            out[kind] = {"count": len(mirror), "hash": f"{h:016x}"}
        return out

    def _mirror_pod_projection(self) -> int:
        """The Pod mirror's fold-identity projection (ns, name, uid,
        rv-signature) — the comparand of
        ``ColumnarPods.projection_digest``."""
        from ..utils.antientropy import obj_hash64
        h = 0
        for (ns, name), obj in self._mirror["Pod"].items():
            md = obj.get("metadata", {})
            rv = md.get("resourceVersion")
            h ^= obj_hash64([ns, name, md.get("uid"),
                             rv if isinstance(rv, str) else None])
        return h

    def _rebuild_columnar_from_mirror(self) -> None:
        """Targeted columnar repair: re-fold every mirrored pod into a
        cleared store (templates memoize, so this re-parses nothing
        whose manifest is unchanged).  Every live uid lands in the
        pending delta events, so the next snapshot conservatively
        treats the whole population as dirty — correct, and bounded by
        one cycle."""
        store = self._columnar
        if store is None:
            return
        store.clear()
        self._col_rows_cache = None
        events = self._pending_col_events
        for (ns, name), obj in self._mirror["Pod"].items():
            uid = self._col_upsert((ns, name), obj, events)
            if uid is not None:
                events["pods_changed"].add(uid)

    def _enqueue_repair(self, kind: str) -> int:
        """Targeted repair re-list of ONE divergent kind: diff the live
        listing against the mirror and enqueue every difference through
        the normal dirty-key path (the next snapshot folds it with the
        machinery the parity rings prove).  Signatures of enqueued keys
        are dropped so content divergence at an UNCHANGED
        resourceVersion — the corrupted-frame case — re-folds instead
        of being skipped by the sig-match fast path.  Returns the
        number of keys enqueued."""
        listed = {}
        for obj in self.api.list(kind):
            md = obj.get("metadata", {})
            listed[(md.get("namespace", "default"), md.get("name"))] = obj
        stale = [key for key in self._mirror[kind] if key not in listed]
        repaired = 0
        with self._changes_lock:
            # setdefault: a watch payload recorded since our list()
            # returned is NEWER than the listing — it wins (the
            # _apply_changes re-queue pattern); clobbering it would
            # regress the mirror to the older listed content with no
            # event left to re-deliver it.
            for (ns, name), obj in listed.items():
                self._changed_keys.add((kind, ns, name))
                if self._payload_auth:
                    self._changed_objs.setdefault((kind, ns, name), obj)
                repaired += 1
            for ns, name in stale:
                self._changed_keys.add((kind, ns, name))
                if self._payload_auth:
                    self._changed_objs.setdefault((kind, ns, name), None)
                repaired += 1
        self._kind_sigs[kind].clear()
        METRICS.inc("anti_entropy_repairs_total", kind=kind)
        return repaired

    def anti_entropy_check(self) -> dict:
        """Periodic anti-entropy pass: compare the mirrors (and the
        columnar projection) against the store's authoritative digest.

        Runs on the scheduler thread — the mirrors' single writer — so
        the local state is frozen for the duration.  The comparison is
        made exact by ordering: local digest first, THEN the store's
        (which can only be newer), then a dirty-queue re-check — any
        event that could make the two legitimately unequal has either
        marked a key dirty (skip, reason "dirty") or not yet been
        delivered by the watch (skip, reason "lagging", wire dialect).
        What remains unequal after that is real divergence: the wire
        lied, or a fold bug dropped state.  Divergent kinds count
        ``cache_divergence_total{kind=}`` and are repaired by a
        targeted re-list; a diverged columnar projection quarantines
        the array fast path until two consecutive clean digests
        re-promote it (``columnar_repromote_total``)."""
        from ..utils.antientropy import diverged_kinds
        out: dict = {"checked": False, "diverged": [], "columnar_ok": True,
                     "repaired_keys": 0, "skipped": None,
                     "quarantined": self._columnar_quarantined}
        digest_fn = getattr(self.api, "digest", None)
        if digest_fn is None or not self._primed:
            out["skipped"] = ("unsupported" if digest_fn is None
                              else "unprimed")
            self.last_anti_entropy = out
            return out
        with self._changes_lock:
            dirty = bool(self._changed_keys)
        if dirty or self._resync_pending:
            METRICS.inc("anti_entropy_skipped_total", reason="dirty")
            out["skipped"] = "dirty"
            self.last_anti_entropy = out
            return out
        local = self.content_digest()
        col_ok = True
        if self._columnar is not None:
            col_ok = (self._columnar.projection_digest()
                      == self._mirror_pod_projection())
        remote = digest_fn()
        remote_seq = remote.get("seq")
        cursor = getattr(self.api, "watch_cursor", None)
        if remote_seq is not None and cursor is not None \
                and cursor < remote_seq:
            # Events between our cursor and the digest's seq are in
            # flight, not lost — compare at the next quiescent point.
            METRICS.inc("anti_entropy_skipped_total", reason="lagging")
            out["skipped"] = "lagging"
            self.last_anti_entropy = out
            return out
        with self._changes_lock:
            dirty = bool(self._changed_keys)
        if dirty or self._resync_pending:
            # A delta landed while we were digesting: the store moved
            # under us, legitimately.
            METRICS.inc("anti_entropy_skipped_total", reason="dirty")
            out["skipped"] = "dirty"
            self.last_anti_entropy = out
            return out
        METRICS.inc("anti_entropy_checks_total")
        out["checked"] = True
        diverged = diverged_kinds(local, remote.get("kinds", {}),
                                  _CONSUMED_KINDS)
        out["diverged"] = diverged
        out["columnar_ok"] = col_ok
        for kind in diverged:
            METRICS.inc("cache_divergence_total", kind=kind)
            LOG.warning("anti-entropy: cache digest diverged from the "
                        "store for kind %s — repairing with a targeted "
                        "re-list", kind)
            out["repaired_keys"] += self._enqueue_repair(kind)
        if diverged and self._columnar is not None:
            # The columns fold from the mirrors: a poisoned mirror may
            # have poisoned them identically (projection digests agree
            # on the lie), so a mirror repair always rebuilds the
            # columns from the repaired truth too.
            col_ok = False
        if not col_ok:
            METRICS.inc("cache_divergence_total", kind="_columnar")
            self._col_clean_streak = 0
            if not self._columnar_quarantined:
                LOG.warning("anti-entropy: columnar projection diverged "
                            "from the Pod mirror — quarantining the "
                            "fast path (object path authoritative)")
            self._columnar_quarantined = True
            self._rebuild_columnar_from_mirror()
        elif self._columnar_quarantined:
            self._col_clean_streak += 1
            if self._col_clean_streak >= 2:
                self._columnar_quarantined = False
                self._col_clean_streak = 0
                METRICS.inc("columnar_repromote_total")
                LOG.info("anti-entropy: two consecutive clean digests — "
                         "columnar fast path re-promoted")
        METRICS.set_gauge("columnar_quarantined",
                          1.0 if self._columnar_quarantined else 0.0)
        out["quarantined"] = self._columnar_quarantined
        self.last_anti_entropy = out
        return out

    # -- parse layers (template-memoized) ------------------------------------
    def _parse_node(self, n: dict) -> NodeInfo:
        spec = n.get("status", {}).get("allocatable", {})
        gpu_mem = n.get("metadata", {}).get("annotations", {}).get(
            "nvidia.com/gpu.memory")
        return NodeInfo(
            n["metadata"]["name"],
            rs.vec_from_spec(spec.get("cpu", "0"),
                             spec.get("memory", "0"),
                             float(spec.get("nvidia.com/gpu", 0))),
            labels=n.get("metadata", {}).get("labels", {}),
            taints={t["key"] for t in n.get("spec", {}).get(
                "taints", [])},
            gpu_memory_per_device=rs.parse_memory(gpu_mem)
            if gpu_mem else 16 * 2 ** 30,
            max_pods=int(spec.get("pods", 110)),
            mig_capacity={k: float(v) for k, v in spec.items()
                          if k.startswith("nvidia.com/mig-")})

    def _build_nodes(self) -> dict:
        mirror = self._mirror["Node"]
        tmpls = self._node_tmpl
        nodes = {}
        for key in self._iter_order("Node"):
            n = mirror[key]
            name = n["metadata"]["name"]
            sig = self._sig_rv(n)
            ent = tmpls.get(name)
            if ent is None or ent[0] != sig:
                tmpls[name] = ent = (sig, self._parse_node(n))
            nodes[name] = ent[1].instantiate()
        if len(tmpls) > len(nodes):
            self._node_tmpl = {name: ent for name, ent in tmpls.items()
                               if name in nodes}
        return nodes

    def _parse_queue(self, q: dict) -> QueueInfo:
        spec = q.get("spec", {})
        info = QueueInfo(
            q["metadata"]["name"],
            parent=spec.get("parentQueue"),
            priority=spec.get("priority", 0),
            creation_ts=float(q["metadata"].get("creationTimestamp",
                                                0) or 0),
            quota=QueueQuota.from_spec(
                deserved=_quota_vec(spec.get("deserved")),
                limit=_quota_vec(spec.get("limit")),
                over_quota_weight=spec.get("overQuotaWeight", 1.0)),
            preempt_min_runtime=spec.get("preemptMinRuntime"),
            reclaim_min_runtime=spec.get("reclaimMinRuntime"))
        # Spec-level signature RIDES THE TEMPLATE (never a side table):
        # every consumer of the parse — object path, columnar path,
        # template drops, wholesale invalidation — stays coherent by
        # construction, because a re-parse always carries its own spec's
        # signature (the columnar build compares against exactly this).
        info._spec_sig = repr((spec, q["metadata"].get(
            "creationTimestamp")))
        return info

    def _build_queues(self) -> dict:
        mirror = self._mirror["Queue"]
        tmpls = self._queue_tmpl
        queues = {}
        for key in self._iter_order("Queue"):
            q = mirror[key]
            name = q["metadata"]["name"]
            sig = self._sig_rv(q)
            ent = tmpls.get(name)
            if ent is None or ent[0] != sig:
                tmpls[name] = ent = (sig, self._parse_queue(q))
            t = ent[1]
            # Per-cycle instance: quota arrays copied (plugins may divide
            # in place), children rebuilt below.
            queues[name] = QueueInfo(
                t.uid, t.name, t.parent, [], t.priority, t.creation_ts,
                QueueQuota(t.quota.deserved.copy(), t.quota.limit.copy(),
                           t.quota.over_quota_weight.copy()),
                t.preempt_min_runtime, t.reclaim_min_runtime)
        if len(tmpls) > len(queues):
            self._queue_tmpl = {name: ent for name, ent in tmpls.items()
                                if name in queues}
        for name, q in queues.items():
            if q.parent and q.parent in queues \
                    and name not in queues[q.parent].children:
                queues[q.parent].children.append(name)
        return queues

    def _build_queues_columnar(self) -> dict:
        """Array-native ``_build_queues`` (DESIGN §11): quota vectors
        live as stacked [Q, R] matrices rebuilt only when a Queue
        manifest changes; each cycle copies the three matrices WHOLESALE
        and hands every QueueInfo row views — same values, same
        per-cycle isolation (plugins divide quota in place), a fraction
        of the 3-arrays-per-queue copy cost at 10k queues.  Children
        lists and parent-chain (ancestor) tables precompute with the
        batch; the proportion roll-up reuses the chains."""
        order = self._iter_order("Queue")
        mirror = self._mirror["Queue"]
        tmpls = self._queue_tmpl
        templates = []
        for key in order:
            q = mirror[key]
            name = q["metadata"]["name"]
            sig = self._sig_rv(q)
            ent = tmpls.get(name)
            if ent is None or ent[0] != sig:
                spec_sig = repr((q.get("spec"),
                                 q["metadata"].get("creationTimestamp")))
                if ent is not None \
                        and getattr(ent[1], "_spec_sig",
                                    None) == spec_sig:
                    # Status-only churn: the rv moved but nothing
                    # QueueInfo reads did — keep the template (and the
                    # stacked rows derived from it).  The signature
                    # lives ON the template (see _parse_queue), so an
                    # object-path re-parse in between can never leave a
                    # stale match behind.
                    ent = (sig, ent[1])
                else:
                    ent = (sig, self._parse_queue(q))
                tmpls[name] = ent
            templates.append(ent[1])
        if len(tmpls) > len(templates):
            live = {key[1] for key in order}
            self._queue_tmpl = {n: e for n, e in tmpls.items()
                                if n in live}
        qc = self._queue_cols
        same = (qc is not None and qc["order"] is order
                and len(qc["templates"]) == len(templates)
                and all(a is b for a, b in zip(qc["templates"],
                                               templates)))
        if not same:
            n = len(templates)
            if n:
                des = np.stack([t.quota.deserved for t in templates])
                lim = np.stack([t.quota.limit for t in templates])
                oqw = np.stack([t.quota.over_quota_weight
                                for t in templates])
            else:
                des = lim = oqw = np.zeros((0, rs.NUM_RES))
            pos = {t.name: i for i, t in enumerate(templates)}
            children: list = [[] for _ in range(n)]
            for t in templates:
                if t.parent and t.parent in pos:
                    children[pos[t.parent]].append(t.name)
            # Ancestor chains (own idx first) for the proportion
            # roll-up's expanded add.at — identical to the per-queue
            # parent walk.
            chains = []
            depth = 1
            for i, t in enumerate(templates):
                chain = [i]
                seen = {i}
                parent = t.parent
                while parent:
                    j = pos.get(parent)
                    if j is None or j in seen:
                        break
                    chain.append(j)
                    seen.add(j)
                    parent = templates[j].parent
                chains.append(chain)
                depth = max(depth, len(chain))
            anc = np.full((n, depth), -1, np.int64)
            for i, chain in enumerate(chains):
                anc[i, :len(chain)] = chain
            self._queue_cols = qc = {
                "order": order, "templates": templates, "des": des,
                "lim": lim, "oqw": oqw, "children": children,
                "anc": anc}
        templates = qc["templates"]
        des = qc["des"].copy()
        lim = qc["lim"].copy()
        oqw = qc["oqw"].copy()
        children = qc["children"]
        queues = {}
        for i, t in enumerate(templates):
            queues[t.name] = QueueInfo(
                t.uid, t.name, t.parent, list(children[i]), t.priority,
                t.creation_ts, QueueQuota(des[i], lim[i], oqw[i]),
                t.preempt_min_runtime, t.reclaim_min_runtime)
        return queues

    def _parse_group(self, pg_obj: dict) -> _GroupTmpl:
        spec = pg_obj.get("spec", {})
        topo = spec.get("topology") or {}
        t = _GroupTmpl()
        t.name = pg_obj["metadata"]["name"]
        t.namespace = pg_obj["metadata"].get("namespace", "default")
        t.queue_id = spec.get("queue", "default")
        t.priority = spec.get("priority", 50)
        t.min_available = spec.get("minMember", 1)
        t.preemptible = spec.get("preemptible", True)
        t.creation_ts = float(pg_obj["metadata"].get(
            "creationTimestamp", 0) or 0)
        t.topology_name = topo.get("name")
        t.required_topology_level = topo.get("required")
        t.preferred_topology_level = topo.get("preferred")
        t.pod_sets = tuple(
            (ps["name"], ps["minAvailable"],
             (ps.get("topology") or {}).get("name"),
             (ps.get("topology") or {}).get("required"),
             (ps.get("topology") or {}).get("preferred"))
            for ps in spec.get("podSets") or [])
        t.last_start_ts = pg_obj.get("status", {}).get(
            "lastStartTimestamp")
        t.node_pool = pg_obj["metadata"].get("labels", {}).get(
            "kai.scheduler/node-pool")
        return t

    def _build_groups(self) -> dict:
        mirror = self._mirror["PodGroup"]
        tmpls = self._group_tmpl
        podgroups: dict[str, PodGroupInfo] = {}
        for key in self._iter_order("PodGroup"):
            pg_obj = mirror[key]
            name = pg_obj["metadata"]["name"]
            sig = self._sig_rv(pg_obj)
            ent = tmpls.get(name)
            if ent is None or ent[0] != sig:
                tmpls[name] = ent = (sig, self._parse_group(pg_obj))
            podgroups[name] = ent[1].instantiate()
        if len(tmpls) > len(podgroups):
            self._group_tmpl = {name: ent for name, ent in tmpls.items()
                                if name in podgroups}
        return podgroups

    def snapshot(self) -> ClusterInfo:
        import time as _time
        t0 = _time.perf_counter()
        arena = self.arena
        resync_fired = False
        if self._resync_pending:
            # Deferred watch-gap invalidation (see _on_watch_resync):
            # rebind, don't clear() — the watch thread may set the flag
            # again concurrently, which the NEXT snapshot then honors.
            # A resync means an unknown stretch of events was missed:
            # the incremental store AND the arena (packed arrays, device
            # residency) invalidate wholesale along with the pod parse
            # cache.
            self._resync_pending = False
            self._wholesale_invalidate()
            arena.invalidate("watch-resync")
            resync_fired = True
        # Frozen copy of the speculative view (overlapped commits whose
        # writes are still in flight), taken BEFORE the queued watch
        # changes: an epilogue on the commit executor queues a bind's
        # echo and THEN clears its entry, so a copy taken after the
        # changes could miss both — the pod reads pending and is bound
        # twice.  Copied first, either the entry is still here or its
        # echo is already in the queue taken below.
        with self._changes_lock:
            speculative = dict(self._speculative)
        was_primed = self._primed
        if self._watch_mode and self._primed:
            changed = self._apply_changes(*self._take_changes())
        else:
            # The full refresh subsumes every change marked so far:
            # discard the backlog FIRST (keys marked while the listing
            # runs stay queued for the next snapshot), or the first
            # delta snapshot after priming would see the whole setup
            # history as dirty and force a spurious full rebuild.
            self._take_changes()
            changed = self._refresh_full()
            self._primed = True
        # Consume the fold's accumulated delta events only now, after
        # it SUCCEEDED — events recorded by a re-queued (failed) apply
        # survive here for the retry's snapshot.
        events = self._pending_col_events
        self._pending_col_events = {"pods_changed": set(),
                                    "pods_removed": set(),
                                    "groups": set()}
        if changed["Node"]:
            # Any Node add/remove/modify is a topology-class change: the
            # static arrays, label/taint codec, and node axis may all
            # shift — rebuild from scratch (the steady-state contract is
            # that this never fires without real node churn).
            arena.note_full("node-change")
        if changed["Queue"]:
            arena.note_tasks()  # queue arrays (and job gating) rebuild
        if changed["PodGroup"]:
            arena.note_tasks()  # job arrays / candidate sets rebuild

        cluster = None
        reason = self._columnar_verdict(was_primed, resync_fired)
        if reason is None:
            try:
                with TRACER.span("snapshot_columnar",
                                 kind="snapshot_columnar") as sp:
                    cluster = self._snapshot_columnar(changed, events, sp,
                                                      speculative)
            except Exception:
                # The fast path must degrade, never crash the cycle; the
                # parity ring (tests/test_columnar_store.py) keeps this
                # branch honest — it asserts fast-path snapshots DO
                # happen, so a silent always-fallback fails there.
                from ..utils.logging import LOG
                LOG.warning("columnar snapshot failed; falling back to "
                            "the object path", exc_info=True)
                reason = "error"
        if cluster is None:
            if reason not in ("disabled", "priming"):
                # Priming/disabled are not degradations; resync, vocab
                # overflow, feature-bearing pods, and fast-path errors
                # are — tools/fleet_budget.py gates this at 0 on the
                # warm fleet shape.
                METRICS.inc("columnar_fallback_total")
            self.last_columnar_stats = {"path": "object",
                                        "reason": reason}
            cluster = self._snapshot_objects(changed, speculative)
        self.last_snapshot_stats["columnar"] = self.last_columnar_stats
        METRICS.observe("snapshot_build_latency_ms",
                        (_time.perf_counter() - t0) * 1000.0)
        return cluster

    def _columnar_verdict(self, was_primed: bool,
                          resync_fired: bool) -> str | None:
        """None = take the array-native path; otherwise the fallback
        reason (DESIGN §11 invalidation table)."""
        if not self._columnar_enabled:
            return "disabled"
        if resync_fired:
            return "resync"
        if not was_primed:
            return "priming"
        if self._columnar_quarantined:
            # Anti-entropy found the columns disagreeing with the
            # mirrors: the object path is authoritative until two
            # consecutive clean digests re-promote the fast path.
            return "anti-entropy"
        store = self._columnar
        if store.overflowed:
            return "vocab-overflow"
        from ..framework.columnar import FLAG_COMPLEX
        if np.count_nonzero(
                store.flags[:store.n_alloc] & FLAG_COMPLEX):
            # Fractional/MIG/gpu-memory/storage/affinity-bearing pods
            # need accounting the vectorized path does not model.
            return "complex-pods"
        if self._mirror["PersistentVolumeClaim"] \
                or self._mirror["CSIStorageCapacity"]:
            # Schedule-time CSI storage links claims onto pods and nodes
            # at snapshot build — object path only.
            return "storage"
        return None

    def _build_cluster(self, nodes: dict, podgroups: dict, queues: dict,
                       prewired: bool) -> ClusterInfo:
        """Shared tail of both snapshot paths: per-cycle aux views at
        clone depths + the ClusterInfo itself."""
        aux = self._build_aux()
        # Per-cycle views of the aux caches, at exactly the copy depths
        # ClusterInfo.clone() uses (sessions mutate these containers the
        # same way they mutate a clone's).
        topologies = dict(aux["topologies"])
        resource_claims = {k: dict(v)
                           for k, v in aux["resource_claims"].items()}
        resource_slices = {n: {c: list(d) for c, d in by_class.items()}
                           for n, by_class in
                           aux["resource_slices"].items()}
        device_classes = dict(aux["device_classes"])
        config_maps = set(aux["config_maps"])
        pvcs = {k: dict(v) for k, v in aux["pvcs"].items()}
        storage_classes = dict(aux["storage_classes"])
        storage_claims = {k: c.clone()
                          for k, c in aux["storage_claims"].items()}
        storage_capacities = {}
        for uid, cap in aux["storage_capacities"].items():
            cc = cap.clone()
            cc.provisioned_pvcs = {}  # re-derived by linking + add_task
            storage_capacities[uid] = cc
        return ClusterInfo(nodes, podgroups, queues, topologies,
                           now=self.now_fn(),
                           resource_claims=resource_claims,
                           config_maps=config_maps, pvcs=pvcs,
                           resource_slices=resource_slices,
                           storage_classes=storage_classes,
                           storage_claims=storage_claims,
                           storage_capacities=storage_capacities,
                           device_classes=device_classes,
                           prewired=prewired)

    def _snapshot_columnar(self, changed: dict, events: dict,
                           span, speculative: dict) -> ClusterInfo:
        """Array-native snapshot build (DESIGN §11): one index build +
        vectorized segment reductions over the columnar store, with
        per-cycle ``PodInfo`` views fast-instantiated from row
        templates.  Bit-identical to ``_snapshot_objects`` — every
        float accumulation below runs in the SAME order as the object
        walk it replaces (``np.add.at`` applies sequentially in index
        order), and the dirty/arena bookkeeping is computed O(delta)
        from the fold's change events instead of an O(pods) rescan."""
        from ..framework.columnar import (FLAG_SELECTOR, FLAG_TOLERATIONS,
                                          _ACTIVE_ALLOCATED, _PENDING,
                                          _RELEASING)
        store = self._columnar
        arena = self.arena
        _BOUND = int(PodStatus.BOUND)
        _DONE = (int(PodStatus.SUCCEEDED), int(PodStatus.FAILED),
                 _RELEASING)

        nodes = self._build_nodes()
        queues = self._build_queues_columnar()
        podgroups = self._build_groups()

        ordered_keys = self._iter_order("Pod")
        rcache = self._col_rows_cache
        if rcache is not None and rcache[0] == store.version \
                and rcache[1] is ordered_keys:
            rows = rcache[2]
        else:
            rows = store.live_rows(ordered_keys)
            self._col_rows_cache = (store.version, ordered_keys, rows)

        # -- index build: group/node id -> snapshot position lookups ----
        gvocab = store.group_vocab
        n_gvocab = len(gvocab.strs)
        glist = list(podgroups.values())
        gpos_lut = np.full(n_gvocab + 1, -1, np.int64)
        for pos, pg in enumerate(glist):
            gid = gvocab.ids.get(pg.uid)
            if gid is not None:
                gpos_lut[gid] = pos
        gids = store.group_id[rows]
        gpos = gpos_lut[np.where(gids >= 0, gids, n_gvocab)]
        live_mask = gpos >= 0
        live = rows[live_mask]
        # Wire order: groups outer (podgroups insertion order = name
        # order), pods inner (name order) — the exact walk order of
        # _wire_tasks_to_nodes / queue_aggregates on the object path.
        order = np.argsort(gpos[live_mask], kind="stable")
        wrows = live[order]
        gpos_w = gpos[live_mask][order]

        status = store.status[wrows]          # fancy index: fresh copy
        node_ids = store.node_id[wrows]
        reqs = store.req[wrows]
        flags = store.flags[wrows]

        node_order = sorted(nodes)
        node_pos = {name: i for i, name in enumerate(node_order)}
        nvocab = store.node_vocab
        nv_lut = np.full(len(nvocab.strs) + 1, -1, np.int64)
        for name, nid in nvocab.ids.items():
            idx = node_pos.get(name)
            if idx is not None:
                nv_lut[nid] = idx
        eff_idx = nv_lut[np.where(node_ids >= 0, node_ids,
                                  len(nvocab.strs))]

        # -- speculative overlay (DESIGN §10), applied on the columns ----
        applied_overlay: dict = {}
        overlay_names: dict = {}
        n_overlaid = 0
        row_pos: dict = {}
        if speculative:
            row_pos = {int(r): i for i, r in enumerate(wrows)}
            for uid, (_seq, kind, node) in speculative.items():
                srow = store.uid_rows.get(uid)
                i = row_pos.get(srow) if srow is not None else None
                if i is None:
                    continue
                st = int(status[i])
                if kind == "bind":
                    if st == _PENDING and node_ids[i] < 0 \
                            and node in nodes:
                        status[i] = _BOUND
                        eff_idx[i] = node_pos[node]
                        applied_overlay[uid] = ("bind", node)
                        overlay_names[i] = node
                        n_overlaid += 1
                    elif st == _RELEASING and node_ids[i] < 0 \
                            and node in nodes:
                        # Deleted/evicted before the bind echo landed:
                        # overlay the node, keep the terminal state.
                        eff_idx[i] = node_pos[node]
                        applied_overlay[uid] = ("bind", node)
                        overlay_names[i] = node
                        n_overlaid += 1
                elif kind == "evict":
                    if st not in _DONE:
                        status[i] = _RELEASING
                        applied_overlay[uid] = ("evict", node)
                        n_overlaid += 1

        # -- vectorized accounting (bit-identical: same order, same
        #    expressions as NodeInfo.add_task / queue_aggregates) -------
        n_res = reqs.shape[1]
        n_nodes = len(node_order)
        active = (status & _ACTIVE_ALLOCATED) > 0
        releasing = status == _RELEASING
        pending = status == _PENDING
        placed = eff_idx >= 0
        used_mat = np.zeros((n_nodes, n_res))
        rel_mat = np.zeros((n_nodes, n_res))
        acct = placed & (active | releasing)
        np.add.at(used_mat, eff_idx[acct], reqs[acct])
        relp = placed & releasing
        np.add.at(rel_mat, eff_idx[relp], reqs[relp])
        for i, name in enumerate(node_order):
            nd = nodes[name]
            nd.used = used_mat[i]
            nd.releasing = rel_mat[i]

        q_uids = list(queues)
        qpos = {qid: i for i, qid in enumerate(q_uids)}
        nq = max(len(q_uids), 1)
        gq_lut = np.full(max(len(glist), 1) + 1, -1, np.int64)
        for pos, pg in enumerate(glist):
            gq_lut[pos] = qpos.get(pg.queue_id, -1)
        qidx = gq_lut[gpos_w] if gpos_w.size else gpos_w
        qok = qidx >= 0
        alloc_mat = np.zeros((nq, n_res))
        req_mat = np.zeros((nq, n_res))
        am = qok & active
        np.add.at(alloc_mat, qidx[am], reqs[am])
        rm = qok & (active | pending)
        np.add.at(req_mat, qidx[rm], reqs[rm])
        allocated = {qid: alloc_mat[i] for i, qid in enumerate(q_uids)}
        requested = {qid: req_mat[i] for i, qid in enumerate(q_uids)}

        ng = max(len(glist), 1)
        pend_counts = np.bincount(gpos_w[pending], minlength=ng)
        rel_counts = np.bincount(gpos_w[releasing], minlength=ng)
        for pos, pg in enumerate(glist):
            pg._pending_count = int(pend_counts[pos])
            pg._releasing_count = int(rel_counts[pos])

        # -- per-cycle views: PodInfo.from_columns per row ---------------
        node_list = [nodes[name] for name in node_order]
        tmpl_col = store.tmpl
        wrows_l = wrows.tolist()
        gpos_l = gpos_w.tolist()
        eff_l = eff_idx.tolist()
        tasks = []
        for i, row in enumerate(wrows_l):
            task = tmpl_col[row].instantiate_fast()
            pg = glist[gpos_l[i]]
            task.job_id = pg.uid
            pg.pods[task.uid] = task
            ps = pg.pod_sets.get(task.subgroup)
            if ps is None:
                ps = pg.pod_sets.get("default")
                if ps is None:
                    ps = PodSet("default", 1)
                    pg.pod_sets["default"] = ps
            ps.pods[task.uid] = task
            ni = eff_l[i]
            if ni >= 0:
                node_list[ni].pod_infos[task.uid] = task
            tasks.append(task)
        if applied_overlay:
            for uid in applied_overlay:
                i = row_pos[store.uid_rows[uid]]
                task = tasks[i]
                task.status = PodStatus(int(status[i]))
                nm = overlay_names.get(i)
                if nm:
                    task.node_name = nm

        # -- pending extras: lifecycle + pipelined nominations -----------
        seen_uids = set()
        for i in np.nonzero(pending)[0].tolist():
            task = tasks[i]
            pg = glist[gpos_l[i]]
            seen_uids.add(task.uid)
            LIFECYCLE.note(task.uid, "snapshotted", podgroup=pg.uid,
                           queue=pg.queue_id)
            if task.uid in self._pipelined:
                node_name, _pgroup = self._pipelined[task.uid]
                if node_name in nodes:
                    task.nominated_node = node_name
        if self._pipelined:
            self._pipelined = {
                uid: v for uid, v in self._pipelined.items()
                if uid in seen_uids}
        for uid in events["pods_removed"]:
            self._pod_cache.pop(uid, None)

        # -- O(delta) signature/arena bookkeeping ------------------------
        candidates = (events["pods_changed"] | events["pods_removed"]
                      | set(applied_overlay) | set(self._prev_overlay))
        for gname in events["groups"]:
            gid = gvocab.ids.get(gname)
            if gid is not None:
                for r in rows[gids == gid].tolist():
                    candidates.add(store.uid[r])
        for uid in candidates:
            row = store.uid_rows.get(uid)
            present = False
            if row is not None:
                gid = int(store.group_id[row])
                present = gid >= 0 and gpos_lut[gid] >= 0
            prev_sig = self._pod_sigs.get(uid)
            if not present:
                if prev_sig is not None:
                    arena.note_tasks()
                    if prev_sig[2]:
                        arena.note_vocab()
                    if prev_sig[1]:
                        arena.note_nodes((prev_sig[1],))
                    LIFECYCLE.mark_vanished(uid)
                    del self._pod_sigs[uid]
                continue
            comp = applied_overlay.get(uid)
            if comp is not None and comp[0] == "bind":
                node_name = comp[1]
            else:
                node_name = nvocab.str_of(int(store.node_id[row]))
            vocab = bool(int(store.flags[row])
                         & (FLAG_SELECTOR | FLAG_TOLERATIONS))
            sig = ((store.rv[row], comp), node_name, vocab)
            if prev_sig is None or prev_sig[0] != sig[0]:
                arena.note_tasks()
                if sig[2] or (prev_sig is not None and prev_sig[2]):
                    arena.note_vocab()
                if prev_sig is not None and prev_sig[1]:
                    arena.note_nodes((prev_sig[1],))
                if node_name:
                    arena.note_nodes((node_name,))
            self._pod_sigs[uid] = sig
        self._prev_overlay = applied_overlay

        cluster = self._build_cluster(nodes, podgroups, queues,
                                      prewired=True)
        # Exact pod-population facts for pack()'s and the plugins'
        # O(pods) scans (identical results, no walk).
        cluster.columnar_hints = {
            "no_host_ports": True,
            "no_selectors": not bool(np.any(flags & FLAG_SELECTOR)),
            "max_tols": int(max(1, store.tol_len[wrows].max()))
            if wrows.size else 1,
        }
        # A pod with an inter-pod term is FLAG_COMPLEX, and this path runs
        # only where the store holds none (``_columnar_verdict``).
        cluster.term_carriers = []
        # Memoized queue aggregates (same accumulation order as the
        # object walk); statement mutations invalidate and recompute
        # from the materialized objects as usual.  Summed in turn, not
        # counted: the proportion plugin rolls these pods up from the
        # batch below, not from this memo.
        cluster._queue_aggregates = QueueAggregates(allocated, requested)
        # Wire-order row batch for plugin-side vectorization (the
        # proportion roll-up): request rows + queue index + status masks,
        # exactly the walk's inputs in the walk's order.
        pre_lut = np.array([bool(pg.preemptible) for pg in glist]
                           + [True])
        cluster.columnar_batch = {
            "q_uids": q_uids,
            "qidx": qidx,
            "reqs": reqs,
            "active": active,
            "pending": pending,
            "preemptible": pre_lut[gpos_w] if gpos_w.size
            else np.zeros(0, bool),
            # Precomputed ancestor-chain table (own idx first, aligned
            # with q_uids) for the proportion roll-up.
            "queue_anc": self._queue_cols["anc"]
            if self._queue_cols is not None else None,
        }
        arena.stamp(cluster)
        n_dirty = sum(changed.values())
        METRICS.set_gauge("snapshot_dirty_objects", n_dirty)
        METRICS.set_gauge("snapshot_columnar_rows", int(wrows.size))
        self.last_columnar_stats = {
            "path": "columnar", "reason": "",
            "rows": int(wrows.size), "dirty_pods": len(candidates),
            "overlaid": n_overlaid, "store": store.stats(),
        }
        span.set(rows=int(wrows.size), dirty=len(candidates),
                 overlaid=n_overlaid)
        self.last_snapshot_stats = {
            "watch_mode": self._watch_mode,
            "dirty": dict(changed),
            "store": {"nodes": len(nodes), "queues": len(queues),
                      "podgroups": len(podgroups),
                      "pods": len(self._mirror["Pod"])},
            "speculative_overlaid": n_overlaid,
        }
        cluster.cache_stats = self.last_snapshot_stats
        return cluster

    def _snapshot_objects(self, changed: dict,
                          speculative: dict) -> ClusterInfo:
        arena = self.arena
        nodes = self._build_nodes()
        queues = self._build_queues()
        podgroups = self._build_groups()

        seen_uids = set()
        cache_seen = set()
        pod_sigs: dict = {}
        pod_mirror = self._mirror["Pod"]
        # ``speculative`` (snapshot()'s frozen copy) is applied onto the
        # parsed pods below, so this snapshot sees the previous cycle's
        # decisions whether or not their watch echo has arrived.  A
        # frozen copy — the commit epilogue may clear entries
        # concurrently, and a half-applied clear mid-loop would make the
        # snapshot internally inconsistent.
        n_overlaid = 0
        overlay_now: dict = {}
        for pod_key in self._iter_order("Pod"):
            pod = pod_mirror[pod_key]
            group = pod["metadata"].get("labels", {}).get(POD_GROUP_LABEL)
            if not group or group not in podgroups:
                continue
            task = self._parse_pod(pod)
            # Speculative overlay: an in-flight bind reads as BOUND on
            # its node (exactly what the store shows once the binder's
            # echo lands); an in-flight evict reads RELEASING.  The
            # overlay participates in the change signature below, so
            # applying/clearing it dirties the arena the same way a real
            # manifest change would.
            spec_entry = speculative.get(task.uid)
            if spec_entry is not None:
                _seq, spec_kind, spec_node = spec_entry
                if spec_kind == "bind":
                    if task.status == PodStatus.PENDING \
                            and not task.node_name \
                            and spec_node in nodes:
                        task.status = PodStatus.BOUND
                        task.node_name = spec_node
                        n_overlaid += 1
                    elif task.status == PodStatus.RELEASING \
                            and not task.node_name \
                            and spec_node in nodes:
                        # Deleted/evicted before the bind echo landed:
                        # the serial path would show RELEASING on the
                        # decided node — overlay the node, keep the
                        # terminal-bound state.
                        task.node_name = spec_node
                        n_overlaid += 1
                    else:  # echo landed (or pod moved on): no-op overlay
                        spec_entry = None
                elif spec_kind == "evict":
                    if task.status not in (PodStatus.SUCCEEDED,
                                           PodStatus.FAILED,
                                           PodStatus.RELEASING):
                        task.status = PodStatus.RELEASING
                        n_overlaid += 1
                    else:
                        spec_entry = None
            # Pod-level change signature: a changed pod dirties the node
            # rows it touches (previous and current placement) and, when
            # it carries scheduling vocabulary (selectors/tolerations),
            # poisons the codec reuse.  The speculative overlay folds
            # into the rv component: overlay transitions re-dirty the
            # pod even though the manifest's resourceVersion never moved.
            if spec_entry is not None:
                # Record the applied component so a later columnar
                # snapshot can diff overlay transitions O(in-flight).
                overlay_now[task.uid] = spec_entry[1:]
            sig = ((self._sig_rv(pod),
                    spec_entry[1:] if spec_entry is not None else None),
                   task.node_name,
                   bool(task.node_selector or task.tolerations))
            prev_sig = self._pod_sigs.get(task.uid)
            if prev_sig is None or prev_sig[0] != sig[0]:
                arena.note_tasks()
                if sig[2] or (prev_sig is not None and prev_sig[2]):
                    arena.note_vocab()
                if prev_sig is not None and prev_sig[1]:
                    arena.note_nodes((prev_sig[1],))
                if task.node_name:
                    arena.note_nodes((task.node_name,))
            pod_sigs[task.uid] = sig
            cache_seen.add(task.uid)
            if task.status == PodStatus.PENDING:
                seen_uids.add(task.uid)
                # Lifecycle: the pod made it into a schedulable snapshot
                # (idempotent per attempt — one dict probe on repeats).
                LIFECYCLE.note(task.uid, "snapshotted", podgroup=group,
                               queue=podgroups[group].queue_id)
            # A remembered pipelined assignment becomes a nomination: the
            # task stays schedulable, the nominated-node boost steers it
            # back to its node, and it binds the moment idle resources
            # free there (re-pipelining otherwise keeps the memory fresh).
            if task.status == PodStatus.PENDING \
                    and task.uid in self._pipelined:
                node_name, _pgroup = self._pipelined[task.uid]
                if node_name in nodes:
                    task.nominated_node = node_name
            podgroups[group].add_task(task)
        # Vanished pods (deleted, or dropped out of any live group): the
        # node they occupied changes, and a vocab-bearing one retires
        # codec entries.
        for uid, (_rv, node_name, vocab) in self._pod_sigs.items():
            if uid not in pod_sigs:
                arena.note_tasks()
                if vocab:
                    arena.note_vocab()
                if node_name:
                    arena.note_nodes((node_name,))
                # Lifecycle: the pod left the store without binding —
                # close its timeline so no open state leaks.
                LIFECYCLE.mark_vanished(uid)
        self._pod_sigs = pod_sigs
        # Forget assignments for pods that vanished or already bound.
        self._pipelined = {
            uid: v for uid, v in self._pipelined.items()
            if uid in seen_uids}  # seen = still pending this snapshot
        # Drop parse-cache entries for vanished pods.
        self._pod_cache = {uid: v for uid, v in self._pod_cache.items()
                           if uid in cache_seen}
        self._prev_overlay = overlay_now

        cluster = self._build_cluster(nodes, podgroups, queues,
                                      prewired=False)
        # Only the arena's LATEST stamped view may pack incrementally; an
        # older ClusterInfo (or one filtered by a shard provider) packs
        # from scratch.
        arena.stamp(cluster)
        n_dirty = sum(changed.values())
        METRICS.set_gauge("snapshot_dirty_objects", n_dirty)
        self.last_snapshot_stats = {
            "watch_mode": self._watch_mode,
            "dirty": dict(changed),
            "store": {"nodes": len(nodes), "queues": len(queues),
                      "podgroups": len(podgroups),
                      "pods": len(self._mirror["Pod"])},
            # Overlapped-pipeline verdict: how much of this snapshot's
            # placement state came from the speculative view (in-flight
            # commits) rather than the store echo.
            "speculative_overlaid": n_overlaid,
        }
        cluster.cache_stats = self.last_snapshot_stats
        return cluster

    def _build_aux(self) -> dict:
        """Rebuild the aux parse caches whose family saw changes; serve
        everything else from the previous build."""
        aux = self._aux
        if self._aux_dirty["topology"]:
            aux["topologies"] = {
                topo["metadata"]["name"]: {
                    "levels": [lvl["nodeLabel"] for lvl in
                               topo.get("spec", {}).get("levels", [])]}
                for topo in self._mirror["Topology"].values()}
            self._aux_dirty["topology"] = False
        if self._aux_dirty["dra"]:
            # DRA objects: structured claims + per-node device inventory
            # (the upstream DRA manager's ResourceClaim/ResourceSlice
            # views).
            resource_claims = {}
            for rc in self._mirror["ResourceClaim"].values():
                spec = rc.get("spec", {})
                device_reqs = (spec.get("devices") or {}).get("requests") \
                    or [{}]
                alloc = rc.get("status", {}).get("allocation")
                resource_claims[rc["metadata"]["name"]] = {
                    # Every device request (multi-class claims supported).
                    "requests": [
                        {"device_class": r.get("deviceClassName", ""),
                         "count": int(r.get("count", 1)),
                         "selectors": self._audit_device_selectors(
                             "ResourceClaim/"
                             f"{rc['metadata'].get('namespace', 'default')}"
                             f"/{rc['metadata']['name']}",
                             _parse_device_selectors(r.get("selectors")))}
                        for r in device_reqs],
                    # Legacy single-request view kept for older callers.
                    "device_class": device_reqs[0].get("deviceClassName",
                                                       ""),
                    "count": int(device_reqs[0].get("count", 1)),
                    "allocation": alloc,
                    "allocated": bool(alloc),
                    "node": (alloc or {}).get("node"),
                }
            aux["resource_claims"] = resource_claims
            resource_slices: dict = {}
            for sl in self._mirror["ResourceSlice"].values():
                spec = sl.get("spec", {})
                node = spec.get("nodeName")
                if not node:
                    continue
                per_node = resource_slices.setdefault(node, {})
                driver = spec.get("driver")
                for dev in spec.get("devices") or []:
                    cls = dev.get("deviceClassName", "")
                    attrs = _parse_device_attributes(dev)
                    caps = _parse_device_capacity(dev)
                    if driver:
                        # The slice's driver is addressable from CEL
                        # (device.driver == "...").
                        attrs.setdefault("driver", driver)
                    entry = ({"name": dev.get("name", ""),
                              "attributes": attrs, "capacity": caps}
                             if attrs or caps else dev.get("name", ""))
                    per_node.setdefault(cls, []).append(entry)
            aux["resource_slices"] = resource_slices
            aux["device_classes"] = {
                dc["metadata"]["name"]: {
                    "selectors": self._audit_device_selectors(
                        f"DeviceClass/{dc['metadata']['name']}",
                        _parse_device_selectors(
                            dc.get("spec", {}).get("selectors")))}
                for dc in self._mirror["DeviceClass"].values()}
            self._aux_dirty["dra"] = False
        if self._aux_dirty["configmap"]:
            aux["config_maps"] = {
                (cm["metadata"].get("namespace", "default"),
                 cm["metadata"]["name"])
                for cm in self._mirror["ConfigMap"].values()}
            self._aux_dirty["configmap"] = False
        if self._aux_dirty["pvc"]:
            pvcs = {}
            for pvc in self._mirror["PersistentVolumeClaim"].values():
                md = pvc["metadata"]
                pvcs[(md.get("namespace", "default"), md["name"])] = {
                    "bound_node": md.get("annotations", {}).get(
                        "volume.kubernetes.io/selected-node")}
            aux["pvcs"] = pvcs
            self._aux_dirty["pvc"] = False
        if self._aux_dirty["storage"]:
            # Schedule-time CSI storage (storage.go snapshot* chain).
            # The built infos are TEMPLATES: snapshot() clones them per
            # cycle before linking, because linking/placement mutates
            # them (provisioned_pvcs, reprovision flags).
            from ..api.storage_info import build_storage_snapshot

            def listed(kind):
                return sorted(self._mirror[kind].values(),
                              key=lambda o: o["metadata"]["name"])

            (aux["storage_classes"], aux["storage_claims"],
             aux["storage_capacities"]) = build_storage_snapshot(
                listed("CSIDriver"), listed("StorageClass"),
                listed("PersistentVolumeClaim"),
                listed("CSIStorageCapacity"))
            self._aux_dirty["storage"] = False
        return aux

    # -- side-effect executor (framework Session cache interface) ------------
    def _bind_manifest(self, task, node_name: str, bind_request,
                       fk: dict) -> dict:
        """The BindRequest object for one placement decision — shared by
        the single write and the bulk bind wave."""
        return {
            "kind": "BindRequest",
            "metadata": {"name": f"bind-{task.uid}",
                         "namespace": task.namespace},
            "spec": {"podName": task.name, "podUid": task.uid,
                     "selectedNode": node_name,
                     "selectedGPUGroups": bind_request.gpu_groups,
                     "gpuFraction": task.res_req.gpu_fraction or None,
                     "backoffLimit": bind_request.backoff_limit,
                     # Leadership epoch of the deciding scheduler —
                     # auditable fencing trail on the object itself.
                     "schedulerEpoch": fk.get("epoch"),
                     # Flight-recorder correlation: which cycle decided
                     # this bind (GET /debug/trace?cycle=<id>).
                     "traceId": getattr(bind_request, "trace_id", None),
                     "resourceClaims": list(
                         getattr(bind_request, "resource_claims", [])),
                     "resourceClaimAllocations": list(
                         getattr(bind_request, "claim_allocations", []))},
            "status": {"phase": "Pending"},
        }

    def bind(self, task, node_name: str, bind_request) -> None:
        """Create (or supersede) the BindRequest object the binder
        consumes (cache/cache.go:267-290).  A leftover request from a
        previous failed attempt is replaced: the fresh scheduling decision
        resets the phase and retry budget."""
        fk = self._fence_kwargs()
        obj = self._bind_manifest(task, node_name, bind_request, fk)
        with TRACER.span(f"bind:{task.name}", kind="kubeapi",
                         op="bindrequest_create", node=node_name,
                         epoch=fk.get("epoch")) as sp:
            try:
                self.api.create(obj, **fk)
            except Conflict:
                # Leftover from a failed earlier attempt: supersede it.
                # The common case stays a single API call.
                sp.set(superseded=True)
                self.api.delete("BindRequest", obj["metadata"]["name"],
                                task.namespace, **fk)
                obj["metadata"].pop("resourceVersion", None)
                obj["metadata"].pop("uid", None)
                self.api.create(obj, **fk)
        # Lifecycle: the durable bind intent is in the store (stamped
        # only after the write survived the fence).
        LIFECYCLE.note(task.uid, "bind_requested", node=node_name,
                       trace_id=getattr(bind_request, "trace_id", None))

    def bind_many(self, entries) -> list:
        """Bulk bind wave: ``entries`` is [(task, node_name,
        bind_request)]; the whole wave lands through ONE
        ``create_many`` round trip (``POST /bulk/create`` on the wire,
        supersede-on-conflict matching ``bind``'s semantics), with
        per-item outcomes — one fenced or failed item never poisons the
        wave.  Returns the outcome list aligned with ``entries``
        (``{"ok": True, ...}`` / ``{"ok": False, "error": exc}``);
        lifecycle stamps land only for requests that reached the store.
        Falls back to per-item ``bind`` on substrates without
        ``create_many`` (every failure raises immediately there, the
        historical contract)."""
        entries = list(entries)
        if not entries:
            return []
        create_many = getattr(self.api, "create_many", None)
        if create_many is None:
            # Per-item fallback with per-item OUTCOMES: a mid-wave
            # failure stops the wave (the historical abort-on-raise
            # order) but already-landed binds keep their ok outcomes, so
            # the caller's journal/landed bookkeeping stays truthful.
            outcomes = []
            for i, (task, node_name, bind_request) in enumerate(entries):
                try:
                    self.bind(task, node_name, bind_request)
                    outcomes.append({"ok": True})
                except Exception as exc:
                    outcomes.extend(
                        {"ok": False, "error": exc}
                        for _ in range(len(entries) - i))
                    break
            return outcomes
        fk = self._fence_kwargs()
        objs = [self._bind_manifest(task, node, br, fk)
                for task, node, br in entries]
        with TRACER.span("bind_wave", kind="kubeapi",
                         op="bindrequest_create_bulk", binds=len(objs),
                         epoch=fk.get("epoch")) as sp:
            try:
                outcomes = create_many(objs, supersede=True, **fk)
            except OSError:
                # Ambiguous wave death (connection reset or response
                # dropped mid-bulk-POST): the store may hold ANY prefix
                # of the wave.  One idempotent replay resolves it —
                # create_many answers identical-spec items with
                # fence-checked no-ops, so a landed prefix can never
                # double-bind and an unlanded suffix lands now.  A
                # second transport death propagates: the journal replay
                # at restart is the backstop then.
                METRICS.inc("bind_wave_replays_total")
                sp.set(replayed=True)
                outcomes = create_many(objs, supersede=True, **fk)
            failed = sum(1 for out in outcomes if not out.get("ok"))
            if failed:
                sp.set(failed_items=failed)
        METRICS.inc("bulk_write_batches_total", path="bind_wave")
        METRICS.inc("bulk_write_items_total", len(entries),
                    path="bind_wave")
        if failed:
            METRICS.inc("bulk_write_errors_total", failed,
                        path="bind_wave")
        for (task, node_name, bind_request), out in zip(entries, outcomes):
            if out.get("ok"):
                LIFECYCLE.note(task.uid, "bind_requested", node=node_name,
                               trace_id=getattr(bind_request, "trace_id",
                                                None))
        return outcomes

    def task_pipelined(self, task, node_name: str,
                       gpu_group: str = "") -> None:
        """Remember a pipelined assignment between cycles
        (Cache.TaskPipelined, cache/interface.go:44)."""
        self._pipelined[task.uid] = (node_name, gpu_group)

    # -- speculative view (overlapped commits, DESIGN §10) -------------------
    def speculate(self, entries) -> dict:
        """Register in-flight commit decisions: ``entries`` is
        [(uid, kind, node)] with kind "bind" | "evict".  Returns
        {uid: seq} — the handle the commit epilogue (or a fenced
        rollback) later clears.  Called on the scheduler thread at
        commit-enqueue time, BEFORE any durable write."""
        out = {}
        with self._changes_lock:
            for uid, kind, node in entries:
                seq = next(self._spec_seq)
                self._speculative[uid] = (seq, kind, node)
                self._spec_unsealed[uid] = seq
                out[uid] = seq
        METRICS.set_gauge("pipeline_speculative_entries",
                          len(self._speculative))
        return out

    def seal_speculation(self) -> dict:
        """Take ownership of every entry registered since the last seal
        (one cycle's worth): the cycle epilogue clears exactly this set
        after its writes + binder round trip landed."""
        with self._changes_lock:
            sealed, self._spec_unsealed = self._spec_unsealed, {}
        return sealed

    def clear_speculation(self, handle: dict) -> int:
        """Drop sealed entries whose seq still matches (an entry
        superseded by a NEWER decision for the same pod — e.g. a
        speculatively-bound pod evicted the very next cycle — stays).
        Runs on the commit-executor thread; the next snapshot's
        signature diff re-dirties the affected pods/nodes on its own."""
        cleared = 0
        with self._changes_lock:
            for uid, seq in handle.items():
                entry = self._speculative.get(uid)
                if entry is not None and entry[0] == seq:
                    del self._speculative[uid]
                    cleared += 1
                # seq-conditional: cycle N's epilogue (commit-executor
                # thread) must not unregister a NEWER decision for the
                # same pod that cycle N+1's decision phase speculated
                # concurrently — that entry belongs to N+1's seal, and
                # dropping it here would leave it uncleared forever.
                if self._spec_unsealed.get(uid) == seq:
                    del self._spec_unsealed[uid]
        METRICS.set_gauge("pipeline_speculative_entries",
                          len(self._speculative))
        return cleared

    def rollback_speculation(self, handle: dict, reason: str) -> int:
        """Fenced/failed overlapped commit: the decisions never became
        durable — remove their speculative view so the next snapshot
        re-schedules the pods from scratch (the serial path's
        abort_uncommitted analog, one pipeline stage later)."""
        rolled = self.clear_speculation(handle)
        if rolled:
            METRICS.inc("pipeline_speculation_rollback_total", rolled)
            self.record_event(
                "SpeculationRolledBack",
                f"{rolled} overlapped commit decision(s) rolled back: "
                f"{reason}")
        return rolled

    def speculation_stats(self) -> dict:
        with self._changes_lock:
            return {"entries": len(self._speculative),
                    "unsealed": len(self._spec_unsealed)}

    def evict(self, task) -> None:
        """Delete the pod + patch the eviction condition
        (cache/evictor/default_evictor.go:24-45)."""
        pod = self.api.get_opt("Pod", task.name, task.namespace)
        if pod is not None:
            conditions = list(pod.get("status", {}).get("conditions", []))
            conditions.append(
                {"type": "TerminationByKaiScheduler", "status": "True",
                 "reason": "Evicted"})
            fk = self._fence_kwargs()
            with TRACER.span(f"evict:{task.name}", kind="kubeapi",
                             op="evict", epoch=fk.get("epoch")):
                self.api.patch(
                    "Pod", task.name,
                    {"status": {"conditions": conditions},
                     "metadata": {"deletionTimestamp": str(self.now_fn())}},
                    task.namespace, **fk)
            # Lifecycle: the eviction committed — the current attempt
            # closes; a resubmit opens attempt N+1 on the same timeline.
            LIFECYCLE.note_evicted(task.uid)

    def evict_many(self, tasks) -> int:
        """Batched eviction writes: one dedicated patch per victim is
        built host-side and routed through the async status-updater
        worker pool with ONE flush for the whole gang batch, instead of
        one synchronous API round trip per victim (the serialized write
        train that dominated the 400-node reclaim cycle).  The fence
        kwargs ride in the payload so a deposed leader's eviction is
        still rejected at apply time (KAI005 intent).  Falls back to the
        per-victim synchronous path when no async updater is attached."""
        import time as _time
        tasks = list(tasks)
        if not tasks:
            return 0
        updater = self.status_updater
        if updater is None or not hasattr(updater, "submit_patch"):
            t0 = _time.perf_counter()
            for task in tasks:
                self.evict(task)
            dt = _time.perf_counter() - t0
            METRICS.observe("evict_write_latency_ms", dt * 1000.0)
            return len(tasks)
        fk = self._fence_kwargs()
        # Loud deposal check BEFORE enqueueing: the synchronous evict
        # path raised Fenced at the patch — the batch path must not
        # silently downgrade that to a per-write drop on the worker.
        # (A depose in the enqueue->apply window is still rejected at
        # the store; only the loud abort moves here.)
        check_fence = getattr(self.api, "check_fence", None)
        if check_fence is not None and fk:
            check_fence(fk.get("epoch"), fk.get("fence"))
        enqueued = 0
        t0 = _time.perf_counter()
        # Per-victim outcome, written on the worker threads (per-key
        # dict stores are atomic): absent = write landed, "vanished" =
        # pod gone before the write (the serial path's silent no-op),
        # exception = the write failed.  Worker-side failures surface
        # HERE after the flush exactly like the synchronous evict —
        # Fenced first, then any other failure — so the commit never
        # marks a failed eviction done and never proceeds to a bind
        # whose victim still holds its capacity.
        outcomes: dict = {}
        with TRACER.span("evict_batch", kind="kubeapi",
                         op="evict_batch", victims=len(tasks),
                         epoch=fk.get("epoch")):
            now = str(self.now_fn())

            def build_evict(name, namespace, uid):
                # Runs ON THE WORKER: the read-modify-write round trip
                # parallelizes across the pool instead of serializing
                # per-victim reads on the commit thread.
                def build():
                    pod = self.api.get_opt("Pod", name, namespace)
                    if pod is None:
                        outcomes[uid] = "vanished"
                        return None   # vanished: skip the doomed write
                    conditions = list(pod.get("status", {}).get(
                        "conditions", []))
                    conditions.append(
                        {"type": "TerminationByKaiScheduler",
                         "status": "True", "reason": "Evicted"})
                    return {"status": {"conditions": conditions},
                            "metadata": {"deletionTimestamp": now}}
                return build

            for task in tasks:
                updater.submit_patch(
                    "Pod", task.name, task.namespace,
                    build=build_evict(task.name, task.namespace,
                                      task.uid),
                    fence_kwargs=fk,
                    on_error=lambda exc, uid=task.uid:
                        outcomes.__setitem__(uid, exc))
                enqueued += 1
            METRICS.inc("evict_writes_batched_total", enqueued)
            # One flush per gang batch: the commit returns with every
            # eviction durably applied (or loudly raised), matching the
            # synchronous path's guarantees at a fraction of its
            # serialized round-trip cost.
            updater.flush()
        dt = _time.perf_counter() - t0
        METRICS.observe("evict_write_latency_ms", dt * 1000.0)
        # Lifecycle attempts close only for evictions that actually
        # landed — vanished pods stay a no-op and failed writes stay
        # open, exactly like the per-victim synchronous path.
        for task in tasks:
            if task.uid not in outcomes:
                LIFECYCLE.note_evicted(task.uid)
        from .kubeapi import Fenced
        failures = [exc for exc in outcomes.values()
                    if isinstance(exc, BaseException)]
        for exc in failures:
            if isinstance(exc, Fenced):
                raise exc
        if failures:
            raise failures[0]
        return enqueued

    def record_event(self, kind: str, message: str) -> None:
        # Correlation: events emitted mid-cycle carry the cycle's trace
        # id (None off the scheduler thread — watch/binder events).
        trace_id = TRACER.current_trace_id()
        if self.status_updater is not None:
            self.status_updater.record_event(kind, message,
                                             trace_id=trace_id)
            return
        self.api.create({
            "kind": "Event",
            "metadata": {"name": f"evt-{next(_EVENT_SEQ)}"},
            "spec": {"reason": kind, "message": message,
                     "traceId": trace_id},
        })

    def update_job_statuses(self, ssn) -> None:
        """Push scheduling explanations onto PodGroup statuses
        (status_updater markPodGroupUnschedulable,
        default_status_updater.go:295); routed through the async worker
        pool when one is attached.

        DEDUPED: a group whose current Unschedulable condition already
        carries the same message is skipped — on a sustained
        over-capacity backlog the un-deduped path rewrote thousands of
        identical conditions per cycle, and every rewrite bumped the
        object's resourceVersion, forcing the incremental cache to
        re-parse the whole backlog next snapshot (self-inflicted
        O(backlog) host work)."""
        group_mirror = self._mirror.get("PodGroup", {})
        for pg in ssn.cluster.podgroups.values():
            if not pg.fit_errors:
                continue
            # The watch-fresh mirror already holds the manifest: no API
            # read per backlog group (3200 pending groups used to cost
            # 3200 reads per cycle just to decide "nothing changed").
            obj = group_mirror.get((pg.namespace, pg.uid)) \
                or self.api.get_opt("PodGroup", pg.uid, pg.namespace)
            if obj is None:
                continue
            current = next(
                (c for c in obj.get("status", {}).get("conditions", [])
                 if c.get("type") == "Unschedulable"
                 and c.get("status") == "True"), None)
            if current is not None \
                    and current.get("message") == pg.fit_errors[-1]:
                # Same verdict as last cycle: rewriting it (with only a
                # fresh traceId) is churn, not information — /explain
                # still has the live per-cycle ledger.
                METRICS.inc("status_writes_deduped_total")
                continue
            conditions = [c for c in obj.get("status", {}).get(
                "conditions", []) if c.get("type") != "Unschedulable"]
            conditions.append({
                "type": "Unschedulable", "status": "True",
                "reason": "SchedulingFailed",
                "message": pg.fit_errors[-1],
                # The cycle whose ledger explains this verdict
                # (GET /explain?podgroup=<name> has the full reason list).
                "traceId": getattr(ssn, "trace_id", None),
            })
            if self.status_updater is not None:
                self.status_updater.patch_status(
                    "PodGroup", pg.uid, pg.namespace,
                    {"conditions": conditions})
            else:
                self.api.patch("PodGroup", pg.uid,
                               {"status": {"conditions": conditions}},
                               pg.namespace)

    def gc_stale_bind_requests(self) -> int:
        """Stale BindRequest GC (cache/cache.go:371): drop requests whose
        pod vanished or already bound."""
        removed = 0
        fk = self._fence_kwargs()
        for br in self.api.list("BindRequest"):
            ns = br["metadata"].get("namespace", "default")
            pod = self.api.get_opt("Pod", br["spec"]["podName"], ns)
            done = br.get("status", {}).get("phase") == "Succeeded"
            if pod is None or (done and pod.get("spec", {}).get("nodeName")):
                self.api.delete("BindRequest", br["metadata"]["name"], ns,
                                **fk)
                removed += 1
        return removed

    # -- restart reconcile (the crash-consistency pass) ----------------------
    def startup_reconcile(self, commitlog=None) -> dict:
        """Replay the commit journal against live API state and scrub the
        cluster of everything a crashed scheduler/binder can leave behind.
        Runs once at daemon startup, BEFORE the first scheduling cycle:

        1. every journal intent without a ``done`` marker is resolved
           against the store — a BindRequest that exists (or a pod that
           bound) means the write survived; otherwise the decision died
           with the old process and is dropped (the next cycle
           re-schedules the pod from scratch);
        2. orphaned reservation pods in ``kai-resource-reservation`` —
           gpu-groups no live pod annotation and no live BindRequest
           references — are deleted (a phantom reservation holds real
           GPU capacity hostage forever);
        3. BindRequests stuck past their backoff limit (phase Failed, or
           attempts exhausted) are reaped so the pod re-enters
           scheduling instead of wedging behind a dead request.

        Returns a summary dict (counts) for logging/healthz."""
        from .binder import GPU_GROUP_ANNOTATION, RESERVATION_NAMESPACE
        log = commitlog if commitlog is not None else self.commitlog
        summary = {"lost_commits": 0, "recovered_commits": 0,
                   "orphaned_reservations": 0, "reaped_bind_requests": 0}

        if log is not None:
            for intent in log.pending_intents():
                if intent.get("kind") == "bind":
                    ns = intent.get("namespace", "default")
                    br = self.api.get_opt("BindRequest",
                                          f"bind-{intent['pod_uid']}", ns)
                    pod = self.api.get_opt("Pod", intent.get("pod_name"),
                                           ns)
                    bound = pod is not None and \
                        pod.get("spec", {}).get("nodeName")
                    if br is not None or bound:
                        summary["recovered_commits"] += 1
                    else:
                        # Crash between journal append and API commit:
                        # the decision is lost, the pod re-schedules.
                        summary["lost_commits"] += 1
                        METRICS.inc("commitlog_lost_commits")
                        self.record_event(
                            "CommitLost",
                            f"bind intent for pod "
                            f"{ns}/{intent.get('pod_name')} died before "
                            f"the API commit; pod will re-schedule")
                else:  # evict intents are idempotent: nothing to undo
                    summary["recovered_commits"] += 1
            log.compact()

        # Reap BindRequests past their backoff budget FIRST: Failed
        # phase, or a Pending request whose attempts already exhausted
        # the limit (binder died before marking it Failed).  Order
        # matters — a dead-but-Pending request must not count its
        # gpu-groups as "live" in the orphan scan below, or the
        # reservations it took survive as phantoms until a SECOND
        # restart.
        for br in self.api.list("BindRequest"):
            status = br.get("status", {})
            limit = br.get("spec", {}).get("backoffLimit", 3)
            exhausted = status.get("attempts", 0) >= limit
            if status.get("phase") == "Failed" or \
                    (status.get("phase") == "Pending" and exhausted):
                ns = br["metadata"].get("namespace", "default")
                # Reaping is a scheduler write like any other: carry the
                # fence so a deposed instance replaying its journal after
                # a new leader took over cannot delete the new leader's
                # requests (KAI005).
                self.api.delete("BindRequest", br["metadata"]["name"], ns,
                                **self._fence_kwargs())
                summary["reaped_bind_requests"] += 1
                METRICS.inc("bind_requests_reaped_total")

        # Orphaned reservation-pod GC: collect every gpu-group still
        # referenced by a live pod annotation or a live BindRequest;
        # reservation pods holding any OTHER group are phantoms.
        live_groups: set = set()
        for pod in self.api.list("Pod"):
            if pod["metadata"].get("namespace") == RESERVATION_NAMESPACE:
                continue
            ann = pod["metadata"].get("annotations", {})
            for g in ann.get(GPU_GROUP_ANNOTATION, "").split(","):
                if g:
                    live_groups.add(g)
        for br in self.api.list("BindRequest"):
            for g in br.get("spec", {}).get("selectedGPUGroups") or []:
                live_groups.add(g)
        for pod in self.api.list("Pod", namespace=RESERVATION_NAMESPACE):
            group = pod["metadata"].get("labels", {}).get(
                GPU_GROUP_ANNOTATION)
            if group and group not in live_groups:
                self.api.delete("Pod", pod["metadata"]["name"],
                                RESERVATION_NAMESPACE)
                summary["orphaned_reservations"] += 1
                METRICS.inc("reservation_orphans_gc_total")
                self.record_event(
                    "OrphanedReservationReclaimed",
                    f"reservation pod for gpu-group {group} had no "
                    f"owning pod or BindRequest after restart")

        if any(summary.values()):
            LOGGER_MSG = ("startup reconcile: %(lost_commits)d lost "
                          "commits, %(recovered_commits)d recovered, "
                          "%(orphaned_reservations)d orphaned "
                          "reservations GC'd, %(reaped_bind_requests)d "
                          "stale BindRequests reaped")
            from ..utils.logging import LOG
            LOG.warning(LOGGER_MSG, summary)
        return summary


_EVENT_SEQ = itertools.count()
