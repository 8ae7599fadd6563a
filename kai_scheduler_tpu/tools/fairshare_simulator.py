"""Fair-share simulator: the offline correctness harness for the division
algorithm.

Mirrors cmd/fairshare-simulator (main.go:39-103): POST /simulate with
{"totalResource": {...}, "queues": [...]} -> per-queue fair share.  Grown
(per BASELINE.json config #1) with a --backend flag selecting the
sequential numpy reference or the JAX kernel, so the two can be diffed on
arbitrary snapshots.

Usage:
  python -m kai_scheduler_tpu.tools.fairshare_simulator --port 8099
  python -m kai_scheduler_tpu.tools.fairshare_simulator --input snap.json \
      --backend jax
"""

from __future__ import annotations

import argparse
import json
import sys
from http.server import BaseHTTPRequestHandler, HTTPServer

import numpy as np

from ..api import resources as rs
from ..ops import fairshare as fsops

RESOURCES = ("cpu", "memory", "gpu")


def _vec(d: dict | None, default: float) -> np.ndarray:
    if d is None:
        return np.full(rs.NUM_RES, default)
    return np.array([float(d.get(r, default)) for r in RESOURCES])


def simulate(payload: dict, backend: str = "numpy") -> dict:
    """payload: {"totalResource": {cpu,memory,gpu}, "kValue": float,
    "queues": [{"name", "parent", "priority", "creationTimestamp",
                "deserved", "limit", "overQuotaWeight", "request",
                "allocated", "usage"}]}"""
    queues = payload.get("queues", [])
    total = _vec(payload.get("totalResource"), 0.0)
    k = float(payload.get("kValue", 1.0))
    q = len(queues)
    if q == 0:
        return {"queues": {}}

    names = [qd["name"] for qd in queues]
    index = {n: i for i, n in enumerate(names)}
    parent = np.array([index.get(qd.get("parent"), -1) for qd in queues],
                      np.int64)
    priority = np.array([int(qd.get("priority", 0)) for qd in queues])
    creation = np.array([float(qd.get("creationTimestamp", 0))
                         for qd in queues])
    deserved = np.stack([_vec(qd.get("deserved"), rs.UNLIMITED)
                         for qd in queues])
    limit = np.stack([_vec(qd.get("limit"), rs.UNLIMITED) for qd in queues])
    oqw = np.stack([_vec(qd.get("overQuotaWeight"), 1.0) for qd in queues])
    leaf_request = np.stack([_vec(qd.get("request"), 0.0) for qd in queues])
    usage = np.stack([_vec(qd.get("usage"), 0.0) for qd in queues])
    request = fsops.roll_up_requests(parent, leaf_request)

    if backend == "jax":
        # What the scheduler runs (plugins/proportion._set_fair_share):
        # the prepared forest, one dispatch.  Offline CLI: there is no
        # Session (and no device-guard) here — the simulator exists to
        # diff the product's kernel against the sequential reference
        # below, so the call is direct by design.
        prep = fsops.prepared_forest(parent, priority, creation, names,
                                     deserved, limit, oqw)
        # kailint: disable=KAI004 — offline simulator, no Session to dispatch through
        fair = fsops.fair_share_forest(total, k, prep, request, usage)
    else:
        # Sequential reference, level by level (proportion.go:410-425).
        fair = np.zeros((q, rs.NUM_RES))
        by_depth: dict[int, list] = {}
        depth = [0] * q
        for i in range(q):
            d, p = 0, parent[i]
            while p >= 0:
                d, p = d + 1, parent[p]
            depth[i] = d
            by_depth.setdefault(d, []).append(i)
        for d in sorted(by_depth):
            groups: dict[int, list] = {}
            for i in by_depth[d]:
                groups.setdefault(parent[i], []).append(i)
            for p, idxs in groups.items():
                pool = total if p < 0 else fair[p]
                order = sorted(range(len(idxs)),
                               key=lambda j: (creation[idxs[j]],
                                              names[idxs[j]]))
                rank = np.empty(len(idxs), np.int64)
                for r_, j in enumerate(order):
                    rank[j] = r_
                fair[idxs] = fsops.set_resources_share_np(
                    pool, k, deserved[idxs], limit[idxs], oqw[idxs],
                    request[idxs], usage[idxs], priority[idxs], rank)

    return {"queues": {
        name: {"fairShare": {r: fair[i, j] for j, r in enumerate(RESOURCES)}}
        for i, name in enumerate(names)}}


class _Handler(BaseHTTPRequestHandler):
    backend = "numpy"

    def do_POST(self):
        if self.path != "/simulate":
            self.send_error(404)
            return
        length = int(self.headers.get("Content-Length", 0))
        payload = json.loads(self.rfile.read(length) or b"{}")
        result = simulate(payload, self.backend)
        body = json.dumps(result).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *args):
        pass


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, default=0,
                    help="serve HTTP /simulate on this port")
    ap.add_argument("--input", help="simulate a JSON file and print result")
    ap.add_argument("--backend", choices=("numpy", "jax"), default="numpy")
    args = ap.parse_args(argv)

    if args.input:
        with open(args.input) as f:
            payload = json.load(f)
        print(json.dumps(simulate(payload, args.backend), indent=1))
        return
    _Handler.backend = args.backend
    server = HTTPServer(("127.0.0.1", args.port), _Handler)
    print(f"fairshare-simulator listening on :{server.server_port} "
          f"(backend={args.backend})", file=sys.stderr)
    server.serve_forever()


if __name__ == "__main__":
    main()
