"""Data-parallel serving replicas: one ``ElasticEngine`` per device slice.

``ElasticEngine(mesh=...)`` is tensor parallelism — ONE logical engine whose
weights, KV pools, and step functions are sharded over a mesh's ``model``
axis, with token streams bit-identical to the single-device engine
(docs/serving_internals.md §11). Data parallelism is the other axis:
independent engines over disjoint device groups, each serving a disjoint
slice of the request stream. The two compose here — a ``ReplicaSet`` of
``n_replicas`` engines, each on its own ``(1, tp)`` mesh.

Requests partition by ``rid % n_replicas``: deterministic, stateless, and
stable across snapshot/resume (a request's home replica is a pure function
of its rid, so a resumed fleet re-derives the same partition). Each
replica's wave is a plain single-engine wave — streams are bit-identical to
running that replica's requests alone on one engine, which is this module's
tested contract (tests/test_mesh_serving.py).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from repro.serve.engine import ElasticEngine, Request


def replica_meshes(n_replicas: int, tp: int = 1, devices=None):
    """Carve ``devices`` (default: all of ``jax.devices()``) into
    ``n_replicas`` disjoint ``(1, tp)`` meshes with axes ``("data",
    "model")``. ``tp == 1`` still returns meshes — a uniform code path —
    but callers may pass ``mesh=None`` per engine instead for the plain
    single-device build."""
    import jax
    from jax.sharding import Mesh
    if devices is None:
        devices = jax.devices()
    need = n_replicas * tp
    if len(devices) < need:
        raise ValueError(
            f"{n_replicas} replica(s) x tp={tp} needs {need} device(s); "
            f"only {len(devices)} available")
    return [Mesh(np.array(devices[i * tp:(i + 1) * tp]).reshape(1, tp),
                 ("data", "model"))
            for i in range(n_replicas)]


class ReplicaSet:
    """``n_replicas`` independent engines serving a partitioned stream.

    Every engine is built with identical configuration (same anchor, same
    knobs) so any request produces the same tokens regardless of which
    replica it lands on; the partition only decides WHERE, never WHAT.
    """

    def __init__(self, api, anchor, *, n_replicas: int, tp: int = 1,
                 devices=None, **engine_kwargs):
        if n_replicas < 1:
            raise ValueError(f"n_replicas ({n_replicas}) must be >= 1")
        if "mesh" in engine_kwargs:
            raise ValueError(
                "pass tp= instead of mesh=; ReplicaSet builds one "
                "(1, tp) mesh per replica")
        if tp > 1:
            meshes = replica_meshes(n_replicas, tp, devices)
        else:
            meshes = [None] * n_replicas
        self.n_replicas = n_replicas
        self.tp = tp
        # the replicas share one anchor: none may take it over
        engine_kwargs.setdefault("donate_anchor", n_replicas == 1)
        self.engines: List[ElasticEngine] = [
            ElasticEngine(api, anchor, mesh=m, **engine_kwargs)
            for m in meshes]

    def home(self, rid: int) -> int:
        """The replica index serving request ``rid``."""
        return rid % self.n_replicas

    def partition(self, requests: List[Request]) -> List[List[Request]]:
        parts: List[List[Request]] = [[] for _ in range(self.n_replicas)]
        for r in requests:
            parts[self.home(r.rid)].append(r)
        return parts

    def generate(self, requests: List[Request], **kw) -> List[Request]:
        """Serve ``requests`` across the replicas; returns them all (each
        mutated in place by its home engine, original order preserved)."""
        for part, eng in zip(self.partition(requests), self.engines):
            if part:
                eng.generate(part, **kw)
        return requests

    @property
    def stats(self) -> Dict:
        per = [e.stats for e in self.engines]
        return {
            "n_replicas": self.n_replicas,
            "tp": self.tp,
            "tokens_out": sum(s["tokens_out"] for s in per),
            "ticks": sum(s["ticks"] for s in per),
            "replicas": per,
        }
