"""Data-parallel serving replicas behind one admission queue.

Tensor parallelism lives *inside* one :class:`ServingEngine` (its
``spec.mesh.tp`` devices run the one fused step under GSPMD); data
parallelism lives *outside*, here: ``dp`` independent engine replicas,
each pinned to its own ``tp``-device mesh slice with its own paged pool
and prefix-cache namespace, behind a single host-side admission surface.
Nothing is sharded across replicas — a request's whole lifetime happens
on the replica that admitted it, which is what keeps every stream
bit-identical to the single-device engine (same program, same lane
arithmetic, just fewer neighbours per pool).

The cluster is a drop-in for ``ServingEngine`` wherever only the public
serving surface is touched — ``submit`` / ``step`` / ``queue`` /
``slot_req`` / ``events`` / ``stats`` — which is exactly the contract
``harness.driver.replay`` documents.  One trace replays against the
replica set unchanged, with every replica's :class:`EngineEvent` stream
relayed onto the cluster bus under cluster-level uids and the cluster's
logical clock (rounds of replica steps), so ``reduce_events`` works on
the merged log as-is.

Placement is by *free capacity*: each submit seats on the replica with
the most free pool blocks net of demand already queued there (dense
layout: free slots net of queue length).  Ties break to the lowest
replica index, and the router reads only host-side state, so placement
— and therefore the whole replay — is deterministic.
"""
from __future__ import annotations

import dataclasses
from typing import Any

from jax.profiler import TraceAnnotation

from repro.core.paging import blocks_for_tokens
from repro.core.spec import MeshSpec, RuntimeSpec
from repro.serving.engine import Request, ServingEngine
from repro.serving.events import ENGINE_KINDS, EngineEvent, EventBus


class EngineCluster:
    """``spec.mesh.dp`` ServingEngine replicas, one admission queue."""

    def __init__(self, spec: RuntimeSpec, *, devices=None, rng=None):
        import jax

        mesh = spec.mesh
        if mesh.dp < 1:
            raise ValueError(f"mesh.dp must be >= 1, got {mesh.dp}")
        need = mesh.n_devices
        devs = list(devices) if devices is not None else jax.devices()[:need]
        if len(devs) < need:
            raise ValueError(
                f"mesh tp={mesh.tp} x dp={mesh.dp} needs {need} devices but "
                f"only {len(devs)} are visible; call "
                "launch.mesh.ensure_host_devices(n) before importing jax "
                "(or pass devices=)")
        self.spec = spec
        replica_spec = dataclasses.replace(
            spec, mesh=MeshSpec(tp=mesh.tp, dp=1))
        self.replicas: list[ServingEngine] = [
            ServingEngine(replica_spec, rng=rng,
                          devices=devs[i * mesh.tp:(i + 1) * mesh.tp])
            for i in range(mesh.dp)
        ]
        self.events = EventBus()
        self.stats: dict[str, int] = {"decode_steps": 0}
        self._uid = 0
        # per-replica {replica uid -> cluster uid}; entries live from
        # submit to finish (spanning preempt/re-admit cycles)
        self._maps: list[dict[int, int]] = [{} for _ in self.replicas]
        for i, eng in enumerate(self.replicas):
            eng.events.subscribe(self._relay(i))

    # ------------------------------------------------------------------
    def _relay(self, idx: int):
        """Republish one replica's events under cluster uids + clock."""

        def cb(e: EngineEvent) -> None:
            if not self.events.active:
                return
            if e.kind in ENGINE_KINDS:   # no request: say whose step
                self.events.publish(EngineEvent(
                    e.kind, e.uid, self.stats["decode_steps"], e.t,
                    {**e.data, "replica": idx}))
                return
            uid = self._maps[idx].get(e.uid)
            if uid is None:        # event for a request we didn't route
                return
            self.events.publish(EngineEvent(
                e.kind, uid, self.stats["decode_steps"], e.t, e.data))

        return cb

    def load(self, params) -> None:
        """Install the same weights on every replica."""
        for eng in self.replicas:
            eng.load(params)

    # ------------------------------------------------------------------
    def _place(self, prompt_len: int) -> int:
        """Replica index with the most free capacity net of queued
        demand; ties to the lowest index (deterministic routing)."""
        best, best_score = 0, None
        for i, eng in enumerate(self.replicas):
            if eng.paging is not None:
                bs = eng.paging.block_size
                demand = sum(
                    blocks_for_tokens(len(r.prompt) + len(r.prefix), bs)
                    for r in eng.queue)
                score = eng.allocator.num_free - demand
            else:
                free = sum(r is None for r in eng.slot_req)
                score = free - len(eng.queue)
            if best_score is None or score > best_score:
                best, best_score = i, score
        return best

    def submit(self, prompt: list[int], max_new_tokens: int = 32,
               eos_id=None, sampling=None, model: int = 0) -> int:
        idx = self._place(len(prompt))
        eng = self.replicas[idx]
        # pre-register the uid mapping: the replica emits its "submit"
        # event *inside* submit(), and the relay needs the translation
        # already in place.  Every submit-side validation raises before
        # the replica increments its uid, so the prediction is exact;
        # roll back on raise.
        ruid = eng._uid + 1
        self._uid += 1
        self._maps[idx][ruid] = self._uid
        try:
            got = eng.submit(prompt, max_new_tokens=max_new_tokens,
                             eos_id=eos_id, sampling=sampling, model=model)
        except Exception:
            del self._maps[idx][ruid]
            self._uid -= 1
            raise
        assert got == ruid, "replica uid drifted from prediction"
        return self._uid

    # ------------------------------------------------------------------
    def _busy(self, eng: ServingEngine) -> bool:
        return bool(eng.queue) or any(r is not None for r in eng.slot_req)

    def step(self) -> list[Request]:
        """One cluster round: every replica with work advances one fused
        step.  Returns requests finished this round, uids rewritten to
        cluster uids."""
        done: list[Request] = []
        stepped = False
        for i, eng in enumerate(self.replicas):
            if not self._busy(eng):
                continue
            stepped = True
            with TraceAnnotation("engine.replica", replica=i):
                finished = eng.step()
            for req in finished:
                req.uid = self._maps[i].pop(req.uid)
                done.append(req)
        if stepped:
            self.stats["decode_steps"] += 1
        return done

    def run_to_completion(self, max_steps: int = 10_000) -> list[Request]:
        done: list[Request] = []
        steps = 0
        while any(self._busy(eng) for eng in self.replicas):
            if steps >= max_steps:
                raise RuntimeError(
                    f"cluster did not drain within max_steps={max_steps}")
            done += self.step()
            steps += 1
        return done

    # ------------------------------------------------------------------
    # replay-surface views (harness.driver touches only these)
    # ------------------------------------------------------------------
    @property
    def queue(self) -> list[Request]:
        return [r for eng in self.replicas for r in eng.queue]

    @property
    def slot_req(self) -> list[Request | None]:
        return [r for eng in self.replicas for r in eng.slot_req]

    @property
    def compilations(self) -> list[dict[str, int]]:
        """Per-replica compile counts (the census asserts decode == 1 on
        every replica)."""
        return [dict(eng.compilations) for eng in self.replicas]

    def replica_stats(self) -> list[dict[str, Any]]:
        return [dict(eng.stats) for eng in self.replicas]
