"""Structured per-request lifecycle events emitted by the serving engine.

The engine used to grow an ad-hoc ``stats`` dict whenever a benchmark
needed a new counter; anything finer-grained (when did request 17 get
its first token?) meant another bespoke polling loop around
``engine.step()`` with its own ``device_get``.  This module is the
replacement: the engine publishes one :class:`EngineEvent` per request
lifecycle transition through an :class:`EventBus`, and consumers (the
load harness, benchmarks, tests) subscribe instead of polling.

Lifecycle of one request::

    submit ──> admit ──> first_token ──> progress* ──> finish
                  └──────────── preempt ──> admit ...(re-entry)

* ``submit``       — the request entered the engine queue.
  data: ``prompt_len``, ``max_new_tokens``, ``model``.
* ``admit``        — the request was seated in a slot.
  data: ``slot``, ``cached_tokens`` (prefix-cache hit span, 0 otherwise).
* ``first_token``  — the request's first token exists on device.  Under
  the bucketed scheduler this coincides with ``admit`` (the prefill
  dispatch samples it); under the chunked scheduler it is the fused step
  whose chunk grant completes the prompt.
* ``progress``     — one per occupied slot per harvest sync, carrying
  the slot's generated-token ``count``.  Emitted *after* the harvest's
  bulk ``device_get``, so its wall-clock stamp is completion-honest
  (the dispatch-side stamps on ``first_token`` are not — use the first
  ``progress`` with ``count >= 1`` for wall-clock TTFT).  With
  speculative decoding the event also carries the slot's cumulative
  ``accepted`` (draft tokens the target verified) and ``spec_steps``
  (fused steps the slot spec-decoded in) — both in the deterministic
  step currency, reduced by ``harness.metrics`` into the
  mean-accepted-draft-length metric.
* ``finish``       — the request completed and was harvested.
  data: ``n_generated``.
* ``preempt``      — the slot was recompute-preempted; the request
  re-enters admission later.  data: ``banked`` (tokens carried over).

Two more kinds belong to no request (``uid == -1``, ``ENGINE_KINDS``):

* ``dispatch``     — one per step program launched, right after the
  launch (its stamp is the host's, not the device's).  data:
  ``program`` (``"mixed"`` or ``"decode"``), ``slots`` (occupied
  slots), ``prefill_tokens`` (prompt tokens granted to this dispatch),
  ``lanes`` (query lanes the program computes: ``max_batch x W`` with
  W the chunk size for mixed, 1 — or the speculative horizon — for
  decode) and ``live_lanes`` (granted prompt tokens plus W per
  decoding slot; a slot that finished since the last harvest still
  counts), ``keys`` (occupied slots x the attended view's positions)
  and ``live_keys`` (over occupied slots, the positions in the key
  blocks that latent attention's kernel reads: up to the slot's last
  lane, from the host's upper bound on its index plus W, rounded up to
  the kernel's key block; an upper bound, since the bound runs ahead of
  a slot that finished since the last harvest; GQA's gather path reads
  all ``keys``).  It carries the same ``step`` as the ``progress``
  events of the harvest that follows it.  An :class:`EngineCluster` relays
  it with ``replica`` added.
* ``experts``      — MoE models only, one per harvest, after its read:
  what the held experts computed since the previous harvest, summed
  over the MoE layers.  data: ``expert_rows`` ((token, expert) pairs
  routed to a held expert and computed), ``experts_hit`` (held experts
  with at least one row, counted per layer and step) and
  ``dispatches`` (step programs launched since the previous harvest).
  The totals ride in the device-side slot state and come back in the
  harvest's one read of done/count.  Speculative steps are not counted.

Every event carries the engine's logical clock (``step`` = fused
dispatches so far) and a ``time.perf_counter()`` wall stamp.  Step
arithmetic is bit-reproducible across runs; wall stamps are not — the
harness keeps the two strictly separated for exactly that reason.

The bus costs one attribute check per would-be event when nobody
subscribed, so the engine's normal (harness-free) operation is
unchanged; the ``stats`` counters stay as the cheap always-on summary.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable

EVENT_KINDS = ("submit", "admit", "first_token", "progress", "finish",
               "preempt", "dispatch", "experts")
# the kinds that belong to no request (uid -1)
ENGINE_KINDS = ("dispatch", "experts")

# Host spans (``jax.profiler.TraceAnnotation``) the engine opens around
# its phases.  They cost ~1 µs each while no profiler runs; under one
# they land in the host plane on the device planes' clock, so a trace
# can say which phase the host was in while the device sat idle.
SPAN_NAMES = (
    "engine.step",           # ServingEngine.step; kwarg step = dispatches
                             # before it (its own dispatch's events carry
                             # step + 1 on the events' logical clock)
    "engine.submit",         # ServingEngine.submit
    "engine.admit",          # seating queued requests: block allocation,
                             # prefix lookup, the bucketed prefill
    "engine.capacity",       # block reservation, preemption included
    "engine.dispatch",       # table upload, chunk grants, the step
                             # program's launch, prefix registration;
                             # kwarg program = mixed | decode
    "engine.harvest",        # the sync: progress, finished rows, release
    "engine.harvest.wait",   # in harvest: the blocking read of done/count,
                             # where the host waits on the device
    "engine.harvest.fetch",  # in harvest: the read of finished token rows
    "engine.replica",        # EngineCluster.step, one replica's step;
                             # kwarg replica = its index
)

# ``jax.named_scope`` names in the step programs: compile-time metadata
# (each HLO op's ``op_name``), free at run time.  A profiler trace ties
# an op's device time to the innermost of these in its ``op_name``.
SCOPE_NAMES = (
    "embed",             # token embedding (models.layers.embed)
    "norm",              # every norm (models.layers.apply_norm)
    "attn.qkv",          # paged attention: q/k/v projections and rope
    "attn.kv_write",     # paged attention: codec store + pool write
    "attn.kv_gather",    # paged attention: block-table view of the pool
    "attn.core",         # scores and values, or the Pallas kernel
    "attn.out",          # the output projection
    "ffn",               # dense FFN or MoE
    "moe.route",         # in ffn: router scores and top-k selection
    "moe.dispatch",      # in ffn: held (token, expert) pairs sorted
    "moe.experts",       # in ffn: the held experts' grouped matmuls
    "moe.combine",       # in ffn: weighted rows added back to tokens
    "moe.shared",        # in ffn: the shared expert
    "head",              # final norm + vocabulary head (Model._unembed)
    "sample",            # the sampler over the step's logits
    "slot_update",       # the SlotState writes after sampling
)


@dataclass(frozen=True)
class EngineEvent:
    """One lifecycle transition of one request, or one dispatch."""

    kind: str                 # one of EVENT_KINDS
    uid: int                  # engine request uid (-1: dispatch)
    step: int                 # engine logical clock (fused dispatches)
    t: float                  # wall stamp (time.perf_counter())
    data: dict[str, Any] = field(default_factory=dict)

    def __post_init__(self) -> None:
        if self.kind not in EVENT_KINDS:
            raise ValueError(f"unknown event kind {self.kind!r}; expected "
                             f"one of {EVENT_KINDS}")


class EventBus:
    """Tiny synchronous pub/sub: subscribers are called in order, on the
    engine's host thread, at emission time."""

    def __init__(self) -> None:
        self._subs: list[Callable[[EngineEvent], None]] = []

    @property
    def active(self) -> bool:
        """True when at least one subscriber would see an event — the
        engine skips event construction entirely otherwise."""
        return bool(self._subs)

    def subscribe(self, cb: Callable[[EngineEvent], None]) -> None:
        self._subs.append(cb)

    def unsubscribe(self, cb: Callable[[EngineEvent], None]) -> None:
        self._subs.remove(cb)

    def publish(self, event: EngineEvent) -> None:
        for cb in self._subs:
            cb(event)


class EventLog:
    """The standard subscriber: an append-only list."""

    def __init__(self) -> None:
        self.events: list[EngineEvent] = []

    def __call__(self, event: EngineEvent) -> None:
        self.events.append(event)

    def __len__(self) -> int:
        return len(self.events)


def now() -> float:
    return time.perf_counter()
