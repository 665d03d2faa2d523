"""Slot-based continuous batching over a ServeEngine.

A fixed bank of ``slots`` batch rows shares one cache pytree.  Requests are
admitted into free slots in arrival order (prefill runs per request at its
exact prompt length — no prompt padding, so tokens stay identical to the
per-request reference), decode advances every slot in one jitted step, and
finished requests are evicted so waiting requests can reuse the slot.
Throughput holds under a stream of staggered requests instead of requiring
one synchronized batch.

Token identity: each slot's attention sees only its own rows (per-slot
lengths mask the kv cache; per-slot positions drive RoPE), so a request
decoded in a mixed batch emits the same greedy tokens as the same request
decoded alone — the property the equivalence fixture pins.  The one
documented exception is MoE routing: expert capacity is contended *across*
the batch (Switch-style drops), so per-request token identity across
different batch compositions does not hold by construction; MoE archs are
therefore benchmarked but not pinned in stream scenarios.

Inactive slots keep stepping with garbage rows (the batch shape is static);
their outputs are never recorded and their rows never influence other
slots.  Admission scatters a single-request cache into the slot bank with
one generic ``dynamic_update_slice`` per leaf — stale rows beyond the new
request's length are masked by its per-slot length until overwritten.

Like the engine, the loop never reads a device value: the schedule depends
only on statically known prompt/gen lengths, and all tokens are fetched in
one sync at the end.

Each ``run`` call records its host phases as spans (``serve.wave``,
``serve.bank``, ``serve.admit``, ``serve.decode``, ``serve.fetch``,
``serve.unpack``; ``telemetry.SpanRecorder``) and each request's admission
and completion on the step clock, the count of batched decode steps
dispatched so far; ``stats`` carries both.

The same slot bookkeeping also drives ``PipelineServeEngine`` (continuous
batching *across* pipeline stages): the engine supplies per-stage cache
banks (``slot_bank``), per-request admission (``admit_slot``), the chained
decode step, and — under an injected stage ``kill`` — checkpoint-backed
recovery with per-slot replay (``recover_and_replay``); the host-side
schedule here is identical either way, which is why pipelined streams stay
token-identical to the monolithic reference.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import init_serve_cache, prefill

from .engine import _quiet
from .pipeline import StageDown
from .telemetry import SpanRecorder


@dataclasses.dataclass
class Request:
    """One serving request: prompt tokens (1, S) int32 + a fixed greedy
    generation budget.  extras: per-request modal inputs with leading dim 1
    (vlm: vision; encdec: frames — stream requests must share the frames
    length, since the slot bank's cross-kv buffers have one static shape)."""
    rid: int
    tokens: np.ndarray
    gen_len: int
    extras: dict | None = None


def leaf_batch_axes(shapes):
    """Per-leaf batch-axis index from a ``shapes(batch_size)`` eval-shape
    callable: the one axis where a batch=1 and a batch=2 cache disagree
    (only batch_size varies).  Shared by the monolithic slot bank and the
    pipeline engine's per-stage banks."""
    s1, s2 = shapes(1), shapes(2)
    return jax.tree.map(
        lambda a, b: int(np.argmax(np.array(a.shape) != np.array(b.shape))),
        s1, s2)


def _insert_leaf(full, one, slot, b_ax):
    """Scatter a single-request cache leaf into slot `slot` of the bank.

    Writes `one`'s full extent at offset 0 on every axis except the batch
    axis — covering both seq-bearing leaves (kv rows [0, S1)) and
    per-slot state (ssm state, conv buffers, length counters)."""
    fullb = jnp.moveaxis(full, b_ax, 0)
    upd = jnp.moveaxis(one, b_ax, 0).astype(fullb.dtype)
    starts = (slot,) + (0,) * (fullb.ndim - 1)
    return jnp.moveaxis(jax.lax.dynamic_update_slice(fullb, upd, starts),
                        0, b_ax)


class SlotScheduler:
    """Continuous batching: admit/evict requests into `slots` cache rows.

    clock: the span recorder's clock (default ``time.perf_counter``);
    it times the host phases reported in ``stats`` and nothing else."""

    def __init__(self, engine, slots: int, clock=time.perf_counter):
        self.engine = engine
        self.slots = int(slots)
        self._clock = clock
        self._batch_axes = None
        cfg = engine.cfg

        def _admit(params, tokens, extras, cache_slots, slot_tokens, slot):
            batch = {"tokens": tokens, **extras}
            with jax.named_scope("admit/prefill"):
                c1 = init_serve_cache(cfg, 1, tokens.shape[1], batch=batch)
                logits, c1 = prefill(cfg, params, batch, c1)
            with jax.named_scope("admit/first_token"):
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
            with jax.named_scope("admit/scatter"):
                cache_slots = jax.tree.map(
                    lambda full, one, ax: _insert_leaf(full, one, slot, ax),
                    cache_slots, c1, self._batch_axes)
                slot_tokens = jax.lax.dynamic_update_slice(slot_tokens, tok,
                                                           (slot, 0))
            return tok, cache_slots, slot_tokens

        # slot_tokens is NOT donated: per-step token arrays are retained on
        # the host side until the single end-of-run fetch
        self._admit = jax.jit(_admit, donate_argnums=(3,))

    def _leaf_batch_axes(self, proto_extras):
        cfg, ml = self.engine.cfg, self.engine.max_len

        def shapes(b):
            batch = {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)}
            for k, v in proto_extras.items():
                batch[k] = jax.ShapeDtypeStruct((b,) + v.shape[1:], v.dtype)
            return jax.eval_shape(
                lambda: init_serve_cache(cfg, b, ml, batch=batch))

        return leaf_batch_axes(shapes)

    def run(self, requests: list[Request], engine: str = "fast",
            kill: dict | list | None = None, replan: dict | None = None):
        """Serve `requests` to completion; returns (streams, stats) with
        streams[i] the i-th request's np int32 greedy tokens (gen_len,).

        kill: optional ``{"after_step": s, "stage": k}`` — or a list of
        such specs — only meaningful when the engine is a
        ``PipelineServeEngine``: stage ``k`` loses a copy after the
        ``s``-th batched decode step (an optional ``"replica"`` key names
        the copy node; default the primary).  A kill with surviving warm
        replicas is absorbed with **zero restore** — no checkpoint read,
        no replay.  Only when a stage's last copy dies is it restored
        from its checkpoint onto a spare node with every in-flight
        request replayed into its slot (see
        ``PipelineServeEngine.recover_and_replay``).  The streams stay
        identical to an undisturbed run either way.

        replan: optional ``{"after_step": s, "cluster": state, ...}`` —
        only meaningful for a ``PipelineServeEngine``: after the ``s``-th
        batched decode step, ``replan_live`` runs against ``state`` (a
        ClusterState or ClusterGraph; optional ``max_moves`` /
        ``min_gain_s`` / ``allow_replicas``), executes the bounded plan
        diff as live migrations and replica adds, and replays every
        in-flight request into its slot for the stages whose primary
        moved (``migrate_and_replay``; replica adds are capacity-only).
        Streams stay identical to an undisturbed run — the ``-replan``
        cells of the serve equivalence fixture pin this.

        stats: ``wall_s`` (the ``serve.wave`` span's seconds),
        ``decode_steps``, ``slot_utilization`` (busy slot-steps over all),
        ``spans`` (``{name: [count, seconds]}``) and, on the fast path,
        ``requests``: ``{rid: [admit_step, done_step]}``, the decode steps
        dispatched when the request was admitted (its first token) and
        after its last token was produced, so ``done_step - admit_step ==
        gen_len - 1``."""
        if not requests:
            return [], {"wall_s": 0.0, "decode_steps": 0,
                        "slot_utilization": 0.0, "spans": {},
                        "requests": {}}
        for r in requests:
            self.engine._check_fit(r.tokens.shape[1], r.gen_len)

        rec = SpanRecorder(self._clock)
        events = None
        with rec.span("serve.wave"):
            if engine == "reference":
                streams, n_steps, util = self._reference(requests), 0, 1.0
            else:
                streams, n_steps, busy, events = self._serve(
                    requests, kill, replan, rec)
                util = busy / max(1, n_steps * self.slots)
        stats = {"wall_s": rec.seconds("serve.wave"),
                 "decode_steps": n_steps, "slot_utilization": util,
                 "spans": rec.snapshot()}
        if events is not None:
            stats["requests"] = events
        return streams, stats

    def _reference(self, requests):
        """Per-request isolation: the oracle the slot path must match."""
        streams = []
        for r in requests:
            batch = {"tokens": jnp.asarray(r.tokens),
                     **{k: jnp.asarray(v)
                        for k, v in (r.extras or {}).items()}}
            toks = self.engine.generate(batch, r.gen_len, engine="reference")
            streams.append(toks[0])
        return streams

    def _serve(self, requests, kill, replan, rec):
        """The slot path; returns (streams, decode steps, busy slot-steps,
        per-request step events)."""
        eng = self.engine
        cfg, B = eng.cfg, self.slots
        pipeline = getattr(eng, "is_pipeline", False)
        proto_extras = requests[0].extras or {}
        with rec.span("serve.bank"):
            proto_batch = {"tokens": jnp.zeros((B, 1), jnp.int32)}
            for k, v in proto_extras.items():
                proto_batch[k] = jnp.zeros((B,) + v.shape[1:], v.dtype)
            if pipeline:
                # per-stage cache banks; admission/scatter live on the engine
                cache = eng.slot_bank(B, proto_batch)
            else:
                if self._batch_axes is None:
                    self._batch_axes = self._leaf_batch_axes(proto_extras)
                cache = init_serve_cache(cfg, B, eng.max_len,
                                         batch=proto_batch)
            slot_tokens = jnp.zeros((B, 1), jnp.int32)
        tel = getattr(eng, "telemetry", None)

        next_idx = 0
        active: dict[int, list] = {}          # slot -> [request, n_emitted]
        free = list(range(B))
        slot_len = np.zeros(B, np.int64)      # host mirror of cache lens
        first_tok: dict[int, object] = {}     # rid -> (1,1) device token
        step_toks: list = []                  # per-step (B,1) device tokens
        step_maps: list[dict[int, int]] = []  # per-step slot -> rid
        events: dict[int, list[int]] = {}     # rid -> [admit, done] step
        n_steps = busy = 0

        kills = ([] if kill is None
                 else [kill] if isinstance(kill, dict) else list(kill))
        fired = [False] * len(kills)
        replanned = False
        # overlap engines pace admissions (admit_burst) so prefills ride
        # the micro-batch interleave instead of stalling the decode train;
        # None keeps the legacy fill-every-free-slot schedule.  Pacing
        # reorders admissions only — per-request tokens are schedule
        # -independent (slot isolation), so streams are unchanged.
        burst = getattr(eng, "admit_burst", lambda: None)()
        while next_idx < len(requests) or active:
            admitted = 0
            while free and next_idx < len(requests) and (
                    burst is None or admitted < burst):
                r = requests[next_idx]
                next_idx += 1
                admitted += 1
                slot = free.pop(0)
                with rec.span("serve.admit", rid=r.rid, slot=slot,
                              prompt_len=r.tokens.shape[1]):
                    extras = {k: jnp.asarray(v)
                              for k, v in (r.extras or {}).items()}
                    if pipeline:
                        tok, cache, slot_tokens = eng.admit_slot(
                            jnp.asarray(r.tokens), extras, cache,
                            slot_tokens, slot)
                    else:
                        tok, cache, slot_tokens = _quiet(
                            self._admit, eng.params, jnp.asarray(r.tokens),
                            extras, cache, slot_tokens, np.int32(slot))
                first_tok[r.rid] = tok
                events[r.rid] = [n_steps, n_steps]
                slot_len[slot] = r.tokens.shape[1]
                if r.gen_len > 1:
                    active[slot] = [r, 1]
                else:
                    free.append(slot)
                    free.sort()
            if pipeline and not all(fired):
                # a copy dies after `after_step` completed batched decode
                # steps (0 = right after the first admissions); with warm
                # replicas the survivors absorb it (zero restore), and
                # only a last-copy loss costs a checkpoint restore with
                # every in-flight request replayed into its slot
                hit = False
                for i, spec in enumerate(kills):
                    if not fired[i] and n_steps >= spec["after_step"]:
                        fired[i] = True
                        hit = True
                        if spec.get("silent"):
                            # node goes dark: nothing happens until the
                            # heartbeat monitor confirms it DEAD mid-chain
                            eng.fail_silent(spec["stage"])
                        else:
                            eng.kill_stage(spec["stage"],
                                           replica=spec.get("replica"))
                if hit and eng.down:
                    inflight = [(s, st[0], st[1])
                                for s, st in sorted(active.items())]
                    cache, slot_tokens = eng.recover_and_replay(
                        inflight, cache, slot_tokens, proto_batch)
            if (replan is not None and pipeline and not replanned
                    and n_steps >= replan["after_step"]):
                # telemetry-driven live replan: execute the bounded plan
                # diff as migrations / replica adds, then replay every
                # in-flight request into its slot on the moved stages'
                # fresh banks (replica adds need no replay)
                replanned = True
                res = eng.replan_live(
                    replan["cluster"],
                    max_moves=replan.get("max_moves", 1),
                    min_gain_s=replan.get("min_gain_s", 0.0),
                    allow_replicas=replan.get("allow_replicas", False))
                if res.migrated_stages:
                    inflight = [(s, st[0], st[1])
                                for s, st in sorted(active.items())]
                    cache, slot_tokens = eng.migrate_and_replay(
                        list(res.migrated_stages), inflight, cache,
                        slot_tokens, proto_batch)
            if not active:
                continue
            if tel is not None:
                tel.record_queue_depth(len(active))
            bucket = eng.bucket_for(
                int(max(slot_len[s] for s in active)) + 1)
            with rec.span("serve.decode", step=n_steps, bucket=bucket,
                          active=len(active)):
                while True:
                    try:
                        slot_tokens, _, cache = eng._decode_quiet(
                            slot_tokens, cache, bucket)
                        break
                    except StageDown:
                        # a silent failure just got confirmed DEAD
                        # mid-chain: restore the stage and replay every
                        # in-flight request into its slot, then retry the
                        # batched step
                        inflight = [(s, st[0], st[1])
                                    for s, st in sorted(active.items())]
                        cache, slot_tokens = eng.recover_and_replay(
                            inflight, cache, slot_tokens, proto_batch)
            slot_len += 1                      # every row writes, active or not
            n_steps += 1
            busy += len(active)
            step_toks.append(slot_tokens)
            step_maps.append({s: st[0].rid for s, st in active.items()})
            for slot in list(active):
                active[slot][1] += 1
                if active[slot][1] >= active[slot][0].gen_len:
                    events[active[slot][0].rid][1] = n_steps
                    del active[slot]
                    free.append(slot)
            free.sort()

        # single host sync: fetch every step's tokens at once
        with rec.span("serve.fetch"):
            stacked = (np.asarray(jnp.concatenate(step_toks, axis=1))
                       if step_toks else np.zeros((B, 0), np.int32))
        with rec.span("serve.unpack"):
            streams = {r.rid: [int(np.asarray(first_tok[r.rid])[0, 0])]
                       for r in requests}
            for i, m in enumerate(step_maps):
                for slot, rid in m.items():
                    streams[rid].append(int(stacked[slot, i]))
            out = [np.asarray(streams[r.rid], np.int32) for r in requests]
        return out, n_steps, busy, events
