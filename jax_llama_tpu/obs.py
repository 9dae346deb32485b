"""Request-timeline tracing, latency histograms, and SLO accounting.

The serving stack's earlier observability was flat counters plus one
``ttft_ms_ewma`` gauge — enough to graph throughput, useless for
answering "where did THIS request's 900 ms go?" or "what goodput do we
hold under a 200 ms TTFT SLO?".  This module is the sensor layer those
questions (and ROADMAP item 5's online chunk controller) need:

  * **Event timeline** (:class:`Observability`).  Every request owns a
    bounded span timeline through the admission state machine —
    ``received -> queued -> prefilling -> restoring -> decoding ->
    finished/failed/cancelled`` (``received``: POST accepted to
    ``submit``, where the server's TTFT clock starts; then the PR 5/6
    states) — and every jitted
    serving dispatch gets a span in a bounded ring recording its kind
    (``decode`` / ``fused`` / ``spec`` / ``insert`` / ``suffix_insert``
    / ``adopt``), effective K/R, slot occupancy, prompt tokens advanced
    by a riding prefill lane, packed-fetch wall time, and how many
    host-tier swap-ins were in flight (the decode/swap overlap, made
    visible).  Request spans are causally linked to the dispatch spans
    they rode in (span.dispatches lists dispatch seq numbers), so a
    timeline answers "which chunk dispatches carried my prefill" and a
    dispatch answers "whose tokens did I emit".
  * **Loop phases** (:meth:`Observability.loop_phase`,
    :data:`LOOP_PHASES`).  The serving-loop thread names what it does
    BETWEEN dispatch records; the phases tile that time, and each
    dispatch record carries its own gap (``gap_ms``, ``host_ms`` per
    phase, ``gap_cpu_ms``, ``compiles``).  Every phase and every
    dispatch is also a ``jax.profiler.TraceAnnotation``
    (``llm.loop.<phase>`` / ``llm.dispatch`` with the record's
    ``seq``), so a profiler session holds them on the device trace's
    clock and a device idle gap is named by overlap.
  * **Loop spans** (:meth:`Observability.loop_span`,
    :data:`LOOP_SPANS`).  The parts of a phase, or of a dispatch's own
    host time: child spans with their cause, the request they work for
    and the ring number of the record their gap leads to.  They ride
    the record as ``span_ms`` / ``span_n`` (``host_ms`` keeps its keys
    and its tiling) and the profiler's clock as ``llm.span.<name>``.
  * **Latency histograms** (:class:`Histogram`).  Prometheus cumulative-
    bucket histograms for TTFT, inter-token latency, queue wait,
    prefill-chunk latency, swap-in latency, jit compile time, and
    dispatch wall time — the distributions the flat EWMA hid.
    ``dispatch_ms`` is a LABELED family: one series per dispatch kind
    (``{kind="decode"|"fused"|"spec"|"insert"|"suffix_insert"|
    "adopt"}``), so a spec-round regression no longer hides inside a
    lumped all-kinds distribution.  Rendered straight into the
    ``/metrics`` text exposition (``_bucket``/``_sum``/``_count``).
  * **Jit-cache observability**.  A ``jax.monitoring`` listener turns
    every backend compile into a ``compile_ms`` observation, a span in
    the trace (its own ``jit compiles`` track), and a per-program
    counter (:meth:`Observability.record_compile`; serving.py names
    the program via :func:`attribute_compiles` around each dispatch),
    and ``/metrics`` exposes per-program jit-cache entry counts — a
    bucketing bug that blows the jit cache is a visible counter, not a
    mystery stall.
  * **SLO accounting**.  With ``slo_ttft_ms`` / ``slo_itl_ms``
    configured (run.py ``--slo-ttft-ms`` / ``--slo-itl-ms``), every
    finished request is scored against both deadlines:
    ``slo_attainment`` gauges (windowed, last 256 requests) and a
    ``goodput_tokens_total`` counter (tokens from requests that met
    every configured deadline — the objective an online
    ``decode_chunk``/``prefill_budget`` controller will maximize).
    An unconfigured dimension always passes, so with no SLO flags the
    gauges read 1.0 and goodput equals delivered tokens.
  * **Metric registry** (:data:`METRICS` / :func:`metric_meta`).  The
    explicit ``# TYPE`` + ``# HELP`` source for every scalar the
    ``/metrics`` endpoint exposes — replacing the old ``"total" in k``
    type heuristic (which already needed a hand-carved
    ``radix_nodes_total`` exception).
  * **Trace export**.  :meth:`Observability.trace_json` emits
    Chrome/Perfetto ``trace_event`` JSON for a recent serving window —
    dispatch spans on one track, request lifecycles on per-request
    tracks, fault/quarantine/kv-tier annotations as instant events —
    loadable in ``chrome://tracing`` or https://ui.perfetto.dev (the
    server serves it at ``GET /debug/trace``).
  * **Decision audit log** (:class:`DecisionLog`).  Every control-plane
    decision — a router's route/reroute/handoff pick (with the
    candidate set and scores it chose from), a brownout-ladder rung
    move, a crash-recovery/quarantine/probe rebuild, a shed — lands as
    one ring-buffered structured event carrying the external request id
    where one exists, so ``GET /debug/decisions`` answers "why did
    request X land on replica Y" and joins back to the request's
    ``/debug/requests/<id>`` timeline by id.  The server's decisions
    live on its Observability instance (they survive batcher rebuilds
    like everything else here); the ReplicaRouter owns its own log.
  * **Flight recorder**.  The bounded rings above (decisions, the
    annotation/state-transition ring, dispatch spans) plus a periodic
    :meth:`Observability.record_metrics_snapshot` ring and the
    :class:`StructuredLogger` tail are the black-box a postmortem
    needs: ``GET /debug/bundle`` (server.py / router.py) exports them
    as one artifact — config + metrics + last-N decisions + log tail +
    Perfetto trace — capturing "the 30 s before the 503 storm".
  * **Anomaly detection building block** (:class:`EwmaDetector`).  An
    online EWMA mean/variance z-score detector — the router's
    per-replica health sentinel (router.py) runs one per latency-class
    signal; kept here because it is pure host math and unit-testable
    without HTTP.

Overhead contract: everything here is HOST-side bookkeeping recorded at
boundaries the serving loop already crosses (admission, the one packed
fetch per chunk, slot frees).  Recording performs **zero device
dispatches and zero host syncs** — ``make perf-smoke`` asserts the
1-fetch / 0-upload steady state is bit-identical with tracing on (it is
always on; the rings are bounded deques, a few hundred bytes per
entry).  All methods are thread-safe (one lock; the serving loop
writes, HTTP handler threads snapshot).
"""

from __future__ import annotations

import bisect
import functools
import json
import math
import sys
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .degrade import FEATURES
from .faults import SITES
from .ops.moe import STATS as _MOE_STATS
from .ops.mhc import STATS as _HC_STATS

# Dispatch kinds serving.py records — each owns a labeled dispatch_ms
# histogram series.  record_dispatch VALIDATES against this set: a
# typo'd kind would otherwise mint a phantom metrics series nobody
# scrapes.
DISPATCH_KINDS = frozenset({
    "decode", "fused", "spec", "insert", "suffix_insert", "adopt",
})

# Serving-loop phases (Observability.loop_phase): what the loop thread —
# the batcher's single owner — is doing BETWEEN two dispatch records.
# The thread is always in exactly one, so they tile the gap with no
# holes; validated like the kinds above (a typo raises).  Server side
# (server.py::LLMServer._loop): ``control`` (heartbeat, flight snapshot,
# control calls, probe rebuilds, recovery), ``intake`` (inbox drain,
# pre-admission reaping, the overload ladder, shed, submit, reap),
# ``idle`` (blocked on an empty inbox with nothing pending — waiting
# for work, not overhead), ``deliver`` (failed-request pops and the
# per-token loop: TTFT/ITL, ``chunks.put``, finalize).  Scheduler side
# (serving.py::ContinuousBatcher): ``barrier`` (deferred-error fetches —
# a device wait), ``admit`` (``_admit`` less its own dispatch records:
# chain hashing, prefix match, block allocation, prefill set-up and
# uploads, restore polling), ``prep`` (chunk pick, dirty-row sync,
# fault sites), ``emit`` (replay of the packed block,
# ``request_end``, slot frees).
LOOP_PHASES = frozenset({
    "control", "intake", "idle", "deliver",
    "barrier", "admit", "prep", "emit",
})

# Child spans (Observability.loop_span): the parts of a phase, or of the
# host time inside a dispatch record (``dispatch``: dispatch_begin to the
# record).  name -> the parent it is recorded under, a phase, ``dispatch``
# or another span; a closed set, validated like the phases.  Opened
# anywhere else a span records nothing and its time stays with whatever is
# open there, so a span's time is part of its parent's by construction
# (``_free_slot`` from a cancel is no ``emit.free``).  What a phase spends
# outside its spans is its self time.  serving.py holds the sites.
LOOP_SPANS: Dict[str, str] = {
    "admit.restore": "admit",      # swap-in polling + restored admissions
    "admit.hash": "admit",         # chain keys of the prompt
    "admit.match": "admit",        # the prefix walk
    "admit.alloc": "admit",        # claims and block allocation
    "admit.evict": "admit.alloc",  # ... when the free list is dry
    "admit.upload": "admit",       # the prefill lane's device operands
    "admit.insert": "admit",       # an insert program's operands
    "prep.sync_rows": "prep",      # dirty rows to the device twins
    "prep.snapshots": "prep",      # state-snapshot operands
    "dispatch.submit": "dispatch",   # the jitted call, until it returns
    "dispatch.publish": "dispatch",  # chain publish under the device
    "emit.replay": "emit",         # replay of the packed block
    "emit.free": "emit.replay",    # a finished row's slot free
}

# The device side's spans: every ``jax.named_scope`` the package opens, a
# closed set (tests/test_scopes.py holds the source to it both ways).  A
# scope is a name on the operations traced under it and costs nothing at
# run time; the profiler puts an operation's path in its ``tf_op`` stat,
# on the clock the host spans above share.  LANES say which half of a
# dispatch an operation served — a reader takes the LAST lane of a path
# (``lane.mixed`` opens inside ``lane.chunk``); the insert programs open
# none, their module name is their lane.  The others are LEAF scopes:
# what the operation did, the innermost one of a path
# (``utils.profiling.lane_and_scope``).  A scope added to an unchanged
# program shows only in executables compiled after it: the compile
# cache's key leaves names out.
DEVICE_LANES: Dict[str, str] = {
    "lane.chunk": "_fused_chunk's in-flight admission: state pick-up, the "
                  "view's gather, the chunk's forward, landing, the sample",
    "lane.mixed": "the mixed branch's shared pass: emit, mixed_forward, the "
                  "shared head product, the riders' draw and advance",
    "lane.decode": "_chunk_scan's lax.scan: every decode iteration of "
                   "_paged_decode_chunk and of _fused_chunk",
}
DEVICE_SCOPES: Dict[str, str] = {
    **DEVICE_LANES,
    # serving.py
    "cache.gather": "_gather_cache: a row's contiguous view cut from the pool",
    "cache.land": "_land_chunk / _scatter_back / an insert's landing: new "
                  "entries of a view or a prefill cache into pool blocks",
    "state.move": "recurrent state between slots, snapshots and a view",
    "sample": "argmax or warp and draw, the logprob, the finite guard",
    "emit": "_emit, _advance, _pack_stats and the packed block's transposes",
    "admit.sample": "_admission_sample: the completing prompt's head and draw",
    # models/llama.py, shared by every block
    "cache.write": "paged_pool_write(_blocks): the dynamic_update_slice chain",
    "embed": "embed_tokens: the token embedding lookup",
    "layers": "layer_scan: the layer scan's own slices and stacking, and a "
              "layer's norms and residual adds outside its sub-blocks",
    "head": "lm_head_logits: the final norm and the vocabulary product",
    "dense.attention": "the dense block's attention, norm to output product",
    "dense.ffn": "a SwiGLU feed-forward (dense layers of every block)",
    # models/mla_moe.py, ops/mhc.py
    "mla.project": "latent attention's q / kv_a / kv_b / o products and rope",
    "mla.attend_decode": "absorbed attention over the latent cache",
    "mla.attend_prefill": "decompressed or tiled attention of a prompt chunk",
    "hc.coeff": "an mHC unit's coefficients (Sinkhorn)",
    "hc.pre": "an mHC unit's read of the streams",
    "hc.post": "an mHC unit's write back into the streams",
    # ops/moe.py, models/mla_moe.py
    "moe.route": "the router's scores, top-k and sort",
    "moe.experts": "the grouped expert products",
    "moe.shared": "the shared expert",
    # models/afmoe.py, sambay.py, falcon_h1.py, dsa_moe.py, ops/key_selection.py
    "attn.window": "a window attention layer",
    "attn.full": "a full attention layer",
    "attn.cross": "a cross layer over the full layer's keys",
    "attn.proj": "sparse attention's q / k / v / o products, norms, rope",
    "attn.index": "the index keys' scores",
    "attn.select": "the k-th value and the chosen list",
    "attn.sparse": "the gather of the chosen keys and attention over them",
    "ssm.mix": "a mixer, projections to output (holds ssm.scan / ssm.step)",
    "ssm.scan": "the recurrence over a prompt chunk",
    "ssm.step": "the recurrence's one-token step",
    "gmu.mix": "a gated memory unit",
}

# Why the queue's head stayed queued (record field ``blocked``, counter
# admit_blocked_total{reason}): the prefill lane is taken, the pool lacks
# blocks for its reservation, no slot is free, or a completed swap-in
# (FIFO priority over the queue) took the lane in the same ``_admit``.
ADMIT_BLOCKED = ("lane", "capacity", "slot", "restoring")

# ---------------------------------------------------------------------------
# Histograms (Prometheus cumulative buckets)
# ---------------------------------------------------------------------------

# Default latency buckets in MILLISECONDS: sub-ms dispatches through
# multi-second prefills/swaps.  +Inf is implicit.
DEFAULT_BUCKETS_MS = (
    0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
    1000.0, 2500.0, 5000.0, 10000.0, 30000.0,
)


class Histogram:
    """A Prometheus-style cumulative histogram (fixed upper bounds).

    ``observe(v)`` is a bisect + two adds; NOT itself synchronized —
    every caller inside :class:`Observability` holds the owner's lock,
    so a concurrent ``/metrics`` render can never see a bucket updated
    ahead of ``_count``.  ``expose(prefix)`` renders the standard
    ``_bucket{le=...}`` / ``_sum`` / ``_count`` family with its
    ``# HELP`` / ``# TYPE`` header.  ``labels`` names one series of a
    LABELED family (e.g. ``{"kind": "decode"}``): the labels render
    into every sample line and ``expose(header=False)`` suppresses the
    family header so sibling series share one ``# TYPE``.  Bucket
    counts are stored NON-cumulative and summed at exposition
    (observe stays O(log B))."""

    def __init__(self, name: str, help_text: str,
                 buckets: Sequence[float] = DEFAULT_BUCKETS_MS,
                 labels: Optional[Dict[str, str]] = None):
        self.name = name
        self.help = help_text
        self.labels = dict(labels) if labels else {}
        self.buckets: Tuple[float, ...] = tuple(float(b) for b in buckets)
        if list(self.buckets) != sorted(set(self.buckets)):
            raise ValueError(f"histogram buckets must ascend: {buckets}")
        self.counts = [0] * (len(self.buckets) + 1)  # last = +Inf overflow
        self.sum = 0.0
        self.count = 0

    def observe(self, value: float) -> None:
        v = float(value)
        self.counts[bisect.bisect_left(self.buckets, v)] += 1
        self.sum += v
        self.count += 1

    def cumulative(self) -> List[Tuple[str, int]]:
        """[(le_label, cumulative_count)] including +Inf."""
        out: List[Tuple[str, int]] = []
        acc = 0
        for b, c in zip(self.buckets, self.counts):
            acc += c
            out.append((format(b, "g"), acc))
        out.append(("+Inf", acc + self.counts[-1]))
        return out

    def expose(self, prefix: str = "", header: bool = True) -> List[str]:
        n = prefix + self.name
        lines = (
            [f"# HELP {n} {self.help}", f"# TYPE {n} histogram"]
            if header else []
        )
        base = "".join(
            f'{k}="{v}",' for k, v in sorted(self.labels.items())
        )
        for le, c in self.cumulative():
            lines.append(f'{n}_bucket{{{base}le="{le}"}} {c}')
        lab = "{" + base.rstrip(",") + "}" if base else ""
        lines.append(f"{n}_sum{lab} {round(self.sum, 3)}")
        lines.append(f"{n}_count{lab} {self.count}")
        return lines


# The serving stack's histogram families (name -> help); every
# Observability owns one of each.  All values are milliseconds.
HISTOGRAMS = {
    "ttft_ms": (
        "Time to first token per delivered request (ms; client-observed, "
        "crash-recovery replays included)"),
    "itl_ms": (
        "Inter-token latency per delivered token after the first (ms; "
        "tokens inside one fused chunk arrive together, so chunked decode "
        "shows a mass near 0 plus one chunk-period mode)"),
    "queue_wait_ms": (
        "Submit-to-admission wait per request (ms; the queued span)"),
    "prefill_chunk_ms": (
        "Wall time of prefill-carrying dispatches (ms: fused prefill "
        "chunks and classic whole-prompt inserts)"),
    "swap_in_ms": (
        "Host-tier swap-in latency per restored admission (ms: staging "
        "H2D start to pool adoption)"),
    "compile_ms": (
        "Backend compile time per jit-cache miss (ms; fed by the "
        "jax.monitoring listener — a busy series here means the jit "
        "cache is being blown, see jit_cache_entries)"),
    "dispatch_ms": (
        "Wall time per jitted serving dispatch incl. its packed fetch "
        "(ms; one K-iteration or R-round chunk each; LABELED by "
        "dispatch kind)"),
    "prefix_hit_depth_tokens": (
        "Prefix-cache hit depth per admission (TOKENS served from "
        "cached blocks; the 0-hit mass lands in the first bucket — "
        "a cold fleet reads as all-first-bucket)"),
    "session_kv_blocks": (
        "KV pool blocks a session held at slot free (BLOCKS, not ms; "
        "the per-session cache footprint distribution)"),
}

# Families rendered as one labeled series per dispatch kind rather
# than a single lumped series (Observability keeps one Histogram per
# kind, created lazily on first dispatch of that kind).
LABELED_HISTOGRAMS = frozenset({"dispatch_ms"})

# Non-latency families override the ms bucket ladder with their own
# unit's (tokens / blocks, pow2 — the same bucketing the admission
# paths use for jit-cache keys, so histogram edges line up with the
# actual quantization of the measured values).
HISTOGRAM_BUCKETS: Dict[str, Tuple[float, ...]] = {
    "prefix_hit_depth_tokens": tuple(
        float(1 << i) for i in range(15)  # 1 .. 16384 tokens
    ),
    "session_kv_blocks": tuple(
        float(1 << i) for i in range(11)  # 1 .. 1024 blocks
    ),
}


# ---------------------------------------------------------------------------
# Metric registry: explicit # TYPE + # HELP for every /metrics scalar
# ---------------------------------------------------------------------------

def _reg(kind: str, help_text: str) -> Tuple[str, str]:
    if kind not in ("counter", "gauge"):
        raise ValueError(kind)
    return (kind, help_text)


METRICS: Dict[str, Tuple[str, str]] = {
    # -- batcher core -------------------------------------------------------
    "emitted_tokens_total": _reg("counter", "Tokens emitted to callers"),
    "decode_steps_total": _reg(
        "counter", "Decode iterations run (K per chunked dispatch)"),
    "active_slots": _reg("gauge", "Slots holding a live request"),
    "queued_requests": _reg("gauge", "Requests waiting for admission"),
    "free_blocks": _reg("gauge", "Unallocated KV pool blocks"),
    "total_blocks": _reg("gauge", "KV pool capacity in blocks"),
    "drafts_proposed_total": _reg(
        "counter", "Draft tokens proposed (speculative serving)"),
    "drafts_accepted_total": _reg(
        "counter", "Draft tokens accepted (speculative serving)"),
    "draft_acceptance_rate": _reg(
        "gauge", "Lifetime draft acceptance fraction"),
    "nonfinite_rows_total": _reg(
        "counter", "Requests failed by the non-finite logits guard"),
    # -- prefix cache / KV capacity ----------------------------------------
    "prefix_cached_blocks": _reg(
        "gauge", "Idle HBM-resident prefix-cache blocks (pre-radix "
                 "alias of the store's idle count)"),
    "prefix_requests_hit_total": _reg(
        "counter", "Admissions that reused cached prefix blocks"),
    "prefix_blocks_reused_total": _reg(
        "counter", "Cached prefix blocks reused by admissions"),
    "radix_nodes_total": _reg(
        "gauge", "Keyed blocks in the radix prefix tree (a resident "
                 "COUNT that shrinks on eviction, not a counter)"),
    "prefix_hit_tokens_ratio": _reg(
        "gauge", "Fraction of admitted prompt tokens served from cached "
                 "prefix blocks"),
    "host_kv_blocks": _reg("gauge", "Host-DRAM KV tier capacity (blocks)"),
    "host_tier_blocks": _reg(
        "gauge", "Blocks currently demoted to the host-DRAM tier"),
    "swap_queue_depth": _reg("gauge", "Host-tier swap-ins in flight"),
    "swap_ins_total": _reg("counter", "Host-tier swap-ins started"),
    "swap_in_blocks_total": _reg(
        "counter", "Blocks restored from the host tier (H2D)"),
    "swap_out_blocks_total": _reg(
        "counter", "Blocks demoted to the host tier (D2H)"),
    "swap_in_ms_total": _reg(
        "counter", "Cumulative swap-in wall time (ms)"),
    "swap_failures_total": _reg(
        "counter", "Swap-ins failed cleanly (request-scoped)"),
    # -- KV chain digest (kvcache.KvDigest — fleet cache telemetry) ---------
    "kv_digest_version": _reg(
        "gauge", "Chain-digest content version (bumps on publish/evict/"
                 "demote/restore; resets with the store on rebuild — "
                 "compare with !=, any change means the consumer's "
                 "copy is stale)"),
    "kv_digest_loss_version": _reg(
        "gauge", "Chain-digest loss version (bumps only when a chain "
                 "can LOSE HBM residency: evict/demote/host-drop — "
                 "the affinity-freshness signal the router consults)"),
    "kv_publish_events_total": _reg(
        "counter", "Chain blocks published into the prefix index"),
    "kv_evict_events_total": _reg(
        "counter", "Chain blocks evicted out of the prefix index"),
    "kv_demote_events_total": _reg(
        "counter", "Chain blocks demoted HBM -> host tier (digest "
                   "view of the swap-out ledger)"),
    "kv_restore_events_total": _reg(
        "counter", "Chain blocks restored host tier -> HBM (digest "
                   "view of the swap-in ledger)"),
    "kv_host_evict_events_total": _reg(
        "counter", "Host-tier slabs lost to the tier's own LRU"),
    "kv_block_bytes": _reg(
        "gauge", "Pool bytes one KV block occupies (k+v+pos+scales, "
                 "draft twins included) — the duplicate-chain "
                 "accounting unit"),
    # -- scale-out serving (serve_mesh.py / router.py) ----------------------
    "kv_export_blocks_total": _reg(
        "counter", "Prefix blocks exported to peer replicas "
                   "(disaggregation handoff, prefill side)"),
    "kv_import_blocks_total": _reg(
        "counter", "Prefix blocks landed from peer replicas "
                   "(disaggregation handoff, decode side)"),
    "kv_export_events_total": _reg(
        "counter", "Prefix handoff exports that moved >= 1 block"),
    "kv_import_events_total": _reg(
        "counter", "Prefix handoff imports that landed >= 1 block"),
    "kv_handoff_aborted_total": _reg(
        "counter", "Prefix handoff imports that hit the wall timeout "
                   "and unwound cleanly (blocks freed, nothing "
                   "published)"),
    "kv_export_demoted_blocks_total": _reg(
        "counter", "Exported prefix blocks demoted/dropped at the "
                   "source after a handoff (demote-after-export: the "
                   "migration deduplicates fleet HBM)"),
    "serve_mesh_data": _reg(
        "gauge", "Serving-mesh row shards (data*fsdp axes; 1 off-mesh)"),
    "serve_mesh_tensor": _reg(
        "gauge", "Serving-mesh tensor shards (KV-head sharding; 1 "
                 "off-mesh)"),
    "replica_id": _reg(
        "gauge", "This server's replica index behind a ReplicaRouter "
                 "(-1 standalone)"),
    # -- chunked decode host boundary --------------------------------------
    "decode_chunk_size": _reg(
        "gauge", "Effective K of the most recent chunk dispatch"),
    "decode_dispatches_total": _reg(
        "counter", "Jitted decode chunk dispatches"),
    "host_syncs_total": _reg(
        "counter", "Device-to-host fetches the serving loop performed"),
    "state_uploads_total": _reg(
        "counter", "Host-to-device state-sync dispatches"),
    "host_uploads_total": _reg(
        "counter", "Host-to-device copies the serving loop made outside "
                   "a jitted call (a fused admission's packed vector, a "
                   "classic insert's operands); a host operand handed to "
                   "a dispatch's own call (a row sync's matrix) is not "
                   "one"),
    "host_syncs_per_token": _reg(
        "gauge", "Fetches per emitted token (trends to 1/K steady-state)"),
    # -- speculative serving ------------------------------------------------
    "spec_rounds_per_dispatch": _reg(
        "gauge", "Effective R of the most recent speculative dispatch"),
    "spec_dispatches_total": _reg(
        "counter", "Jitted speculative dispatches (R rounds each)"),
    "spec_host_syncs_per_token": _reg(
        "gauge", "Speculative-path fetches per emitted token"),
    "spec_window_acceptance_rate": _reg(
        "gauge", "Draft acceptance over the last 64 spec dispatches"),
    # -- fused prefill-decode scheduling ------------------------------------
    "prefill_budget": _reg(
        "gauge", "Prompt tokens a fused admission advances per dispatch"),
    "prefill_tokens_inflight": _reg(
        "gauge", "Prompt tokens of the in-flight admission still to "
                 "prefill"),
    "prefill_chunks_total": _reg(
        "counter", "Chunk dispatches that carried a prefill lane"),
    "fused_admissions_total": _reg(
        "counter", "Admissions routed through the fused prefill lane"),
    "prefill_ctx_slots_attended_total": _reg(
        "counter", "Slots of the prefilling row's view that fused prefill "
                   "attention did work for (live tiles for the latent "
                   "block, the whole view otherwise)"),
    "prefill_ctx_slots_view_total": _reg(
        "counter", "Slots of the prefilling row's view, summed over "
                   "dispatches that carried a prefill lane"),
    "prefill_blocks_written_total": _reg(
        "counter", "Whole blocks the fused prefill lane landed in the pool "
                   "(block form: one slab a block)"),
    "prefill_pairs_written_total": _reg(
        "counter", "(block, offset) token slots the whole-prompt and "
                   "suffix inserts landed in the pool (pair form)"),
    "fused_dispatches_total": _reg(
        "counter", "Chunk dispatches that carried a prompt chunk (the "
                   "denominator of the queued share; equals "
                   "prefill_chunks_total)"),
    "fused_dispatches_queued_total": _reg(
        "counter", "Of those, dispatches submitted while requests queued "
                   "for the prefill lane (they run the lane's K clamp)"),
    "fused_dispatches_merged_total": _reg(
        "counter", "Of those, dispatches whose first decode iteration rode "
                   "the prompt chunk's pass over the weights"),
    "fused_merged_rows_total": _reg(
        "counter", "Decoding rows that rode such a pass, summed over "
                   "those dispatches"),
    "first_sample_skipped_total": _reg(
        "counter", "Fused dispatches whose prompt did not complete: the "
                   "program read no head and drew nothing for the "
                   "admission (with greedy and drawn, adds up to "
                   "prefill_chunks_total)"),
    "first_sample_greedy_total": _reg(
        "counter", "Fused dispatches that completed a greedy request's "
                   "prompt: its first token was an argmax"),
    "first_sample_drawn_total": _reg(
        "counter", "Fused dispatches that completed a sampling request's "
                   "prompt: its first token took the warp and the draw"),
    # -- routed experts (ops/moe.py; zero on a configuration without) -------
    "moe_assignments_total": _reg(
        "counter", "(token, expert) pairs the router assigned"),
    "moe_experts_touched_total": _reg(
        "counter", "Distinct experts touched, summed over expert-layer "
                   "calls"),
    "moe_layer_calls_total": _reg(
        "counter", "Expert-layer calls (one a layer a forward)"),
    "moe_max_load_total": _reg(
        "counter", "Largest per-expert token count, summed over "
                   "expert-layer calls"),
    # -- window and full attention layers (models/afmoe.py; zero without) ----
    "attn_window_kv_steps_total": _reg(
        "counter", "Live grid steps of the paged decode kernel in window "
                   "attention layers, summed over rows, layers and "
                   "iterations"),
    "attn_full_kv_steps_total": _reg(
        "counter", "Live grid steps of the paged decode kernel in full "
                   "attention layers, summed over rows, layers and "
                   "iterations"),
    # -- learned sparse attention (models/dsa_moe.py; zero without) ----------
    "attn_selected_slots_total": _reg(
        "counter", "Slots the paged decode rows attended under their "
                   "learned key selection, summed over rows, layers and "
                   "iterations"),
    "attn_candidate_slots_total": _reg(
        "counter", "Live slots the paged decode rows' selection chose "
                   "from (decode rows x layers x context)"),
    "attn_select_dense_rows_total": _reg(
        "counter", "Paged decode rows (x layers) whose context was no "
                   "longer than the selection's k: every live key attended"),
    "attn_index_steps_run_total": _reg(
        "counter", "Live grid steps of the paged index-ranking kernel, "
                   "summed over rows, layers and iterations"),
    "attn_index_steps_table_total": _reg(
        "counter", "Grid steps the decoding rows' whole block tables would "
                   "take in the paged index-ranking kernel (rows x layers "
                   "x steps a table)"),
    "attn_select_tie_rows_total": _reg(
        "counter", "Paged decode rows (x layers) whose k-th index score was "
                   "shared by more candidates than the selection had room "
                   "for: the lower slots among the equals were taken"),
    # -- a multi-stream residual (ops/mhc.py; zero without) ------------------
    "hc_unconverged_total": _reg(
        "counter", "Tokens x mHC units whose Sinkhorn-normalised mixing "
                   "matrix ended with a row or column sum farther than 1e-3 "
                   "from 1"),
    "hc_units_total": _reg(
        "counter", "Tokens x mHC units counted (two units a layer a token)"),
    # -- recurrent state layers (models/sambay.py, models/falcon_h1.py; zero
    # without) ---------------------------------------------------------------
    "ssm_snapshots_taken_total": _reg(
        "counter", "Recurrent-state snapshots copied out at a block "
                   "boundary of a prompt and hung on its radix node"),
    "ssm_snapshots_restored_total": _reg(
        "counter", "Prefix hits that resumed from a state snapshot"),
    "ssm_snapshots_evicted_total": _reg(
        "counter", "State snapshots taken from their radix node for the "
                   "snapshot pool's room (the node keeps its block)"),
    "ssm_match_tokens_cut_total": _reg(
        "counter", "Cached prompt tokens a prefix match gave up because "
                   "no state snapshot stood behind them"),
    "ssm_snapshots_in_use": _reg(
        "gauge", "State snapshots hung on radix nodes"),
    "ssm_state_bytes_per_slot": _reg(
        "gauge", "Bytes of one slot's recurrent state over all its layers "
                 "(what one state snapshot holds too)"),
    "ssm_snapshot_bytes": _reg(
        "gauge", "Bytes of the state snapshot pool under the radix store"),
    "decode_stall_ms_total": _reg(
        "counter", "Wall time classic whole-prompt admissions stalled "
                   "decoding rows (ms)"),
    # -- fault injection -----------------------------------------------------
    "faults_injected_total": _reg("counter", "Injected faults raised"),
    "fault_delays_total": _reg("counter", "Injected delays served"),
    "fault_nans_armed_total": _reg(
        "counter", "Non-finite poisons armed by the injector"),
    # -- server layer --------------------------------------------------------
    "server_recoveries_total": _reg(
        "counter", "Batcher rebuild+replay crash recoveries"),
    "watchdog_stalls_total": _reg(
        "counter", "Serving-loop heartbeat stalls detected"),
    "watchdog_stalled": _reg("gauge", "Watchdog currently tripped (0/1)"),
    "watchdog_last_step_age_seconds": _reg(
        "gauge", "Seconds since the serving loop's last heartbeat"),
    "quarantine_rebuilds_total": _reg(
        "counter", "Batcher rebuilds onto a feature fallback"),
    "probe_rebuilds_total": _reg(
        "counter", "Batcher rebuilds re-enabling a probed feature"),
    "nonfinite_requests_failed_total": _reg(
        "counter", "Requests failed with HTTP 500 by the non-finite "
                   "guard"),
    "draining": _reg("gauge", "Server in drain mode (0/1)"),
    "ttft_ms_ewma": _reg(
        "gauge", "EWMA time-to-first-token (ms, alpha 0.2; see the "
                 "ttft_ms histogram for the distribution)"),
    "itl_ms_ewma": _reg(
        "gauge", "EWMA inter-token latency (ms, alpha 0.2; the "
                 "per-replica degradation signal the router's health "
                 "sentinel z-scores; canary probes excluded)"),
    "canary_requests_total": _reg(
        "counter", "Synthetic canary-class probe requests served "
                   "(reserved class: excluded from SLO attainment, "
                   "goodput, latency histograms/EWMAs and the "
                   "brownout ladder's inputs)"),
    "decision_events_total": _reg(
        "counter", "Control-plane decisions recorded in the audit log "
                   "(brownout rung moves, recoveries, quarantines, "
                   "probes, sheds, drains — GET /debug/decisions)"),
    # -- request outcomes / SLO ---------------------------------------------
    "requests_finished_total": _reg(
        "counter", "Requests that delivered a complete generation"),
    "requests_failed_total": _reg(
        "counter", "Requests that ended in failure or timeout"),
    "requests_cancelled_total": _reg(
        "counter", "Requests cancelled (client disconnect or cancel)"),
    "slo_ttft_ms": _reg(
        "gauge", "Configured TTFT SLO deadline (ms; 0 = unset, "
                 "dimension always passes)"),
    "slo_itl_ms": _reg(
        "gauge", "Configured inter-token-latency SLO deadline (ms; "
                 "0 = unset)"),
    "requests_slo_ok_total": _reg(
        "counter", "Finished requests that met every configured SLO"),
    "goodput_tokens_total": _reg(
        "counter", "Tokens from requests that met every configured SLO "
                   "(the controller objective)"),
    "slo_ttft_attainment": _reg(
        "gauge", "Fraction of recent requests meeting the TTFT SLO "
                 "(window 256)"),
    "slo_itl_attainment": _reg(
        "gauge", "Fraction of recent requests meeting the ITL SLO "
                 "(window 256)"),
    "slo_attainment": _reg(
        "gauge", "Fraction of recent requests meeting every configured "
                 "SLO (window 256)"),
    # -- jit-cache observability ----------------------------------------------
    "compiles_total": _reg(
        "counter", "Backend jit compiles observed (cache misses; see "
                   "the compile_ms histogram and "
                   "program_compiles_total)"),
    "program_compiles_total": _reg(
        "counter", "Backend jit compiles attributed to each serving "
                   "program (per program)"),
    # -- serving-loop phases (the measured host share of a step) -------------
    "loop_phase_ms_total": _reg(
        "counter", "Serving-loop thread time between dispatch records, "
                   "per phase (ms; the phases tile the gaps, idle = "
                   "blocked on an empty inbox; folded in at each "
                   "dispatch record)"),
    "loop_gap_ms_total": _reg(
        "counter", "Summed gap_ms of the dispatch records: end of one "
                   "record to the start of the next on the loop "
                   "thread's clock (ms; idle included — subtract "
                   "loop_phase_ms_total{phase=\"idle\"} for the host's "
                   "share of a step)"),
    "loop_gap_cpu_ms_total": _reg(
        "counter", "Loop-thread CPU time (time.thread_time) over the "
                   "same gaps (ms; gap - idle - cpu = runnable or "
                   "blocked but not running: the GIL, a descheduled "
                   "core, a profiler)"),
    "loop_span_ms_total": _reg(
        "counter", "Loop-thread time inside the child spans of the loop "
                   "phases and of the dispatches, per span (ms; "
                   "obs.LOOP_SPANS; a nested span's time is also in its "
                   "parent's; folded in at each dispatch record)"),
    "loop_span_total": _reg(
        "counter", "Child spans closed, per span"),
    "dispatch_submit_ms_total": _reg(
        "counter", "Summed submit_ms of the dispatch records: "
                   "dispatch_begin to the return of the jitted call, "
                   "the host's enqueue (ms; inside wall_ms)"),
    "admit_blocked_total": _reg(
        "counter", "Admission passes that left the queue's head "
                   "queued, per reason (lane|capacity|slot|restoring)"),
    "jit_cache_entries": _reg(
        "gauge", "Live jit-cache entries per registered serving "
                 "program (a runaway series here is a bucketing bug "
                 "re-specializing a program per request)"),
    # -- overload control (overload.py) --------------------------------------
    "overload_rung": _reg(
        "gauge", "Brownout-ladder rung (0=normal 1=elevated "
                 "2=brownout-1 3=brownout-2 4=shed)"),
    "overload_transitions_total": _reg(
        "counter", "Brownout-ladder rung transitions (both directions)"),
    "overload_sheds_total": _reg(
        "counter", "Queued batch-class requests shed at the shed rung "
                   "(each got a clean 503 + Retry-After)"),
    "overload_refused_backlog_total": _reg(
        "counter", "Admissions refused by the queue-depth backstop "
                   "(503 + Retry-After)"),
    "overload_refused_deadline_total": _reg(
        "counter", "Admissions refused because the TTFT lower-bound "
                   "estimate provably misses the request's timeout_s"),
    "overload_refused_batch_total": _reg(
        "counter", "Batch-class admissions refused while the ladder "
                   "suspends the class (brownout-2 and above)"),
    "queued_interactive": _reg(
        "gauge", "Interactive-class requests waiting pre-admission"),
    "queued_batch": _reg(
        "gauge", "Batch-class requests waiting pre-admission"),
    "prefill_tokens_per_s_ewma": _reg(
        "gauge", "Observed prefill throughput EWMA (tokens/s; the "
                 "admission cost model's denominator)"),
    "decode_tokens_per_s_ewma": _reg(
        "gauge", "Observed decode throughput EWMA (tokens/s)"),
    "overload_ttft_estimate_ms": _reg(
        "gauge", "Most recent admission-time TTFT lower-bound estimate "
                 "(ms)"),
    "overload_batch_max_new_cap": _reg(
        "gauge", "Current brownout cap on batch-class max_new_tokens "
                 "(0 = uncapped)"),
    "slo_interactive_attainment": _reg(
        "gauge", "Interactive-class SLO attainment over the ladder's "
                 "recent signal window"),
    "slo_batch_attainment": _reg(
        "gauge", "Batch-class SLO attainment over the ladder's recent "
                 "signal window"),
}

# Generated families: per-site injection counters, per-feature
# degradation state.
for _site in SITES:
    METRICS[f"faults_injected_{_site}_total"] = _reg(
        "counter", f"Injected faults raised at site {_site}")
for _f in FEATURES:
    METRICS[f"feature_quarantined_{_f}"] = _reg(
        "gauge", f"{_f} currently quarantined onto its fallback (0/1)")
    METRICS[f"feature_failures_{_f}_total"] = _reg(
        "counter", f"Failures attributed to {_f}")
    METRICS[f"feature_quarantines_{_f}_total"] = _reg(
        "counter", f"Times {_f} entered quarantine")


def metric_meta(name: str) -> Optional[Tuple[str, str]]:
    """(type, help) for a scalar metric name (without the ``llm_``
    prefix), or None for an unregistered name — the exposition then
    falls back to the legacy heuristic and SAYS SO in the HELP line,
    which the /metrics parse test treats as a failure."""
    return METRICS.get(name)


# ---------------------------------------------------------------------------
# Compile attribution
# ---------------------------------------------------------------------------

# Compile attribution: serving.py names the program it is about to
# dispatch (thread-local — each serving loop owns one batcher), and
# the process-wide jax.monitoring listener books any backend compile
# that fires during the call onto that program's Observability sink.
# Compiles outside an attributed dispatch (e.g. bench warmups on the
# main thread) are deliberately ignored: there is no sink to misfeed.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_attr = threading.local()
_listener_state = {"installed": False}
_listener_lock = threading.Lock()


def attribute_compiles(sink: "Observability", program: str) -> None:
    """Point this thread's compile events at ``sink`` as ``program``
    (two attribute writes — cheap enough for every dispatch)."""
    _compile_attr.sink = sink
    _compile_attr.program = program


def _compile_listener(event: str, duration_secs: float, **kw) -> None:
    if event != _COMPILE_EVENT:
        return
    sink = getattr(_compile_attr, "sink", None)
    if sink is None:
        return
    try:
        sink.record_compile(
            getattr(_compile_attr, "program", "unknown"),
            duration_secs * 1000.0,
        )
    except Exception:
        pass  # a metrics sink must never break a compile


def install_compile_listener() -> bool:
    """Register the process-wide compile listener (idempotent; lazy
    jax import keeps this module importable without jax)."""
    with _listener_lock:
        if _listener_state["installed"]:
            return True
        try:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(
                _compile_listener
            )
        except Exception:
            return False
        _listener_state["installed"] = True
        return True


def _annotate(name: str, **args) -> Any:
    """An ENTERED ``jax.profiler.TraceAnnotation`` (an inactive TraceMe
    — a few hundred ns — unless a profiler session is running), or
    None where jax is absent; the caller ``__exit__``s it."""
    cls = _annotation_cls()
    if cls is None:
        return None
    ann = cls(name, **args)
    ann.__enter__()
    return ann


@functools.lru_cache(maxsize=None)
def _annotation_cls() -> Any:
    try:  # lazy: this module stays importable without jax
        from jax.profiler import TraceAnnotation
    except Exception:
        return None
    return TraceAnnotation


# ---------------------------------------------------------------------------
# Decision audit log + anomaly-detection building block
# ---------------------------------------------------------------------------

class DecisionLog:
    """Bounded ring of structured control-plane decision events.

    One event per decision the control plane took — route / reroute /
    handoff (router.py), brownout rung move / recovery / quarantine /
    probe / shed / drain (server.py), canary result / anomaly /
    verdict flip (the health sentinel) — each a dict carrying ``seq``
    (monotonic, survives ring eviction so consumers can detect gaps),
    ``t_ms`` (relative to the log's epoch), ``unix_s`` (wall clock,
    for cross-process joins), ``kind``, the external ``request_id``
    where one exists (the join key back to request timelines), and
    whatever fields the decision point attached (candidate sets,
    scores, hit depths, errors).

    Thread-safe under its own leaf lock (registered in
    analysis/lockcheck.py): decision points record from serving-loop /
    poller / handler threads while ``/debug/decisions`` snapshots.
    The lock is never held while calling out."""

    def __init__(self, ring: int = 512, clock=time.monotonic):
        self._lock = threading.Lock()
        self._clock = clock
        self._t0 = clock()
        self._ring: "deque[Dict[str, Any]]" = deque(maxlen=ring)
        self._seq = 0
        self.counts: Dict[str, int] = {}

    def record(self, kind: str, request_id: Optional[str] = None,
               **fields) -> int:
        """Append one decision event; returns its seq number."""
        ev: Dict[str, Any] = {
            "seq": -1,
            "t_ms": round((self._clock() - self._t0) * 1000.0, 3),
            "unix_s": round(time.time(), 3),
            "kind": kind,
        }
        if request_id:
            ev["request_id"] = request_id
        for k, v in fields.items():
            if v is not None:
                ev[k] = v
        with self._lock:
            ev["seq"] = self._seq
            self._seq += 1
            self._ring.append(ev)
            self.counts[kind] = self.counts.get(kind, 0) + 1
            return ev["seq"]

    def total(self) -> int:
        """Events ever recorded (ring evictions included)."""
        with self._lock:
            return self._seq

    def counts_snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self.counts)

    def json(self, n: int = 128, kind: Optional[str] = None,
             request_id: Optional[str] = None) -> Dict[str, Any]:
        """The ``GET /debug/decisions[?n=&kind=&request_id=]`` payload:
        the most recent ``n`` events after filtering (events the ring
        already evicted are gone — ``events_total`` vs ``len`` tells a
        consumer how much history survives)."""
        with self._lock:
            evs = list(self._ring)
            total = self._seq
            counts = dict(self.counts)
            ring = self._ring.maxlen
        if kind is not None:
            evs = [e for e in evs if e["kind"] == kind]
        if request_id is not None:
            evs = [e for e in evs if e.get("request_id") == request_id]
        evs = evs[-n:] if n > 0 else []
        return {
            "decisions": [dict(e) for e in evs],
            "events_total": total,
            "counts": counts,
            "ring": ring,
        }

    def for_request(self, request_id: str,
                    n: int = 64) -> List[Dict[str, Any]]:
        """The decision events carrying ``request_id`` — the join the
        fleet request lookup attaches to a timeline."""
        return self.json(n=n, request_id=request_id)["decisions"]


class EwmaDetector:
    """Online EWMA mean/variance with z-score anomaly scoring.

    ``update(x)`` returns the z-score of ``x`` against the statistics
    BEFORE the update (so a spike scores against the healthy baseline,
    not against itself), or None during warmup (< ``min_samples``
    observations — no baseline, no verdict).  The variance follows the
    standard exponentially-weighted recurrence; the divisor is floored
    (relative to the mean, and absolutely by ``floor``) so a
    near-constant healthy signal does not turn measurement noise into
    infinite z.  ``floor`` must be set in the SIGNAL'S OWN UNITS: for
    millisecond latencies a floor of ~1 ms says "a deviation under a
    millisecond is never an anomaly, whatever the variance" — without
    it, a 0.05 ms queue-wait baseline turns one harmless 3 ms blip
    into z≈500 and a false critical verdict.

    NOT itself synchronized: the health sentinel mutates it under its
    own lock."""

    def __init__(self, alpha: float = 0.2, min_samples: int = 5,
                 floor: float = 1e-6):
        if not 0.0 < alpha <= 1.0:
            raise ValueError(f"alpha must be in (0, 1], got {alpha}")
        self.alpha = float(alpha)
        self.min_samples = int(min_samples)
        self.floor = float(floor)
        self.n = 0
        self.mean = 0.0
        self.var = 0.0

    def update(self, x: float) -> Optional[float]:
        x = float(x)
        z: Optional[float] = None
        if self.n >= self.min_samples:
            sd = math.sqrt(max(self.var, 0.0))
            z = (x - self.mean) / max(
                sd, abs(self.mean) * 0.05, self.floor
            )
        if self.n == 0:
            self.mean = x
        else:
            a = self.alpha
            d = x - self.mean
            self.mean += a * d
            self.var = (1.0 - a) * (self.var + a * d * d)
        self.n += 1
        return z


# ---------------------------------------------------------------------------
# Timeline / dispatch records
# ---------------------------------------------------------------------------

# Request lifecycle states (the PR 5/6 admission state machine) plus
# terminal outcomes.  ``received`` is the server's part before the
# batcher sees the request (POST accepted -> ``submit``: inbox and
# class queue), opened already closed by ``request_queued``.
STATES = ("received", "queued", "prefilling", "restoring", "decoding")
OUTCOMES = ("finished", "failed", "cancelled")

_MAX_SPANS = 64            # per timeline (replays append; bound them)
_MAX_SPAN_DISPATCHES = 512  # dispatch links per span
_MAX_RIDS = 8              # batcher incarnations indexed per timeline
_PHASE_RING = 4096         # loop-phase intervals kept for trace_json


class _Span:
    __slots__ = ("state", "t0", "t1", "dispatches", "dropped", "note")

    def __init__(self, state: str, t0: float, note: Optional[str] = None):
        self.state = state
        self.t0 = t0
        self.t1: Optional[float] = None
        self.dispatches: List[int] = []
        self.dropped = 0  # dispatch links past _MAX_SPAN_DISPATCHES
        self.note = note


class _Timeline:
    __slots__ = (
        "request_id", "rids", "prompt_tokens", "created", "spans",
        "outcome", "error", "route", "kv",
    )

    def __init__(self, request_id: str, rid: int, prompt_tokens: int,
                 t: float):
        self.request_id = request_id
        self.rids: List[int] = [rid]
        self.prompt_tokens = prompt_tokens
        self.created = t
        self.spans: List[_Span] = []
        self.outcome: Optional[str] = None
        self.error: Optional[str] = None
        # Routing decision (ReplicaRouter via the X-Routed-By header):
        # which replica/policy served this request — shown by
        # /debug/requests/<id> next to the spans it annotates.
        self.route: Optional[str] = None
        # Per-session KV accounting (request_kv): blocks held, prefix
        # hit depth in tokens, swap bytes moved, evictions suffered.
        self.kv: Dict[str, Any] = {}


class _LoopSpan:
    """One ``Observability.loop_span``: a context manager the loop thread
    enters once.  ``t0`` is None while it records nothing (not under its
    parent) and once it is closed."""

    __slots__ = ("obs", "name", "rid", "t0", "parent", "seq", "ann")

    def __init__(self, obs: "Observability", name: str,
                 rid: Optional[int]):
        self.obs = obs
        self.name = name
        self.rid = rid
        self.t0: Optional[float] = None

    def __enter__(self) -> "_LoopSpan":
        obs = self.obs
        stack = obs._sp_open
        if stack:
            cause = stack[-1].name
            if self.rid is None:
                self.rid = stack[-1].rid
        elif obs._ph_resume is not None:
            cause = "dispatch"
        else:
            cause = obs._ph_name
        if cause != LOOP_SPANS[self.name]:
            return self
        self.parent = cause
        self.seq = obs._ph_seq
        args = {"seq": self.seq}
        if self.rid is not None:
            args["rid"] = int(self.rid)
        self.ann = _annotate("llm.span." + self.name, **args)
        stack.append(self)
        self.t0 = obs._clock()
        return self

    def __exit__(self, *exc) -> None:
        if self.t0 is not None:
            self.obs._sp_close(self, self.obs._clock())


class Observability:
    """The serving stack's shared observability sink (module docstring).

    One instance is shared by a ``ContinuousBatcher`` and its
    ``LLMServer`` — and survives crash-recovery/quarantine rebuilds the
    same way the fault injector does (it rides the captured ctor
    kwargs), so timelines and histograms span batcher incarnations.

    ``ring`` bounds the dispatch ring, ``max_timelines`` the request-
    timeline LRU, ``max_events`` the annotation ring.  ``clock`` is
    injectable for tests."""

    def __init__(
        self,
        slo_ttft_ms: Optional[float] = None,
        slo_itl_ms: Optional[float] = None,
        ring: int = 512,
        max_timelines: int = 1024,
        max_events: int = 256,
        slo_window: int = 256,
        decision_ring: int = 512,
        max_snapshots: int = 128,
        clock=time.monotonic,
    ):
        self.slo_ttft_ms = (
            float(slo_ttft_ms) if slo_ttft_ms else None
        )
        self.slo_itl_ms = float(slo_itl_ms) if slo_itl_ms else None
        self._clock = clock
        self.t0 = clock()
        # Wall-clock anchor captured at the SAME instant as the
        # monotonic t0: the fleet-merge (router /debug/trace) shifts
        # each replica's relative timestamps into a common frame via
        # the difference of these anchors (clock-offset normalization).
        self.t0_unix = time.time()
        self._lock = threading.Lock()
        self._seq = 0
        self.dispatches: "deque[Dict[str, Any]]" = deque(maxlen=ring)
        self.events: "deque[Dict[str, Any]]" = deque(maxlen=max_events)
        self._max_timelines = int(max_timelines)
        self._timelines: "OrderedDict[str, _Timeline]" = OrderedDict()
        self._by_rid: Dict[int, _Timeline] = {}
        # Decision audit log (its own leaf lock — never nested with
        # self._lock) + the flight recorder's periodic metric-snapshot
        # ring (server.py feeds it every flight_interval_s; the
        # /debug/bundle artifact exports it).  Both survive batcher
        # rebuilds with the rest of this instance.
        self.decisions = DecisionLog(ring=decision_ring, clock=clock)
        self.metric_snapshots: "deque[Dict[str, Any]]" = deque(
            maxlen=max_snapshots
        )
        # Jit-cache observability: compile spans (bounded ring, a
        # trace track of their own) + per-program counters, fed by the
        # process-wide jax.monitoring listener via record_compile.
        self.compiles: "deque[Dict[str, Any]]" = deque(maxlen=max_events)
        self.compiles_total = 0
        self.compiles_by_program: Dict[str, int] = {}
        # Optional dispatch-record sink (overload.py's throughput
        # EWMAs feed off it).  Called OUTSIDE self._lock with the
        # already-built record dict — the sink takes its own lock, and
        # calling out under ours would order the two locks.  Settable
        # after construction (the server wires its controller here).
        self.on_dispatch: Optional[Any] = None
        self.hist: Dict[str, Histogram] = {
            name: Histogram(
                name, help_text,
                buckets=HISTOGRAM_BUCKETS.get(name, DEFAULT_BUCKETS_MS),
            )
            for name, help_text in HISTOGRAMS.items()
            if name not in LABELED_HISTOGRAMS
        }
        # Per-kind dispatch_ms series (one Histogram per dispatch
        # kind, created lazily under the lock on first dispatch).
        self.hist_dispatch: Dict[str, Histogram] = {}
        # Outcome / SLO accounting.
        self.requests_finished_total = 0
        self.requests_failed_total = 0
        self.requests_cancelled_total = 0
        self.requests_slo_ok_total = 0
        self.goodput_tokens_total = 0
        self._slo_window: "deque[Tuple[bool, bool, bool]]" = deque(
            maxlen=slo_window
        )
        # Serving-loop phases (loop_phase / dispatch_begin /
        # record_dispatch).  ONE writer — the loop thread — so none of
        # this takes the lock; only the *_total folds below do, inside
        # record_dispatch's existing critical section.  Times are
        # seconds on ``clock``.
        self.loop_phases: "deque[Tuple[str, float, float]]" = deque(
            maxlen=_PHASE_RING
        )
        self._ph_name: Optional[str] = None   # open phase; None inside a
        self._ph_t = 0.0                      # dispatch; and its start
        self._ph_ann: Any = None              # the open TraceAnnotation
        self._ph_resume: Optional[str] = None  # set while a dispatch is open
        self._ph_acc: Dict[str, float] = {}   # phase -> s since last record
        self._ph_prev_end: Optional[float] = None  # last record's end
        self._ph_cpu0 = 0.0                   # thread_time() at that end
        self._ph_cpu1 = 0.0                   # ... at dispatch_begin
        self._ph_tid = 0                      # thread that wrote that end
        self._ph_compiles0 = 0                # compiles_total at that end
        self.host_uploads_total = 0           # count_upload; same writer
        self._ph_uploads0 = 0                 # ... at that end
        self._ph_seq = 0                      # the next record's ring number
        self.loop_phase_ms_total: Dict[str, float] = {}
        self.loop_gap_ms_total = 0.0
        self.loop_gap_cpu_ms_total = 0.0
        # Child spans (loop_span): the same single writer.  Ring entries
        # are (name, start, end, parent, rid, seq), seconds on ``clock``.
        self.loop_spans: "deque[Tuple[Any, ...]]" = deque(
            maxlen=_PHASE_RING
        )
        self._sp_open: List[_LoopSpan] = []   # innermost last
        self._sp_acc: Dict[str, float] = {}   # span -> s since last record
        self._sp_n: Dict[str, int] = {}       # ... and how many closed
        self.loop_span_ms_total: Dict[str, float] = {}
        self.loop_span_total: Dict[str, int] = {}
        self.dispatch_submit_ms_total = 0.0
        self.admit_blocked_total: Dict[str, int] = {}

    # -- internal helpers ---------------------------------------------------

    def _now_ms(self) -> float:
        return (self._clock() - self.t0) * 1000.0

    def _evict_locked(self) -> None:
        while len(self._timelines) > self._max_timelines:
            # Prefer the oldest TERMINAL timeline: evicting a live one
            # mid-flight would make its later request_end a no-op (the
            # finished counter undercounts and /debug 404s for a
            # request still being served) — and the longest-lived
            # requests are exactly the ones worth debugging.  Only
            # when every entry is live (a pathological burst) does the
            # oldest go regardless, keeping the bound hard.
            key = next(
                (k for k, tl in self._timelines.items()
                 if tl.outcome is not None),
                next(iter(self._timelines)),
            )
            tl = self._timelines.pop(key)
            for rid in tl.rids:
                if self._by_rid.get(rid) is tl:
                    del self._by_rid[rid]

    def _current_span(self, tl: _Timeline) -> Optional[_Span]:
        return tl.spans[-1] if tl.spans else None

    def _begin_span_locked(self, tl: _Timeline, state: str,
                           note: Optional[str] = None,
                           t: Optional[float] = None) -> None:
        if t is None:
            t = self._now_ms()
        cur = self._current_span(tl)
        if cur is not None and cur.t1 is None:
            cur.t1 = t
            if cur.state == "queued" and state in (
                "prefilling", "restoring"
            ):
                self.hist["queue_wait_ms"].observe(t - cur.t0)
        if len(tl.spans) >= _MAX_SPANS:
            return
        tl.spans.append(_Span(state, t, note))

    # -- request lifecycle (called by the batcher / server) -----------------

    def request_queued(self, rid: int, prompt_tokens: int,
                       received_at: Optional[float] = None) -> None:
        """A request entered the batcher queue (``submit``); creates a
        timeline under the provisional id ``r<rid>`` until the server
        binds the external one.  ``received_at`` (seconds on this
        instance's clock: the server's POST-arrival stamp, where its
        TTFT starts) opens the timeline with a closed ``received`` span
        up to now — the wait in the server's inbox and class queue —
        so the spans start where the client's clock does; ``queued``
        keeps its meaning (batcher queue -> prefill begins)."""
        with self._lock:
            now = self._clock()
            tl = _Timeline(f"r{rid}", rid, prompt_tokens, now)
            self._timelines[tl.request_id] = tl
            self._by_rid[rid] = tl
            t = (now - self.t0) * 1000.0
            if received_at is not None:
                sp = _Span("received", (received_at - self.t0) * 1000.0)
                sp.t1 = t
                tl.spans.append(sp)
            self._begin_span_locked(tl, "queued", t=t)
            self._evict_locked()

    def bind(self, rid: int, request_id: str,
             replay: bool = False) -> None:
        """Attach the server's external request id to ``rid``'s
        timeline.  On a crash-recovery replay (``replay=True``, passed
        by the server's rebuild-and-replay path) the external id
        already owns a timeline: the fresh rid (and its new ``queued``
        span) folds into it, so ``/debug/requests/<id>`` shows the
        whole story across batcher incarnations.

        A NON-replay bind that collides with an existing timeline is a
        client reusing an ``X-Request-Id`` (proxies and retry layers
        do): the new request keeps its provisional ``r<rid>`` timeline
        instead of folding — merging two unrelated requests would
        clobber the live timeline's outcome and grow the merged record
        without bound on every reuse."""
        with self._lock:
            tl_rid = self._by_rid.get(rid)
            existing = self._timelines.get(request_id)
            if existing is None:
                if tl_rid is None:
                    return
                self._timelines.pop(tl_rid.request_id, None)
                tl_rid.request_id = request_id
                self._timelines[request_id] = tl_rid
            elif existing is not tl_rid and replay:
                if tl_rid is not None:
                    self._timelines.pop(tl_rid.request_id, None)
                    room = max(0, _MAX_SPANS - len(existing.spans))
                    for sp in tl_rid.spans[:room]:
                        sp.note = sp.note or "replay"
                        existing.spans.append(sp)
                existing.rids.append(rid)
                # Bound the per-timeline rid list (and the _by_rid
                # index entries it keeps alive): only the most recent
                # incarnations stay addressable by bare rid.
                while len(existing.rids) > _MAX_RIDS:
                    old = existing.rids.pop(0)
                    if self._by_rid.get(old) is existing:
                        del self._by_rid[old]
                existing.outcome = None
                existing.error = None
                self._by_rid[rid] = existing
                self._timelines.move_to_end(request_id)

    def set_route(self, request_id: str, route: str) -> None:
        """Record a ReplicaRouter's decision on the request's timeline
        (called by the server after ``bind`` when the POST carried an
        ``X-Routed-By`` header) AND drop an instant event into the
        annotation ring, so the decision shows both in
        ``/debug/requests/<id>`` and on the trace."""
        with self._lock:
            tl = self._timelines.get(request_id)
            if tl is not None:
                tl.route = route
            self.events.append({
                "t_ms": round(self._now_ms(), 3), "name": "routed",
                "fields": {"request_id": request_id, "via": route},
            })

    def begin_span(self, rid: int, state: str,
                   note: Optional[str] = None) -> None:
        """Transition ``rid`` into a lifecycle state (ends the current
        span; queued->prefilling/restoring edges feed the queue-wait
        histogram)."""
        with self._lock:
            tl = self._by_rid.get(rid)
            if tl is not None:
                self._begin_span_locked(tl, state, note)

    def request_end(self, rid: int, outcome: str,
                    error: Optional[str] = None) -> None:
        """Terminal transition (finished / failed / cancelled)."""
        with self._lock:
            tl = self._by_rid.get(rid)
            if tl is None:
                return
            t = self._now_ms()
            cur = self._current_span(tl)
            if cur is not None and cur.t1 is None:
                cur.t1 = t
            tl.outcome = outcome
            tl.error = error
            if outcome == "finished":
                self.requests_finished_total += 1
            elif outcome == "cancelled":
                self.requests_cancelled_total += 1
            else:
                self.requests_failed_total += 1

    def request_rejected(self, request_id: str, error: str) -> None:
        """A request the server answered (504/503) without it ever
        reaching the batcher — the overload signature: it expired in
        the server inbox, so no rid exists and ``request_queued`` never
        fired.  Record a minimal terminal timeline under the external
        id and count the failure, so ``/debug/requests/<id>`` and
        ``requests_failed_total`` agree with the error the client saw
        (without this, attainment drops while the failure counter
        stays flat — the two overload signals would contradict)."""
        with self._lock:
            # The failure COUNTS regardless of id reuse — every 504 the
            # client saw is a failure, or attainment drops while the
            # counter stays flat (the divergence this method removes).
            self.requests_failed_total += 1
            if request_id in self._timelines:
                return  # id reuse: keep the existing richer record
            tl = _Timeline(request_id, rid=-1, prompt_tokens=0,
                           t=self._clock())
            tl.rids = []  # no batcher incarnation ever existed
            t = self._now_ms()
            sp = _Span("queued", t)
            sp.t1 = t
            tl.spans.append(sp)
            tl.outcome = "failed"
            tl.error = error
            self._timelines[request_id] = tl
            self._evict_locked()

    # -- serving-loop phases -------------------------------------------------

    def _ph_close(self, t: float) -> None:
        """End the open phase (and whatever annotation is open) at
        ``t``: one accumulator add and one ring entry."""
        cur = self._ph_name
        if cur is not None:
            self._ph_acc[cur] = (
                self._ph_acc.get(cur, 0.0) + (t - self._ph_t)
            )
            self.loop_phases.append((cur, self._ph_t, t))
        ann = self._ph_ann
        if ann is not None:
            self._ph_ann = None
            ann.__exit__(None, None, None)

    def _ph_open(self, name: Optional[str], t: float) -> None:
        self._ph_name = name
        self._ph_t = t
        if name is not None:
            self._ph_ann = _annotate("llm.loop." + name)

    def _ph_abandon_dispatch(self) -> None:
        """A dispatch began and never recorded (it raised): its time
        goes back to the phase it interrupted (its spans' with it)."""
        self._sp_close(None, self._ph_t)
        for name in [n for n in self._sp_acc if n.startswith("dispatch.")]:
            del self._sp_acc[name], self._sp_n[name]
        self._ph_close(self._ph_t)  # the llm.dispatch annotation
        resume, self._ph_resume = self._ph_resume, None
        self._ph_open(resume, self._ph_t)

    def loop_phase(self, name: str) -> None:
        """The serving-loop thread says "I am in phase ``name`` from
        now"; the previous phase ends at the same instant, so phases
        tile the thread's time between dispatch records with no holes
        (``record_dispatch`` turns them into ``gap_ms`` / ``host_ms``).
        Also opens ``llm.loop.<name>`` as a ``jax.profiler``
        annotation, so a profiler session holds the phases on the
        device trace's clock.  Loop thread only — the batcher's single
        owner; takes no lock, always on: one clock read, one ring
        entry and one inactive annotation per change of phase."""
        if name not in LOOP_PHASES:
            raise ValueError(
                f"unknown loop phase {name!r}; have {sorted(LOOP_PHASES)}"
            )
        if self._ph_resume is not None:
            self._ph_abandon_dispatch()
        if name == self._ph_name:
            return
        t = self._clock()
        self._ph_close(t)
        self._ph_open(name, t)

    def loop_span(self, name: str, rid: Optional[int] = None) -> _LoopSpan:
        """A child span of the open loop phase, of the open dispatch
        (``dispatch_begin`` to the record) or of the open span: ``with
        obs.loop_span("admit.match", rid=req.rid): ...``.  ``name`` is
        one of :data:`LOOP_SPANS`, which also names the parent it is
        recorded under; opened elsewhere it records nothing and its time
        stays with what is open.  A span ends at its exit or at the next
        ``dispatch_begin`` / record, whichever comes first, so it never
        reaches across a dispatch.  It goes into the ``loop_spans`` ring
        with its cause, ``rid`` (a nested span takes its parent's) and
        the ring number of the record its gap leads to; into that
        record's ``span_ms`` / ``span_n`` (a nested span's time is also
        in its parent's; ``host_ms`` is untouched); and onto the
        profiler's clock as ``llm.span.<name>`` with ``seq`` and
        ``rid``.  Loop thread only, no lock, always on: two clock reads,
        two dict adds, a ring entry and an inactive annotation."""
        if name not in LOOP_SPANS:
            raise ValueError(
                f"unknown loop span {name!r}; have {sorted(LOOP_SPANS)}"
            )
        return _LoopSpan(self, name, rid)

    def _sp_close(self, sp: Optional[_LoopSpan], t: float) -> None:
        """End ``sp`` and every span opened inside it at ``t`` (None:
        every open span)."""
        stack = self._sp_open
        while stack:
            cur = stack.pop()
            dur = max(0.0, t - cur.t0)
            self._sp_acc[cur.name] = self._sp_acc.get(cur.name, 0.0) + dur
            self._sp_n[cur.name] = self._sp_n.get(cur.name, 0) + 1
            self.loop_spans.append(
                (cur.name, cur.t0, cur.t0 + dur, cur.parent, cur.rid,
                 cur.seq)
            )
            cur.t0 = None
            if cur.ann is not None:
                cur.ann.__exit__(None, None, None)
            if cur is sp:
                break

    def admit_blocked(self, reason: str) -> None:
        """The admission pass left the queue's head queued for
        ``reason`` (:data:`ADMIT_BLOCKED`).  Loop thread only."""
        if reason not in ADMIT_BLOCKED:
            raise ValueError(
                f"unknown blocked reason {reason!r}; have {ADMIT_BLOCKED}"
            )
        self.admit_blocked_total[reason] = (
            self.admit_blocked_total.get(reason, 0) + 1
        )

    def dispatch_begin(self, kind: str, program: Optional[str] = None,
                       k: int = 1) -> None:
        """The loop thread is about to submit a jitted dispatch that
        ``record_dispatch`` will record: ends the open phase and opens
        the ``llm.dispatch`` annotation carrying the record's ring
        number (this thread is the ring's only writer, so the next
        ``seq`` is known before the submit — an xplane event is joined
        to its record by identity, never by time)."""
        if self._ph_resume is not None:
            self._ph_abandon_dispatch()
        t = self._clock()
        resume = self._ph_name
        self._sp_close(None, t)
        self._ph_close(t)
        self._ph_cpu1 = time.thread_time()
        # ``_ph_t`` keeps the begin instant until the record lands: an
        # abandoned dispatch falls back into the interrupted phase
        # from there.
        self._ph_t = t
        self._ph_resume = resume if resume is not None else "prep"
        self._ph_name = None
        self._ph_ann = _annotate(
            "llm.dispatch", seq=self._ph_seq, kind=kind,
            program=program or "", k=int(k),
        )

    def _ph_record(self, now: float, start: float,
                   then: Optional[str]) -> Dict[str, Any]:
        """Close the tiling at a dispatch record that ran ``start`` to
        ``now``: the record's gap fields, and the phase the thread is
        in from ``now`` (``then``, default the one the dispatch
        interrupted)."""
        resume = self._ph_resume
        if resume is None:
            # No dispatch_begin (a direct caller): the open phase ran
            # up to the record's start, and the CPU reading below
            # includes the dispatch's own.
            resume = self._ph_name
            self._sp_close(None, max(start, self._ph_t))
            self._ph_close(max(start, self._ph_t))
            cpu1 = time.thread_time()
        else:
            self._ph_resume = None
            self._sp_close(None, now)  # a dispatch span left open
            self._ph_close(now)  # the llm.dispatch annotation
            cpu1 = self._ph_cpu1
        out: Dict[str, Any] = {
            "span_ms": {
                n: round(v * 1000.0, 3) for n, v in self._sp_acc.items()
            },
            "span_n": self._sp_n,
        }
        if "dispatch.submit" in self._sp_acc:
            out["submit_ms"] = out["span_ms"]["dispatch.submit"]
        self._sp_acc, self._sp_n = {}, {}
        tid = threading.get_ident()
        if self._ph_prev_end is not None and resume is not None:
            gap = start - self._ph_prev_end
            acc = self._ph_acc
            # The dispatch's start is read on the caller's clock a few
            # hundred ns after dispatch_begin's; that sliver belongs to
            # the interrupted phase, and the tiling is exact.
            acc[resume] = (
                acc.get(resume, 0.0) + gap - sum(acc.values())
            )
            out["gap_ms"] = round(gap * 1000.0, 3)
            out["host_ms"] = {
                p: round(v * 1000.0, 3) for p, v in acc.items()
            }
            if tid == self._ph_tid:
                out["gap_cpu_ms"] = round(
                    (cpu1 - self._ph_cpu0) * 1000.0, 3
                )
        self._ph_acc = {}
        self._ph_prev_end = now
        self._ph_tid = tid
        self._ph_open(then if then is not None else resume, now)
        self._ph_cpu0 = time.thread_time()
        return out

    def loop_phases_json(self) -> List[Tuple[str, float, float]]:
        """(phase, start_ms, end_ms) of the recent phase intervals, on
        the same origin as every ``start_ms`` here."""
        # No lock: the ring has one writer, and copying a deque of
        # tuples runs no bytecode, so the GIL makes the copy atomic.
        t0 = self.t0
        return [
            (name, (a - t0) * 1000.0, (b - t0) * 1000.0)
            for name, a, b in list(self.loop_phases)
        ]

    def loop_phase_metrics(
        self,
    ) -> List[Tuple[str, Dict[str, str], float]]:
        """``loop_phase_ms_total{phase=...}`` samples for /metrics
        (``(family, labels, value)`` like ``compile_metrics``)."""
        with self._lock:
            totals = sorted(self.loop_phase_ms_total.items())
        return [
            ("loop_phase_ms_total", {"phase": p}, round(v, 3))
            for p, v in totals
        ]

    def loop_span_metrics(
        self,
    ) -> List[Tuple[str, Dict[str, str], float]]:
        """``loop_span_ms_total{span=...}``, ``loop_span_total{span=...}``
        and ``admit_blocked_total{reason=...}`` samples for /metrics."""
        with self._lock:
            ms = sorted(self.loop_span_ms_total.items())
            n = sorted(self.loop_span_total.items())
        # One writer and int values: the copy is atomic under the GIL.
        blocked = sorted(dict(self.admit_blocked_total).items())
        return (
            [("loop_span_ms_total", {"span": p}, round(v, 3)) for p, v in ms]
            + [("loop_span_total", {"span": p}, v) for p, v in n]
            + [("admit_blocked_total", {"reason": r}, v) for r, v in blocked]
        )

    def loop_spans_json(
        self, rids: Optional[Sequence[int]] = None,
    ) -> List[Dict[str, Any]]:
        """The recent child spans (all, or those that worked for one of
        ``rids``), times in ms on the origin of every ``start_ms`` here;
        ``seq`` is the ring number of the dispatch record each led to."""
        t0 = self.t0
        keep = None if rids is None else set(rids)
        return [
            {"name": name, "start_ms": round((a - t0) * 1000.0, 3),
             "end_ms": round((b - t0) * 1000.0, 3),
             "duration_ms": round((b - a) * 1000.0, 3),
             "parent": parent, "rid": rid, "seq": seq}
            # As loop_phases_json: one writer, an atomic copy.
            for name, a, b, parent, rid, seq in list(self.loop_spans)
            if keep is None or rid in keep
        ]

    # -- dispatch spans ------------------------------------------------------

    def record_dispatch(
        self,
        kind: str,
        k: int = 1,
        occupancy: int = 0,
        prefill_tokens: int = 0,
        wall_ms: float = 0.0,
        fetch_ms: float = 0.0,
        swap_inflight: int = 0,
        rids: Sequence[int] = (),
        program: Optional[str] = None,
        then: Optional[str] = None,
        moe: Optional[Sequence[int]] = None,
        hc: Optional[Sequence[int]] = None,
        prefill_ctx: Optional[Tuple[int, int]] = None,
        prefill_write: Optional[Dict[str, int]] = None,
        queued: Optional[int] = None,
        ssm: Optional[Dict[str, int]] = None,
        merged_rows: Optional[int] = None,
        blocked: Optional[str] = None,
        first_sample: Optional[str] = None,
    ) -> int:
        """Record one jitted serving dispatch and link it into the
        CURRENT span of every request that rode it.  Returns the
        dispatch's ring-global seq number.  ``wall_ms`` covers dispatch
        submit through the packed fetch (what the host actually waited);
        ``fetch_ms`` isolates the ``np.asarray`` device sync.
        ``program`` names the jitted program.

        The record also carries the gap that led to it, from the loop
        phases: ``gap_ms`` (end of the previous record -> this one's
        start; absent on the first record and while no phase was ever
        marked), ``host_ms`` (``{phase: ms}``, summing to ``gap_ms`` —
        ``idle`` included so the tiling holds; readers leave it out),
        ``gap_cpu_ms`` (``time.thread_time()`` over the same interval;
        absent when the previous record came from another thread) and
        ``compiles`` (backend compiles booked since the previous record
        ended) beside ``uploads`` (host->device copies the loop thread
        made outside a jitted call since then, ``count_upload``).  ``moe``
        (routed-expert configurations) is the fetch's routing counts, in
        ``ops.moe.STATS``' order, summed over the expert-layer calls.  ``prefill_ctx`` (dispatches with a prefill
        lane) is the (attended, view) slots of the prefilling row's view:
        what prefill attention did work for, and the view's width.
        ``prefill_write`` (dispatches that land prompt KV) is what they
        landed in the pool: ``{"blocks": n}`` whole blocks from the fused
        lane, ``{"pairs": n}`` token slots from an insert.
        ``span_ms`` / ``span_n`` (every record) are the child spans closed
        since the previous record, ``{name: ms}`` and ``{name: count}``
        (``loop_span``: the parts of this gap's phases and of this
        dispatch's own host time; a nested span's time is in its parent's
        too), and ``submit_ms`` is ``span_ms["dispatch.submit"]``.
        ``queued`` (chunk dispatches) is the batcher's queue length at the
        submit: what the dispatch's K was clamped for; ``blocked`` beside
        it is why the queue's head stayed queued in the admission pass
        before the submit (:data:`ADMIT_BLOCKED`; None: nothing waits).
        ``ssm`` (recurrent state layers, dispatches with a prefill lane) is
        the state snapshots it moved: ``{"taken": n, "restored": n}``.
        ``merged_rows`` (fused dispatches that took the mixed pass, and only
        they) is the decoding rows whose first iteration rode the prompt
        chunk's pass over the weights.
        ``first_sample`` (fused dispatches) is what the program's admission
        sample cost: ``skipped`` (the prompt did not complete: no head, no
        draw), ``greedy`` (an argmax) or ``drawn`` (the warp and the draw).
        ``then`` names the phase the loop thread is in once
        the dispatch ends (default: the one it interrupted)."""
        if kind not in DISPATCH_KINDS:
            raise ValueError(
                f"unknown dispatch kind {kind!r}; have "
                f"{sorted(DISPATCH_KINDS)}"
            )
        if then is not None and then not in LOOP_PHASES:
            raise ValueError(
                f"unknown loop phase {then!r}; have {sorted(LOOP_PHASES)}"
            )
        if blocked is not None and blocked not in ADMIT_BLOCKED:
            raise ValueError(
                f"unknown blocked reason {blocked!r}; have {ADMIT_BLOCKED}"
            )
        now = self._clock()
        t = (now - self.t0) * 1000.0
        gap = self._ph_record(now, now - wall_ms / 1000.0, then)
        rec = {
            "seq": -1, "kind": kind, "k": int(k),
            "occupancy": int(occupancy),
            "prefill_tokens": int(prefill_tokens),
            "start_ms": round(t - wall_ms, 3),
            "wall_ms": round(wall_ms, 3),
            "fetch_ms": round(fetch_ms, 3),
            "swap_inflight": int(swap_inflight),
            "rids": list(rids),
        }
        if program is not None:
            rec["program"] = program
        if moe is not None:
            rec["moe"] = dict(zip(_MOE_STATS, map(int, moe)))
        if hc is not None:
            rec["hc"] = dict(zip(_HC_STATS, map(int, hc)))
        if prefill_ctx is not None:
            rec["prefill_ctx"] = {
                "attended": int(prefill_ctx[0]), "view": int(prefill_ctx[1]),
            }
        if prefill_write is not None:
            rec["prefill_write"] = {
                key: int(v) for key, v in prefill_write.items()
            }
        if queued is not None:
            rec["queued"] = int(queued)
            rec["blocked"] = blocked
        if ssm is not None:
            rec["ssm"] = {key: int(v) for key, v in ssm.items()}
        if merged_rows is not None:
            rec["merged_rows"] = int(merged_rows)
        if first_sample is not None:
            rec["first_sample"] = first_sample
        rec.update(gap)
        with self._lock:
            seq = self._seq
            self._seq += 1
            self._ph_seq = seq + 1  # the loop thread's own copy
            rec["seq"] = seq
            rec["compiles"] = self.compiles_total - self._ph_compiles0
            self._ph_compiles0 = self.compiles_total
            rec["uploads"] = self.host_uploads_total - self._ph_uploads0
            self._ph_uploads0 = self.host_uploads_total
            self.dispatches.append(rec)
            if "gap_ms" in gap:
                self.loop_gap_ms_total += gap["gap_ms"]
                self.loop_gap_cpu_ms_total += gap.get("gap_cpu_ms", 0.0)
                totals = self.loop_phase_ms_total
                for p, v in gap["host_ms"].items():
                    totals[p] = totals.get(p, 0.0) + v
            for p, v in gap["span_ms"].items():
                self.loop_span_ms_total[p] = (
                    self.loop_span_ms_total.get(p, 0.0) + v
                )
                self.loop_span_total[p] = (
                    self.loop_span_total.get(p, 0) + gap["span_n"][p]
                )
            self.dispatch_submit_ms_total += gap.get("submit_ms", 0.0)
            h = self.hist_dispatch.get(kind)
            if h is None:
                h = self.hist_dispatch[kind] = Histogram(
                    "dispatch_ms", HISTOGRAMS["dispatch_ms"],
                    labels={"kind": kind},
                )
            h.observe(wall_ms)
            if prefill_tokens > 0 or kind in ("insert", "suffix_insert"):
                self.hist["prefill_chunk_ms"].observe(wall_ms)
            for rid in rids:
                tl = self._by_rid.get(rid)
                if tl is None:
                    continue
                sp = self._current_span(tl)
                if sp is None:
                    continue
                if len(sp.dispatches) < _MAX_SPAN_DISPATCHES:
                    sp.dispatches.append(seq)
                else:
                    sp.dropped += 1
        # Outside the lock: the overload controller's EWMA ingest takes
        # its own lock (lock-order discipline; the record dict is
        # already fully built and never mutated after this point).
        if self.on_dispatch is not None:
            self.on_dispatch(rec)
        return seq

    def count_upload(self, n: int = 1) -> None:
        """The loop thread made ``n`` host->device copies outside a
        jitted call (counted at the site; a host operand handed to a
        dispatch's own call is not one).  ``host_uploads_total``, and the
        next record's ``uploads``."""
        self.host_uploads_total += n

    def record_compile(self, program: str, dur_ms: float) -> None:
        """One backend jit compile landed (fed by the jax.monitoring
        listener; ``program`` is whatever serving.py last attributed
        on the compiling thread).  Becomes a compile_ms observation, a
        span on the trace's ``jit compiles`` track, and a per-program
        counter."""
        with self._lock:
            t = self._now_ms()
            self.hist["compile_ms"].observe(dur_ms)
            self.compiles.append({
                "program": program, "t_ms": round(t, 3),
                "dur_ms": round(dur_ms, 3),
            })
            self.compiles_total += 1
            self.compiles_by_program[program] = (
                self.compiles_by_program.get(program, 0) + 1
            )

    def record_swap_in(self, ms: float, blocks: int) -> None:
        """A host-tier swap-in landed (staging start -> adoption)."""
        with self._lock:
            self.hist["swap_in_ms"].observe(ms)
        self.annotate("kv_swap_in", ms=round(ms, 3), blocks=blocks)

    # -- per-session KV accounting ------------------------------------------

    # request_kv fields that ACCUMULATE across calls (a replay or a
    # second swap-in adds to the session's ledger); everything else is
    # set-latest (gauge semantics: blocks_held, prefix_hit_tokens).
    _KV_ADDITIVE = frozenset({
        "swap_in_bytes", "swap_out_bytes", "evictions_suffered",
    })

    def request_kv(self, rid: int, **fields) -> None:
        """Merge per-session KV accounting onto ``rid``'s timeline —
        blocks held, prefix-hit depth in tokens, swap bytes moved,
        evictions suffered — shown under ``kv`` in
        ``/debug/requests/<id>``.  Host bookkeeping only."""
        with self._lock:
            tl = self._by_rid.get(rid)
            if tl is None:
                return
            for k, v in fields.items():
                if k in self._KV_ADDITIVE:
                    tl.kv[k] = tl.kv.get(k, 0) + v
                else:
                    tl.kv[k] = v

    def observe_kv(self, hit_depth_tokens: Optional[int] = None,
                   session_blocks: Optional[int] = None) -> None:
        """Feed the KV-capacity histograms: prefix-hit depth at
        admission, session block footprint at slot free."""
        with self._lock:
            if hit_depth_tokens is not None:
                self.hist["prefix_hit_depth_tokens"].observe(
                    hit_depth_tokens
                )
            if session_blocks is not None:
                self.hist["session_kv_blocks"].observe(session_blocks)

    def annotate(self, name: str, **fields) -> None:
        """Instant event into the bounded annotation ring (fault
        injections, quarantine transitions, kv-tier demotions...) —
        rendered as instant events in the Perfetto export."""
        with self._lock:
            self.events.append({
                "t_ms": round(self._now_ms(), 3), "name": name,
                "fields": fields,
            })

    def events_json(self, n: int = 256) -> List[Dict[str, Any]]:
        """Snapshot of the annotation ring (state transitions, fault
        injections, kv-tier events) — the flight recorder's
        state-transition record in ``/debug/bundle``."""
        with self._lock:
            items = list(self.events)[-n:] if n > 0 else []
        return [dict(e) for e in items]

    def record_metrics_snapshot(self, snapshot: Dict[str, Any]) -> None:
        """Flight recorder: append one periodic metric snapshot (a
        compact scalar dict the serving loop builds every
        ``flight_interval_s``) to the bounded ring — pure host
        bookkeeping, exported by ``/debug/bundle`` so a postmortem can
        see the trend into the incident, not just the final values."""
        rec = {
            "t_ms": round(self._now_ms(), 3),
            "unix_s": round(time.time(), 3),
        }
        rec.update(snapshot)
        with self._lock:
            self.metric_snapshots.append(rec)

    def metric_snapshots_json(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(s) for s in self.metric_snapshots]

    # -- server-side latency / SLO ------------------------------------------

    def observe_ttft(self, ms: float) -> None:
        # Locked: a concurrent /metrics scrape renders under the lock
        # and must never see a bucket updated ahead of _count (the
        # +Inf == _count invariant the parse test asserts).
        with self._lock:
            self.hist["ttft_ms"].observe(ms)

    def observe_itl(self, ms: float) -> None:
        with self._lock:
            self.hist["itl_ms"].observe(ms)

    def slo_account(
        self,
        ttft_ms: Optional[float],
        max_itl_ms: Optional[float],
        tokens: int,
        completed: bool = True,
    ) -> bool:
        """Score one finished request against the configured SLOs.
        ``ttft_ms`` None means no token was ever delivered (fails a
        configured TTFT SLO); an unconfigured dimension always passes;
        ``completed=False`` (failure/timeout) can never be goodput.
        Returns whether the request met every configured deadline."""
        ttft_ok = self.slo_ttft_ms is None or (
            ttft_ms is not None and ttft_ms <= self.slo_ttft_ms
        )
        itl_ok = self.slo_itl_ms is None or (
            max_itl_ms is None or max_itl_ms <= self.slo_itl_ms
        )
        ok = bool(completed and ttft_ok and itl_ok)
        with self._lock:
            self._slo_window.append((ttft_ok and completed,
                                     itl_ok and completed, ok))
            if ok:
                self.requests_slo_ok_total += 1
                self.goodput_tokens_total += int(tokens)
        return ok

    # -- exposition -----------------------------------------------------------

    def metrics(self) -> Dict[str, float]:
        """Scalar gauges/counters for the /metrics exposition (the
        histograms render separately via ``expose_histograms``)."""
        # Taken BEFORE self._lock: the decision log has its own leaf
        # lock and the two must never nest.
        decisions_total = self.decisions.total()
        with self._lock:
            n = len(self._slo_window) or 1
            ttft_ok = sum(1 for a, _, _ in self._slo_window if a)
            itl_ok = sum(1 for _, b, _ in self._slo_window if b)
            both = sum(1 for _, _, c in self._slo_window if c)
            return {
                "requests_finished_total": self.requests_finished_total,
                "requests_failed_total": self.requests_failed_total,
                "requests_cancelled_total": self.requests_cancelled_total,
                "decision_events_total": decisions_total,
                "compiles_total": self.compiles_total,
                "host_uploads_total": self.host_uploads_total,
                "loop_gap_ms_total": round(self.loop_gap_ms_total, 3),
                "loop_gap_cpu_ms_total": round(
                    self.loop_gap_cpu_ms_total, 3
                ),
                "dispatch_submit_ms_total": round(
                    self.dispatch_submit_ms_total, 3
                ),
                "slo_ttft_ms": self.slo_ttft_ms or 0.0,
                "slo_itl_ms": self.slo_itl_ms or 0.0,
                "requests_slo_ok_total": self.requests_slo_ok_total,
                "goodput_tokens_total": self.goodput_tokens_total,
                "slo_ttft_attainment": round(ttft_ok / n, 4),
                "slo_itl_attainment": round(itl_ok / n, 4),
                "slo_attainment": round(both / n, 4),
            }

    def expose_histograms(self, prefix: str = "llm_") -> List[str]:
        with self._lock:
            lines: List[str] = []
            for h in self.hist.values():
                lines.extend(h.expose(prefix))
            # The labeled dispatch_ms family: one HELP/TYPE header,
            # then every kind's series (header even when no dispatch
            # has landed yet, so the family is always discoverable).
            n = prefix + "dispatch_ms"
            lines.append(f"# HELP {n} {HISTOGRAMS['dispatch_ms']}")
            lines.append(f"# TYPE {n} histogram")
            for kind in sorted(self.hist_dispatch):
                lines.extend(
                    self.hist_dispatch[kind].expose(prefix, header=False)
                )
            return lines

    def compile_metrics(
        self,
    ) -> List[Tuple[str, Dict[str, str], float]]:
        """Per-program compile counters for /metrics: ``(family,
        labels, value)`` triples.  The family is registered in METRICS;
        the server renders one HELP/TYPE header per family."""
        with self._lock:
            compiles = sorted(self.compiles_by_program.items())
        return [
            ("program_compiles_total", {"program": prog}, n)
            for prog, n in compiles
        ]

    # -- debug JSON ------------------------------------------------------------

    def _span_json(self, sp: _Span) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "state": sp.state,
            "start_ms": round(sp.t0, 3),
            "end_ms": round(sp.t1, 3) if sp.t1 is not None else None,
            "duration_ms": (
                round(sp.t1 - sp.t0, 3) if sp.t1 is not None else None
            ),
            "dispatches": list(sp.dispatches),
        }
        if sp.dropped:
            out["dispatches_dropped"] = sp.dropped
        if sp.note:
            out["note"] = sp.note
        return out

    def timeline_json(self, request_id: str) -> Optional[Dict[str, Any]]:
        """The ``/debug/requests/<id>`` payload: the request's span
        timeline (accepts the external id, the provisional ``r<rid>``
        id, or a bare batcher rid)."""
        with self._lock:
            tl = self._timelines.get(request_id)
            if tl is None:
                tl = self._timelines.get(f"r{request_id}")
            if tl is None:
                try:
                    tl = self._by_rid.get(int(request_id))
                except ValueError:
                    tl = None
            if tl is None:
                return None
            seqs = {
                s for sp in tl.spans for s in sp.dispatches
            }
            return {
                "request_id": tl.request_id,
                "rids": list(tl.rids),
                "prompt_tokens": tl.prompt_tokens,
                "outcome": tl.outcome,
                "error": tl.error,
                "route": tl.route,
                "kv": dict(tl.kv),
                "spans": [self._span_json(sp) for sp in tl.spans],
                "dispatch_spans": [
                    dict(d) for d in self.dispatches if d["seq"] in seqs
                ],
                # What the loop thread did for this request's admission.
                "loop_spans": self.loop_spans_json(tl.rids),
            }

    def requests_json(self, n: int = 64) -> Dict[str, Any]:
        """Index of recent request timelines (most recent last).
        ``n <= 0`` returns nothing (``[-0:]`` would return the whole
        store)."""
        with self._lock:
            items = list(self._timelines.values())[-n:] if n > 0 else []
            return {"requests": [
                {
                    "request_id": tl.request_id,
                    "rids": list(tl.rids),
                    "outcome": tl.outcome,
                    "states": [sp.state for sp in tl.spans],
                }
                for tl in items
            ]}

    def dispatches_json(self, n: int = 128) -> Dict[str, Any]:
        with self._lock:
            items = list(self.dispatches)[-n:] if n > 0 else []
            return {"dispatches": [dict(d) for d in items]}

    def trace_json(self, window_ms: Optional[float] = None) -> Dict[str, Any]:
        """Chrome/Perfetto ``trace_event`` JSON for the recent serving
        window (default: everything the rings still hold).  Dispatches
        render on pid 1 / tid 1, the loop thread's phases between them
        on the ``serving loop`` track (tid 2), request lifecycles on
        one tid per request, annotations as instant events — load the payload in
        chrome://tracing or https://ui.perfetto.dev."""
        horizon = None
        if window_ms is not None:
            horizon = self._now_ms() - float(window_ms)
        # Snapshot under the lock, BUILD outside it: constructing tens
        # of thousands of event dicts while holding the one lock the
        # serving loop needs per dispatch would inject exactly the
        # decode-chunk stall this layer exists to measure.  Dispatch
        # and annotation dicts are created once and never mutated, so
        # the list copies are reference-shallow; only the mutable
        # _Span fields are copied out.
        phases = self.loop_phases_json()
        loop_spans = self.loop_spans_json()
        with self._lock:
            dispatches = list(self.dispatches)
            events = list(self.events)
            compiles = list(self.compiles)
            now_ms = self._now_ms()
            timelines = [
                (tl.request_id, tl.outcome, [
                    (sp.state, sp.t0, sp.t1, sp.dispatches[:64])
                    for sp in tl.spans
                ])
                for tl in self._timelines.values()
            ]
        ev: List[Dict[str, Any]] = [
            {"ph": "M", "pid": 1, "tid": 1, "name": "thread_name",
             "args": {"name": "dispatches"}},
            {"ph": "M", "pid": 1, "tid": 0, "name": "thread_name",
             "args": {"name": "jit compiles"}},
            {"ph": "M", "pid": 1, "tid": 2, "name": "thread_name",
             "args": {"name": "serving loop"}},
        ]
        # What the loop thread did between the dispatch spans above.
        for name, a, b in phases:
            if horizon is not None and b < horizon:
                continue
            ev.append({
                "name": name, "cat": "loop", "ph": "X", "pid": 1,
                "tid": 2, "ts": round(a * 1000.0, 1),
                "dur": max(1, round((b - a) * 1000.0)),
            })
        # ... and their parts, which nest under them on the same track.
        for sp in loop_spans:
            if horizon is not None and sp["end_ms"] < horizon:
                continue
            ev.append({
                "name": sp["name"], "cat": "loop_span", "ph": "X",
                "pid": 1, "tid": 2,
                "ts": round(sp["start_ms"] * 1000.0, 1),
                "dur": max(1, round(sp["duration_ms"] * 1000.0)),
                "args": {k: sp[k] for k in ("parent", "rid", "seq")},
            })
        for d in dispatches:
            if horizon is not None and d["start_ms"] < horizon:
                continue
            ev.append({
                "name": f"{d['kind']} k={d['k']}",
                "cat": "dispatch", "ph": "X", "pid": 1, "tid": 1,
                "ts": round(d["start_ms"] * 1000.0, 1),
                "dur": max(1, round(d["wall_ms"] * 1000.0)),
                "args": {
                    k: d[k] for k in (
                        "seq", "occupancy", "prefill_tokens",
                        "fetch_ms", "swap_inflight", "rids",
                        "program", "gap_ms",
                        "host_ms", "gap_cpu_ms", "compiles",
                        "span_ms", "submit_ms", "blocked",
                    ) if k in d
                },
            })
        for c in compiles:
            end = c["t_ms"]
            if horizon is not None and end < horizon:
                continue
            ev.append({
                "name": f"compile {c['program']}",
                "cat": "compile", "ph": "X", "pid": 1, "tid": 0,
                "ts": round((end - c["dur_ms"]) * 1000.0, 1),
                "dur": max(1, round(c["dur_ms"] * 1000.0)),
                "args": {"program": c["program"]},
            })
        tid = 3
        for request_id, outcome, spans in timelines:
            spans = [
                sp for sp in spans
                if horizon is None or sp[2] is None or sp[2] >= horizon
            ]
            if not spans:
                continue
            ev.append({
                "ph": "M", "pid": 1, "tid": tid,
                "name": "thread_name",
                "args": {"name": f"req {request_id}"},
            })
            for state, t0, t1, links in spans:
                if t1 is None:
                    t1 = now_ms
                ev.append({
                    "name": state, "cat": "request", "ph": "X",
                    "pid": 1, "tid": tid,
                    "ts": round(t0 * 1000.0, 1),
                    "dur": max(1, round((t1 - t0) * 1000.0)),
                    "args": {
                        "request_id": request_id,
                        "dispatches": links,
                        "outcome": outcome,
                    },
                })
            tid += 1
        # KV-cache events (tier demotions / host-LRU drops / evictions
        # / swap-ins / handoff export+import) get their OWN track, so a
        # trace window reads cache churn as one lane instead of noise
        # interleaved with dispatch annotations.  Each instant's args
        # keep whatever rid/request_id the emitter attached — the link
        # back to the owning request's track.
        kv_tid = tid
        kv_named = False
        for e in events:
            if horizon is not None and e["t_ms"] < horizon:
                continue
            is_kv = e["name"].startswith("kv_") or e["name"] in (
                "prefix_export", "prefix_import",
            )
            if is_kv and not kv_named:
                kv_named = True
                ev.append({
                    "ph": "M", "pid": 1, "tid": kv_tid,
                    "name": "thread_name",
                    "args": {"name": "kv cache"},
                })
            ev.append({
                "name": e["name"], "cat": "annotation", "ph": "i",
                "pid": 1, "tid": kv_tid if is_kv else 1, "s": "g",
                "ts": round(e["t_ms"] * 1000.0, 1),
                "args": dict(e["fields"]),
            })
        # t0_unix_s: the wall-clock instant ts==0 corresponds to —
        # the router's fleet merge uses it to shift every replica's
        # relative timestamps into one frame (Perfetto ignores
        # unknown top-level keys).
        return {
            "traceEvents": ev, "displayTimeUnit": "ms",
            "t0_unix_s": round(self.t0_unix, 6),
        }


# ---------------------------------------------------------------------------
# Structured logging
# ---------------------------------------------------------------------------

class StructuredLogger:
    """One formatter for every server/batcher log line.

    ``json_mode=False`` (default) renders ``ts event k=v ...`` text;
    ``json_mode=True`` (run.py ``--log-json``) renders one JSON object
    per line with stable ``event`` / ``request_id`` / ``dispatch_seq``
    fields, so a fleet's log pipeline can join server lines to
    ``/debug`` timelines without regexes.  Writes are single ``print``
    calls (atomic enough under the GIL for line-oriented collectors).

    Every formatted line also lands in a bounded in-memory ring — the
    flight recorder's LOG TAIL, exported by ``/debug/bundle`` so a
    postmortem artifact carries the last ``ring`` log lines even when
    nobody captured stdout.  ``quiet=True`` keeps the ring but never
    prints (the server's default logger when the caller supplied
    none: the bundle still has a tail, stdout stays silent)."""

    def __init__(self, json_mode: bool = False, stream=None,
                 ring: int = 256, quiet: bool = False):
        self.json_mode = bool(json_mode)
        self.stream = stream if stream is not None else sys.stdout
        self.quiet = bool(quiet)
        self._lock = threading.Lock()
        self._ring: "deque[str]" = deque(maxlen=ring)

    def log(self, event: str, message: str = "", **fields) -> None:
        if self.json_mode:
            rec: Dict[str, Any] = {
                "ts": round(time.time(), 3), "event": event,
            }
            if message:
                rec["message"] = message
            rec.update({k: v for k, v in fields.items() if v is not None})
            line = json.dumps(rec, default=str)
        else:
            parts = [event]
            if message:
                parts.append(message)
            parts.extend(
                f"{k}={v}" for k, v in fields.items() if v is not None
            )
            line = " ".join(parts)
        with self._lock:
            self._ring.append(line)
        if not self.quiet:
            print(line, file=self.stream, flush=True)

    def tail(self, n: int = 256) -> List[str]:
        """The most recent formatted log lines (flight-recorder tail)."""
        with self._lock:
            out = list(self._ring)
        return out[-n:] if n > 0 else []
