"""Continuous-batching request scheduler over the paged LEXI-compressed
cache (the serving half of the ROADMAP north star).

``ServeEngine`` owns a model-parallel mesh, the jitted device functions and
one ``PagedState``; ``RequestScheduler`` is the admission queue.  The loop:

    while work:
        admit   — a *batched, prefix-deduplicated fast path*:
                  (a) queued requests whose prompt prefix matches full
                      pages already in the cache map those pages into
                      their page-table row (``map_shared_slot``) with ZERO
                      prefill FLOPs and zero extra page memory — only the
                      unmatched suffix replays;
                  (b) remaining ("cold") requests are drained per length
                      bucket and prefilled in ONE jitted dispatch — a
                      vmapped B=1 ``engine.prefill`` over the bucket trunk
                      feeding ``insert_sequences`` (per-sequence LEXI
                      block compression is preserved bit-for-bit, so the
                      blocks scatter straight into pages);
                  (c) every admitted slot's leftover prompt tokens (trunk
                      bucket tail or unmatched prefix suffix) replay
                      per-slot through fused ``paged_replay_steps`` —
                      exact numerics at every position.
        step    — ONE dispatch runs K fused ``paged_decode_step``s as a
                  ``lax.scan`` (K bounded by the earliest budget-finish
                  event, so streams are byte-identical to stepping one
                  token at a time), one greedy token per active slot/step
        evict   — slots that hit their token budget, emit ``eos_id``, or
                  complete a stop sequence (host-side rolling suffix match
                  over the emitted tokens) release their pages
                  (``release_slots``) at the window boundary; with prefix
                  sharing the release routes through the tiered
                  ``PageCache``: a column whose refcount hits zero is
                  RETAINED on the device (hot tier) after its immutable
                  payload spilled to host RAM (warm tier), so a later
                  identical prefix re-maps or re-imports it with zero
                  prefill FLOPs

The same machinery also runs SPLIT across replicas: ``repro.serve.disagg``
drives ``_admit_phase`` on prefill replicas and ``_decode_window`` on
decode replicas, with admitted sequences crossing between them as
compressed page-transfer blobs (``repro.serve.transport``) — see
``docs/ARCHITECTURE.md`` for the full dataflow.

Admission compile count is bounded: admit functions are keyed by
(trunk bucket, batch size) where trunk buckets are power-of-two multiples
of tp — NOT by raw prompt length — so serving arbitrary length mixes
compiles O(log(max_len/tp) * n_slots) admit functions total
(``ServeStats.n_admit_compiles`` tracks it).  Exception: MoE / SSM / MLA
architectures keep the maximal floor-of-tp trunk (see ``_bucket_of`` —
their decode float path is not bit-equal to prefill, so in-prompt replay
must stay under tp tokens to preserve the legacy-exact split).

**Prefix sharing bookkeeping (host-side).**  Full pages are immutable
once LEXI-FW-compressed, so sharing is pure page-table indirection.  The
host owns a tiered content-addressed ``repro.serve.pagecache.PageCache``
keyed by the chained prefix digests of ``repro.serve.digest.chain_keys``
(32-byte SHA-256 chain links, O(len) to build): the **hot** tier maps a
key to its per-shard page-id vector (ids are tracked per shard because
unaligned releases can permanently permute the free-list order between
shards), retains zero-ref columns under an LRU, and evicts them only
under pool pressure (``_ensure_free_pages``); the **warm** tier holds
the columns' compressed payloads in host RAM (spilled at last release,
restored by a device import — no prefill); the **remote** tier pulls
spilled payloads back from a peer replica's digest store by content
digest (the ``FETCH`` message of ``repro.serve.net``).  Page ids are
read back from the device page table at admit/release boundaries only
(no per-token sync).  MoE/MLA decode is not bit-equal to prefill for
the suffix replay, so those architectures auto-disable sharing (streams
are unchanged either way; hits are simply zero).  Hybrids (SSM +
attention) cannot replay a suffix bit-exactly either, but they DO share
whole page-aligned prompts: admission captures the recurrent state at
the prompt boundary (``_capture_snapshots``) and a later identical
prompt maps/imports every page column and restores that snapshot —
replay-free, hence bit-exact (``_snapshot_match``).

Device state crosses jit boundaries as global arrays with one leading
"model"-sharded axis per leaf (each shard's page pool / page table / ring
is independent state, so the global view is simply the stack of per-shard
views).  The wrapper functions squeeze/unsqueeze that axis at the
shard_map boundary.

Constraints (documented, validated in ``submit``):
  * decoder-only families (dense / MoE / SSM / hybrid); no enc-dec.
  * prompt lengths >= the model-parallel degree (any length admits via
    bucketing; the sequence-sharded trunk needs one slot per shard).
  * prompt_len + max_new_tokens <= max_len (page-pool capacity).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import MeshConfig, ModelConfig, RunConfig
from repro.core import collectives as cl
from repro.core import packing
from repro.core import weights as weights_mod
from repro.kernels import ops as kernel_ops
from repro.models import cache as cache_mod
from repro.models import lm, params as PM
from repro.models.ssm import SSMState
from . import engine
from . import transport
from .digest import chain_keys
from .pagecache import PageCache
from .telemetry import (ENGINE_LANE, MetricsRegistry, Tracer,
                        summarize_latencies)


@dataclasses.dataclass
class Request:
    """One generation request (greedy decoding, token budget + optional
    EOS / stop sequences).  ``eos_id`` and ``stop_seqs`` override the
    engine-level defaults when set (``stop_seqs=()`` disables stopping for
    this request even when the engine has defaults)."""
    uid: int
    prompt: np.ndarray               # (S,) int32, S >= tp (any length)
    max_new_tokens: int
    eos_id: Optional[int] = None
    stop_seqs: Optional[Sequence[Sequence[int]]] = None


@dataclasses.dataclass
class RequestResult:
    uid: int
    prompt_len: int
    tokens: List[int]                # generated (incl. EOS/stop seq if hit)
    latency_s: float                 # admit (incl. own prefill) -> finish
    stop_reason: str = "budget"      # budget | eos | stop_string
    ttft_s: float = 0.0              # submit -> first token (0.0 when the
                                     # first token was produced in another
                                     # process, e.g. remote disagg decode)


@dataclasses.dataclass
class ServeStats:
    n_requests: int
    n_tokens: int
    decode_steps: int                # total decode steps executed
    n_dispatches: int                # device dispatches issuing those steps
    n_admit_dispatches: int          # batched-prefill admit dispatches
    n_replay_dispatches: int         # fused prompt-tail replay dispatches
    n_admit_compiles: int            # distinct admit fns compiled (lifetime)
    shared_page_hits: int            # prefix-index page columns mapped
    wall_s: float
    requests_per_s: float
    tokens_per_s: float
    peak_pages: int                  # pages in use, summed over shards/layers
    peak_cache_bytes: int            # stored bytes of those pages
    peak_cache_raw_bytes: int        # bf16 bytes of the same pages
    mean_latency_s: float
    latency_p50_s: float
    latency_p95_s: float
    decode_backend: str              # resolved pallas | interpret | jax
    # tiered PageCache lifecycle counters (engine lifetime, like
    # n_admit_compiles — see repro.serve.pagecache)
    cache_hot_hits: int = 0          # retained zero-ref columns re-acquired
    cache_spilled_pages: int = 0     # page payloads written to the warm store
    cache_spilled_bytes: int = 0
    cache_fetched_pages: int = 0     # payloads restored from warm/remote
    cache_fetched_bytes: int = 0
    cache_reprefill_cols: int = 0    # warm columns lost on every tier
    cache_evicted_cols: int = 0      # hot columns evicted under pool pressure
    # serving weight plane (compressed-at-rest params, core.weights): HBM
    # bytes a decode step streams for weights — analytic, like
    # models/cache.py:page_bytes meters KV bytes
    weights_compressed: bool = False
    weight_backend: str = "jax"      # resolved pallas | interpret | jax
    weight_bytes_per_step: int = 0   # stored (packed + raw-leaf) bytes
    weight_raw_bytes_per_step: int = 0   # same store, all-bf16
    # span-derived latency summaries (telemetry registry histograms;
    # 0.0 when the stage never ran)
    ttft_mean_s: float = 0.0         # submit -> first token
    ttft_p50_s: float = 0.0
    ttft_p95_s: float = 0.0
    admit_window_mean_s: float = 0.0   # batched prefill/replay dispatches
    decode_window_mean_s: float = 0.0  # fused decode dispatches

    @property
    def cache_ratio(self) -> float:
        return self.peak_cache_raw_bytes / max(self.peak_cache_bytes, 1)

    @property
    def weight_ratio(self) -> float:
        """Packed/raw weight HBM traffic per decode step (≤1; 1.0 = raw)."""
        return self.weight_bytes_per_step / max(self.weight_raw_bytes_per_step,
                                                1)


def _norm_stops(stop_seqs) -> Tuple[Tuple[int, ...], ...]:
    """Normalize stop sequences to a tuple of int tuples; empty sequences
    are rejected (they would stop every request at its first token)."""
    if stop_seqs is None:
        return ()
    out = tuple(tuple(int(t) for t in s) for s in stop_seqs)
    if any(not s for s in out):
        raise ValueError("stop sequences must be non-empty")
    return out


@dataclasses.dataclass
class _LoopState:
    """Host-side mutable state of one serving loop.

    Extracted from ``ServeEngine.run`` so the same admission / decode /
    termination machinery can be driven in pieces by the disaggregated
    replicas (``repro.serve.disagg``): a prefill replica runs only
    ``_admit_phase`` on its loop state, a decode replica only
    ``_decode_window`` — with request occupancy seeded by a transfer
    instead of an admission.
    """
    slot_req: List[Optional["Request"]]
    done: List[bool]                  # finished, awaiting eviction
    reason: List[str]
    emitted: Dict[int, List[int]]
    admit_t: Dict[int, float]
    results: Dict[int, "RequestResult"]
    cur: np.ndarray                   # (n_slots, 1) i32 next input tokens
    slot_len: List[int]               # host mirror of cache lengths
    steps: int = 0
    dispatches: int = 0
    admit_dispatches: int = 0
    replay_dispatches: int = 0
    shared_hits: int = 0
    peak_pages: int = 0
    # admission and ring-flush work, summed over the loop's dispatches
    prompt_tokens: int = 0            # prompt tokens of admitted requests
    replay_tokens: int = 0            # of those, fed through replay steps
    rings_filled: int = 0             # cache_mod.flush_work, per step
    rings_compressed: int = 0
    # telemetry timestamps: first-token wall clocks (popped at finish /
    # export), computed TTFTs, and per-dispatch window durations — all
    # O(requests) / O(dispatches), never O(tokens)
    first_tok_t: Dict[int, float] = dataclasses.field(default_factory=dict)
    ttft_s: Dict[int, float] = dataclasses.field(default_factory=dict)
    admit_window_s: List[float] = dataclasses.field(default_factory=list)
    decode_window_s: List[float] = dataclasses.field(default_factory=list)

    def live_slots(self) -> List[int]:
        return [s for s, r in enumerate(self.slot_req) if r is not None]


def _engine_span(name: str, **counts):
    """Run a ``ServeEngine`` method inside ``self.tracer.span(name)`` on
    the engine lane."""
    def wrap(fn):
        @functools.wraps(fn)
        def traced(self, *a, **kw):
            with self.tracer.span(name, pid=self.name, tid=ENGINE_LANE,
                                  **counts):
                return fn(self, *a, **kw)
        return traced
    return wrap


class RequestScheduler:
    """FIFO admission queue with capacity validation.

    Prompt lengths need not be multiples of tp: admission buckets each
    prompt to a power-of-two-multiple-of-tp trunk and replays the leftover
    tokens through exact paged decode steps, so any length >= tp is
    accepted.  Same-bucket requests may admit ahead of a different-bucket
    request queued earlier in the same admission round (bounded FIFO
    deviation in exchange for one prefill dispatch per bucket).
    """

    def __init__(self, tp: int, max_len: int):
        self.tp = tp
        self.max_len = max_len
        self.queue: deque[Request] = deque()
        # wired by the owning engine: the root request span opens at
        # submit, and submit_t feeds TTFT (first token - submit)
        self.tracer: Tracer = Tracer(False)
        self.pid = "serve"
        self.submit_t: Dict[int, float] = {}

    def submit(self, req: Request) -> None:
        s = len(req.prompt)
        if s < self.tp:
            raise ValueError(
                f"prompt length {s} must be >= tp={self.tp} "
                "(the sequence-sharded trunk needs one slot per shard)")
        if s + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"request needs {s + req.max_new_tokens} tokens > "
                f"max_len={self.max_len}")
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        # validate stop sequences HERE, before the request can occupy a
        # slot — a malformed override raising mid-loop (first _check_done)
        # would abort run() with the slot's pages still allocated
        _norm_stops(req.stop_seqs)
        self.submit_t[req.uid] = time.perf_counter()
        self.tracer.request_begin(
            req.uid, pid=self.pid,
            args={"prompt_len": s,
                  "max_new_tokens": int(req.max_new_tokens)})
        self.tracer.stage(req.uid, "queue")
        self.queue.append(req)

    def pop(self) -> Optional[Request]:
        return self.queue.popleft() if self.queue else None

    def __len__(self) -> int:
        return len(self.queue)


class ServeEngine:
    """Continuous-batching inference engine (one replica, model-parallel)."""

    def __init__(self, cfg: ModelConfig, run: RunConfig, *, tp: int = 1,
                 n_slots: int = 4, max_len: int = 256, params=None,
                 seed: int = 0, eos_id: Optional[int] = None,
                 stop_seqs: Optional[Sequence[Sequence[int]]] = None,
                 max_fuse_steps: int = 32, prefix_sharing: bool = True,
                 store_pages: int = 4096, remote_fetch=None,
                 compress_weights: bool = False,
                 tracer: Optional[Tracer] = None, name: str = "serve"):
        if cfg.encdec or cfg.frontend != "none":
            raise ValueError("continuous batching covers decoder-only, "
                             "text-frontend architectures")
        if max_fuse_steps < 1:
            raise ValueError("max_fuse_steps must be >= 1")
        self.cfg, self.run_cfg, self.tp = cfg, run, tp
        self.n_slots, self.max_len = n_slots, max_len
        self.eos_id = eos_id
        self.stop_seqs = _norm_stops(stop_seqs)
        self.max_fuse_steps = max_fuse_steps
        # sharing needs KV pages (attention) and a decode path that is
        # bit-equal to prefill at in-prompt positions (the matched prefix
        # skips prefill; the suffix replays through decode steps) — which
        # rules out MoE / MLA, see _bucket_of.  Hybrids (SSM + attention)
        # cannot suffix-replay either, but share whole page-aligned
        # prompts through boundary SSM snapshots (_snapshot_match), so
        # they stay enabled.
        self.prefix_sharing = bool(prefix_sharing and cfg.n_heads > 0
                                   and cfg.moe is None and cfg.mla is None)
        mesh_cfg = MeshConfig(data=1, model=tp, pod=1)
        self.mesh = jax.make_mesh(
            (1, tp), ("data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2)
        self.table = lm.lm_table(cfg, mesh_cfg, run)
        self.dims = lm.lm_fsdp_dims(self.table)
        self.params = (params if params is not None
                       else PM.init_params(self.table, jax.random.key(seed),
                                           mesh=self.mesh))
        self._pspecs = PM.param_pspecs(self.table)
        # serving weight plane: pack bulk 2-D leaves into the LEXI-FW
        # at-rest layout (idempotent — disagg replicas share one tree) and
        # swap the matching pspec nodes; every jitted fn below closes over
        # self._pspecs, so the packed store flows into all dispatch paths.
        self.compress_weights = bool(compress_weights)
        self.weight_backend = kernel_ops.resolve_weight_backend(run.codec)
        if self.compress_weights:
            self.params, self._pspecs = weights_mod.pack_serving_params(
                self.params, self._pspecs, backend=self.weight_backend,
                tp=tp, mesh=self.mesh, stacked=("blocks",))
        self._weight_bytes = weights_mod.weight_plane_bytes(self.params)
        # telemetry: the tracer is shared (a disagg fleet hands every
        # replica one tracer, distinguished by engine ``name`` = span
        # pid); the metrics registry is per-engine and always on — its
        # counters are plain host ints refreshed by ``sync_metrics``
        self.name = name
        self.tracer = tracer if tracer is not None else Tracer(False)
        self.registry = MetricsRegistry()
        self.scheduler = RequestScheduler(tp, max_len)
        self.scheduler.tracer = self.tracer
        self.scheduler.pid = name

        shard = jax.eval_shape(lambda: engine.empty_paged_state(
            cfg, run, n_slots, max_len, tp))
        self._sspec = jax.tree_util.tree_map(lambda a: P("model"), shard)
        # global view: one leading model-sharded axis, per-shard copies,
        # each built on the device that holds its shard
        self.state = jax.jit(
            lambda: jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (tp,) + a.shape),
                engine.empty_paged_state(cfg, run, n_slots, max_len, tp)),
            out_shardings=jax.tree_util.tree_map(
                lambda a: NamedSharding(self.mesh, P("model")), shard))()

        # tokens covered by one full page column (all shards' owned slots)
        self.blk_tokens = run.codec.cache_block * tp
        self._n_pages = (shard.kv.page_used.shape[-1]
                         if shard.kv is not None else 0)
        self._maxp = (shard.kv.page_table.shape[-1]
                      if shard.kv is not None else 0)

        # host-side page-lifecycle bookkeeping (see module docstring):
        # the tiered content-addressed PageCache owns the prefix index,
        # refcounts, retention LRU, warm spill store and SSM snapshots;
        # _slot_keys mirrors which prefix keys each slot holds refs on
        self.cache = PageCache(max_store_pages=store_pages,
                               remote_fetch=remote_fetch)
        self._slot_keys: List[List[bytes]] = [[] for _ in range(n_slots)]
        self._slot_busy = np.zeros((n_slots,), bool)

        # streaming-prefill hook: the disagg prefill replica sets this to
        # export freshly completed page columns MID-ADMISSION (after the
        # batched trunk insert and after every fused replay dispatch), so
        # full pages can cross the transfer link while the prompt tail is
        # still replaying.  Called with the loop state; None = no-op.
        self.admit_progress_cb = None

        self.n_admit_compiles = 0
        self._admit_cache: Dict[Tuple[int, int], object] = {}
        self._decode_cache: Dict[int, object] = {}
        self._replay_cache: Dict[int, object] = {}
        self._export_cache: Dict[int, object] = {}
        self._import_cache: Dict[int, object] = {}
        self._release = jax.jit(cl.shmap(
            self._release_fn, self.mesh, (self._sspec, P(None)),
            self._sspec))
        self._release_shared = None
        self._map_shared = None
        self._restore_ssm = None

    # legacy aliases: the prefix index/refcounts now live in the PageCache
    # (kept as views — the disagg import path and tests poke them directly)

    @property
    def _prefix_index(self) -> Dict[bytes, np.ndarray]:
        return self.cache.index

    @property
    def _prefix_ref(self) -> Dict[bytes, int]:
        return self.cache.ref

    # -- shard_map bodies --------------------------------------------------

    @staticmethod
    def _squeeze(st_g):
        return jax.tree_util.tree_map(lambda a: a[0], st_g)

    @staticmethod
    def _unsqueeze(st):
        return jax.tree_util.tree_map(lambda a: a[None], st)

    def _release_fn(self, st_g, mask):
        return self._unsqueeze(engine.release_slots(self._squeeze(st_g),
                                                    mask))

    def _release_shared_for(self):
        """(state, slot_mask, free_mask (tp, P)) -> state; frees exactly
        the pages the host refcounts said hit zero (per-shard masks)."""
        if self._release_shared is None:
            def rel(st_g, mask, free_g):
                st = engine.release_slots(self._squeeze(st_g), mask,
                                          free_mask=free_g[0])
                return self._unsqueeze(st)

            self._release_shared = jax.jit(cl.shmap(
                rel, self.mesh, (self._sspec, P(None), P("model", None)),
                self._sspec))
        return self._release_shared

    def _map_shared_for(self):
        """(state, slot, ids (tp, maxp), n_cols, base_len) -> state."""
        if self._map_shared is None:
            def mp(st_g, slot, ids_g, n_cols, base_len):
                st = engine.map_shared_slot(self._squeeze(st_g), slot,
                                            ids_g[0], n_cols, base_len)
                return self._unsqueeze(st)

            self._map_shared = jax.jit(cl.shmap(
                mp, self.mesh,
                (self._sspec, P(), P("model", None), P(), P()),
                self._sspec))
        return self._map_shared

    def _restore_ssm_for(self):
        """(state, slot, ssm slot leaves (tp, L, ...)) -> state: scatter a
        boundary SSM snapshot into one slot.  The hybrid half of a
        snapshot hit whose page columns were ALL still hot — no import
        dispatch runs, so the recurrent state needs its own scatter."""
        if self._restore_ssm is None:
            def rs(st_g, slot, ssm_g):
                st = self._squeeze(st_g)
                ssm = jax.tree_util.tree_map(
                    lambda a, v: a.at[:, slot].set(v.astype(a.dtype)),
                    st.ssm, self._squeeze(ssm_g))
                return self._unsqueeze(st._replace(ssm=ssm))

            self._restore_ssm = jax.jit(cl.shmap(
                rs, self.mesh, (self._sspec, P(), P("model")),
                self._sspec))
        return self._restore_ssm

    def _export_for(self, n_cols: int):
        """(state, slot, col0) -> (kv wire (tp, L, ...) leaves, ssm slot
        leaves, length) — one jitted export per page-column count
        (``n_cols`` is static; at most max-pages-per-slot distinct values
        exist).  ``col0`` (traced) windows the gather to page columns
        ``[col0, col0 + n_cols)`` — 0 for a whole-sequence export, the
        streamed-so-far watermark for chunked prefill export."""
        fn = self._export_cache.get(n_cols)
        if fn is None:
            def ex(st_g, slot, col0):
                kvw, ssm, length = engine.export_slot(
                    self._squeeze(st_g), slot, n_cols, self.tp, col0)
                return (self._unsqueeze(kvw), self._unsqueeze(ssm), length)

            fn = jax.jit(cl.shmap(
                ex, self.mesh, (self._sspec, P(), P()),
                (P("model"), P("model"), P())))
            self._export_cache[n_cols] = fn
        return fn

    def _import_for(self, n_cols: int):
        """(state, slot, kv wire, ssm slot, length, col0) -> state — the
        decode-replica half of a handoff (pages allocated from THIS pool's
        free list; see ``cache.import_sequence``).  ``col0`` (traced) > 0
        imports only the wire columns ``[col0, col0 + n_cols)``, keeping
        the row below ``col0`` (prefix-reuse maps shared pages there)."""
        fn = self._import_cache.get(n_cols)
        if fn is None:
            def im(st_g, slot, kvw_g, ssm_g, length, col0):
                st = engine.import_slot(
                    self._squeeze(st_g), slot, self._squeeze(kvw_g),
                    self._squeeze(ssm_g), length, self.tp, col0)
                return self._unsqueeze(st)

            fn = jax.jit(cl.shmap(
                im, self.mesh,
                (self._sspec, P(), P("model"), P("model"), P(), P()),
                self._sspec))
            self._import_cache[n_cols] = fn
        return fn

    def _decode_for(self, n_steps: int):
        """One jitted K-step fused decode per distinct K.

        The K decode steps run as one ``lax.scan`` inside one dispatch, so
        host overhead amortizes over K tokens; the scanned body is exactly
        ``paged_decode_step`` + greedy, so the emitted (K, S, 1) token block
        is byte-identical to K single-step dispatches.
        """
        fn = self._decode_cache.get(n_steps)
        if fn is not None:
            return fn

        def decode(pp, st_g, toks):
            st = self._squeeze(st_g)

            def body(carry, _):
                st_c, tok = carry
                logits, st_c = engine.paged_decode_step(
                    self.cfg, self.run_cfg, pp, self.dims, st_c, tok,
                    self.tp)
                tok = engine.greedy_token(self.cfg, logits, self.tp)
                return (st_c, tok), tok

            (st, _), seq = jax.lax.scan(body, (st, toks), None,
                                        length=n_steps)
            return seq, self._unsqueeze(st)

        fn = jax.jit(cl.shmap(
            decode, self.mesh,
            (self._pspecs, self._sspec, P(None, None)),
            (P(None, None, None), self._sspec)))
        self._decode_cache[n_steps] = fn
        return fn

    def _replay_for(self, n_steps: int):
        """One jitted K-step fused prompt replay per distinct K (powers of
        two, so the cache stays at O(log max prompt length) entries).
        Feeds known tokens through ``paged_replay_steps`` with a per-step
        per-slot feed mask — heterogeneous tail lengths replay together."""
        fn = self._replay_cache.get(n_steps)
        if fn is not None:
            return fn

        def replay(pp, st_g, toks, feed):
            seq, st = engine.paged_replay_steps(
                self.cfg, self.run_cfg, pp, self.dims, self._squeeze(st_g),
                toks, feed, self.tp)
            return seq, self._unsqueeze(st)

        fn = jax.jit(cl.shmap(
            replay, self.mesh,
            (self._pspecs, self._sspec, P(None, None, None), P(None, None)),
            (P(None, None, None), self._sspec)))
        self._replay_cache[n_steps] = fn
        return fn

    def _fuse_steps(self, bound: int) -> int:
        """Decode steps to fuse into the next dispatch: the largest power
        of two <= the earliest slot-finish event (so eviction/admission
        still happen at window boundaries and the jit cache stays at
        O(log max_new_tokens) entries), capped by ``max_fuse_steps``."""
        k = 1 << (max(bound, 1).bit_length() - 1)
        return min(k, self.max_fuse_steps)

    def _bucket_of(self, prompt_len: int) -> int:
        """Trunk bucket: the largest power-of-two multiple of tp that fits
        the prompt, for pure-attention architectures — leftover tokens
        replay through paged decode steps that are bit-identical to the
        prefill at the same positions, so bucketing never changes streams
        while bounding the admit compile count at O(log(max_len/tp)).

        Routed / recurrent layers (MoE, SSM, MLA absorbed-form decode)
        combine shard partials on a different float path at decode than at
        batched prefill (e.g. MoE decode psums bf16 per-shard partials
        where prefill a2a-combines expert outputs in f32), so for them an
        in-prompt replay step is NOT bit-equal to prefilling that position.
        Those families keep the maximal floor-of-tp trunk (tail < tp, the
        exact legacy admission split) — their admit compile count grows
        with distinct aligned lengths, which is the price of exactness."""
        c = self.cfg
        exact = (prompt_len // self.tp) * self.tp
        if c.moe is not None or c.ssm is not None or c.mla is not None:
            return exact
        b = self.tp
        while b * 2 <= prompt_len:
            b *= 2
        return b

    def _admit_for(self, trunk_len: int, n_batch: int):
        """One jitted admit per (trunk bucket, batch size): a vmapped B=1
        ``engine.prefill`` over the batch (per-sequence numerics AND
        per-sequence LEXI block compression are bit-identical to separate
        B=1 prefills — a true B>1 prefill would jointly compress blocks
        across sequences and couple MoE capacity between them) feeding one
        vectorized ``insert_sequences`` scatter."""
        key = (trunk_len, n_batch)
        fn = self._admit_cache.get(key)
        if fn is not None:
            return fn

        def admit(pp, st_g, prompts, slots):
            st = self._squeeze(st_g)

            def one(prompt):
                logits, d = engine.prefill(
                    self.cfg, self.run_cfg, pp, self.dims, prompt[None],
                    self.max_len, self.tp)
                return engine.greedy_token(self.cfg, logits, self.tp), d

            toks, ds = jax.vmap(one)(prompts)
            st = engine.insert_sequences(self.cfg, self.run_cfg, st, ds,
                                         slots, trunk_len, self.tp)
            return toks[:, 0], self._unsqueeze(st)

        fn = jax.jit(cl.shmap(
            admit, self.mesh,
            (self._pspecs, self._sspec, P(None, None), P(None)),
            (P(None, None), self._sspec)))
        self._admit_cache[key] = fn
        self.n_admit_compiles += 1
        return fn

    # -- prefix index ------------------------------------------------------

    def _prefix_keys(self, prompt: np.ndarray, n_cols: int) -> List[bytes]:
        """Chained content keys, one per full page column — shared with
        the transport's dedup layer, see ``repro.serve.digest``."""
        return chain_keys(prompt, n_cols, self.blk_tokens)

    def _prefix_match_cols(self, prompt: np.ndarray
                           ) -> Tuple[int, List[bytes], List[List[bytes]]]:
        """(matched column count, their keys, warm payload columns).

        The longest run of leading full page columns restorable from the
        cache — hot columns (mapped for free) extended by warm columns
        (payloads fetched from the host-RAM store or a peer, imported
        without prefill FLOPs).  Capped so at least one suffix token
        remains to replay (the first generated token needs logits from
        the last prompt position) — and gated on replay cost: a match is
        only worth taking when the unmatched suffix replay is no longer
        than the cold path's own bucket-tail replay (plus at most one
        column), otherwise a shallow hit on a long prompt (e.g. a shared
        short preamble) would trade one batched prefill dispatch for a
        long per-token replay.  The gate is monotone in the match depth,
        so it is checked against the deepest candidate BEFORE any warm
        bytes are fetched.  Hybrids never take this path (suffix replay
        is not bit-equal for the recurrence) — see ``_snapshot_match``."""
        if not self.prefix_sharing or self.cfg.ssm is not None:
            return 0, [], []
        bt = self.blk_tokens
        keys = self._prefix_keys(prompt, (len(prompt) - 1) // bt)
        h = 0
        while h < len(keys) and keys[h] in self.cache.index:
            h += 1
        m_cand = h
        while m_cand < len(keys) and self.cache.has_warm(keys[m_cand]):
            m_cand += 1

        def ok(mm: int) -> bool:
            if mm < 1:
                return False
            suffix = len(prompt) - mm * bt
            cold_tail = len(prompt) - self._bucket_of(len(prompt))
            return suffix <= max(cold_tail, bt)

        if not ok(m_cand):
            return 0, [], []
        warm: List[List[bytes]] = []
        m = h
        with self._cache_fetch_span():
            for j in range(h, m_cand):
                payloads = self.cache.fetch_warm(keys[j])
                if payloads is None:    # gone on every tier: truncate
                    break
                warm.append(payloads)
                m += 1
        if not ok(m):
            return 0, [], []
        return m, keys[:m], warm

    def _snapshot_match(self, prompt: np.ndarray):
        """Hybrid replay-free hit: ``(keys, hot cols, warm payload
        columns, snapshot)`` when EVERY full column of this page-aligned
        prompt is restorable (hot or warm) AND its boundary SSM snapshot
        exists; ``None`` otherwise.  Partial matches stay cold — replaying
        a suffix through the recurrence is not bit-equal to prefill, so
        the only exact hybrid hit is the whole prompt plus the captured
        state at its boundary."""
        bt = self.blk_tokens
        if len(prompt) < bt or len(prompt) % bt != 0:
            return None
        n = len(prompt) // bt
        keys = self._prefix_keys(prompt, n)
        snap = self.cache.get_snapshot(keys[-1])
        if snap is None:
            return None
        h = 0
        while h < n and keys[h] in self.cache.index:
            h += 1
        if any(not self.cache.has_warm(keys[j]) for j in range(h, n)):
            return None
        warm: List[List[bytes]] = []
        with self._cache_fetch_span():
            for j in range(h, n):
                payloads = self.cache.fetch_warm(keys[j])
                if payloads is None:
                    return None
                warm.append(payloads)
        return keys, h, warm, snap

    @_engine_span("page_upkeep", what="register_prefixes")
    def _register_prefixes(self, slots_prompts) -> None:
        """Index the freshly admitted slots' full page columns.

        One small device read of the page tables per admission round (rows
        are read per shard — ids may differ across shards, see module
        docstring).  Already-indexed keys were mapped shared and counted at
        map time; new keys start at refcount 1 (their owner slot).
        """
        if not self.prefix_sharing or not slots_prompts:
            return
        rows = np.asarray(self.state.kv.page_table)[:, 0]  # (tp, S, maxp)
        for slot, prompt, length in slots_prompts:
            keys = self._prefix_keys(prompt, length // self.blk_tokens)
            for c, key in enumerate(keys):
                if key in self.cache.index:
                    continue
                ids = rows[:, slot, c].copy()
                assert (ids >= 0).all(), (slot, c, ids)
                self.cache.insert(key, ids)
                self._slot_keys[slot].append(key)

    # -- slot release (tiered retention) -----------------------------------

    def _page_geometry(self) -> Tuple[int, int, int, int, int]:
        """(blk, w, k, esc_cap, npad) of one page in this pool — the
        payload geometry shared with the transport wire format."""
        codec = self.run_cfg.codec
        blk = codec.cache_block
        w = cache_mod.kv_width(self.cfg) if self.cfg.n_heads > 0 else 0
        n = blk * w
        if n == 0:
            return blk, 0, codec.k, 0, 0
        return blk, w, codec.k, codec.esc_capacity(n), packing.pad_to_lanes(n)

    @contextlib.contextmanager
    def _cache_fetch_span(self):
        """Engine-lane span over a warm/remote fetch burst.  Byte args
        are deltas of the PageCache counters, so summed trace bytes
        equal the ``cache.*`` stats counters by construction."""
        tr = self.tracer
        if not tr.enabled:
            yield
            return
        c = self.cache
        t0 = tr.now()
        p0, b0 = c.fetched_pages, c.fetched_bytes
        rp0, rb0 = c.remote_pages, c.remote_bytes
        try:
            yield
        finally:
            if c.fetched_pages != p0 or c.remote_pages != rp0:
                tr.emit("cache_fetch", cat="cache", pid=self.name,
                        tid=ENGINE_LANE, t0=t0, t1=tr.now(),
                        args={"pages": c.fetched_pages - p0,
                              "bytes": c.fetched_bytes - b0,
                              "remote_pages": c.remote_pages - rp0,
                              "remote_bytes": c.remote_bytes - rb0})

    def _spill_slots(self, slots: List[int], rows: np.ndarray) -> None:
        """Export and spill every page column whose LAST reference is
        being released — the hot -> warm handoff, run BEFORE the refcount
        drop while the releasing slot's page-table row still addresses
        the pages (an evicted column is in no row, so spilling later
        would be impossible).  Columns already warm skip the export."""
        tr = self.tracer
        t0 = tr.now()
        p0, b0 = self.cache.spilled_pages, self.cache.spilled_bytes
        holds: Dict[bytes, int] = {}
        for s in slots:
            for key in self._slot_keys[s]:
                holds[key] = holds.get(key, 0) + 1
        codec_on = bool(self.run_cfg.codec.cache)
        fields = (("signman", "planes", "dict_syms", "esc_pos", "esc_raw")
                  if codec_on else ("raw_pages",))
        done = set()
        for s in slots:
            colof = {int(rows[0, s, c]): c for c in range(self._maxp)
                     if rows[0, s, c] >= 0}
            pend = []
            for key in self._slot_keys[s]:
                if (key in done or self.cache.has_warm(key)
                        or self.cache.ref.get(key, 0) != holds[key]):
                    continue          # other refs remain: stays hot there
                ids = self.cache.index.get(key)
                c = None if ids is None else colof.get(int(ids[0]))
                if c is None:
                    continue          # duplicate column owned elsewhere
                pend.append((key, c))
            if not pend:
                continue
            span = max(c for _, c in pend) + 1
            n = 1
            while n < span:           # power-of-two export windows keep
                n *= 2                # the jit cache at O(log maxp)
            n = min(n, self._maxp)
            kvw, _, _ = self._export_for(n)(
                self.state, jnp.asarray(s, jnp.int32),
                jnp.asarray(0, jnp.int32))
            kv = {f: np.asarray(getattr(kvw, f)) for f in fields}
            for key, c in pend:
                payloads = [transport.page_payload(kv, codec_on, t, l, c)
                            for t in range(self.tp)
                            for l in range(self.cfg.n_layers)]
                self.cache.spill(key, payloads)
                done.add(key)
        if tr.enabled and self.cache.spilled_pages != p0:
            tr.emit("cache_spill", cat="cache", pid=self.name,
                    tid=ENGINE_LANE, t0=t0, t1=tr.now(),
                    args={"pages": self.cache.spilled_pages - p0,
                          "bytes": self.cache.spilled_bytes - b0})

    @_engine_span("page_upkeep", what="free_slots")
    def _free_slots(self, slots: List[int]) -> None:
        """Evict ``slots`` through the tiered PageCache: spill last-copy
        columns to the warm store, drop the slots' references (columns at
        zero are RETAINED on the device under the cache's LRU — the
        tentpole change from free-at-zero), and free only the pages no
        index entry claims (decode-grown columns, duplicates; all pages
        when sharing is off).  Double release is rejected loudly —
        freeing a slot that is not occupied would hand its (possibly
        shared) pages back to the allocator while another sequence still
        reads them."""
        slots = [int(s) for s in slots]
        for s in slots:
            if not self._slot_busy[s]:
                raise RuntimeError(
                    f"double release: slot {s} is not occupied")
        mask = np.zeros((self.n_slots,), bool)
        mask[slots] = True
        if not self.prefix_sharing or self.state.kv is None:
            self.state = self._release(self.state, jnp.asarray(mask))
        else:
            rows = np.asarray(self.state.kv.page_table)[:, 0]  # (tp,S,maxp)
            self._spill_slots(slots, rows)        # 1) hot -> warm handoff
            for s in slots:                       # 2) drop references
                for key in self._slot_keys[s]:
                    self.cache.release(key)       # zero-ref -> retained
            free = np.zeros((self.tp, self._n_pages), bool)
            for s in slots:                       # 3) free unindexed pages
                for t in range(self.tp):
                    keep = {int(self.cache.index[key][t])
                            for key in self._slot_keys[s]
                            if key in self.cache.index}
                    for p in rows[t, s]:
                        if p >= 0 and int(p) not in keep:
                            free[t, int(p)] = True
                self._slot_keys[s] = []
            self.state = self._release_shared_for()(
                self.state, jnp.asarray(mask), jnp.asarray(free))
        self._slot_busy[mask] = False

    def _lfp(self, length: int, t: int) -> int:
        """Full page columns shard ``t`` holds at sequence ``length`` —
        host arithmetic mirroring the device flush rule."""
        if length <= 0:
            return 0
        blk = self.run_cfg.codec.cache_block
        return max((length - 1 - t) // self.tp + 1, 0) // blk

    def _page_growth(self, l0: int, l1: int) -> int:
        """Worst-per-shard new full pages when a slot grows l0 -> l1."""
        return max(self._lfp(l1, t) - self._lfp(l0, t)
                   for t in range(self.tp))

    @_engine_span("page_upkeep", what="ensure_free_pages")
    def _ensure_free_pages(self, need: int) -> None:
        """Make room for ``need`` fresh pages per shard/layer pool by
        evicting retained zero-ref columns (LRU order) from the hot tier.
        Retention must never cause an allocation failure the free-at-zero
        engine could not have had — this is the pool-pressure valve,
        called before every page-allocating dispatch.  Spilling happened
        at release time, so eviction is pure ``page_used`` clearing."""
        if need <= 0 or not self.cache.lru or self.state.kv is None:
            return
        used = np.asarray(self.state.kv.page_used)      # (tp, L, P)
        free = self._n_pages - int(used.sum(axis=-1).max())
        if free >= need:
            return
        fmask = np.zeros((self.tp, self._n_pages), bool)
        n = 0
        while free + n < need and self.cache.lru:
            _, ids = self.cache.evict_lru()
            for t in range(self.tp):
                fmask[t, int(ids[t])] = True
            n += 1
        self.state = self._release_shared_for()(
            self.state, jnp.asarray(np.zeros((self.n_slots,), bool)),
            jnp.asarray(fmask))

    def drop_cache(self) -> int:
        """Evict every RETAINED (zero-ref) column and clear the warm +
        snapshot tiers — the explicit teardown free-at-zero used to do
        implicitly at the last release.  Live slots are untouched.
        Returns the number of hot columns dropped."""
        if not self.prefix_sharing or self.state.kv is None:
            return 0
        ids = self.cache.drop_retained()
        if ids:
            fmask = np.zeros((self.tp, self._n_pages), bool)
            for v in ids:
                for t in range(self.tp):
                    fmask[t, int(v[t])] = True
            self.state = self._release_shared_for()(
                self.state, jnp.asarray(np.zeros((self.n_slots,), bool)),
                jnp.asarray(fmask))
        return len(ids)

    # -- metrics -----------------------------------------------------------

    def _pages_for_length(self, length: int) -> int:
        """Pages one sequence of ``length`` tokens occupies (all layers,
        summed over shards) — pure host arithmetic, mirroring the device's
        flush rule (a page exists exactly per full block of owned slots),
        so the serving loop never syncs device state for its metrics."""
        if self.cfg.n_heads == 0 or length <= 0:
            return 0
        blk = self.run_cfg.codec.cache_block
        per_shard = sum(
            max((length - 1 - t) // self.tp + 1, 0) // blk
            for t in range(self.tp))
        return per_shard * self.cfg.n_layers

    def _shared_page_overcount(self) -> int:
        """Pages counted multiple times by the per-slot sum because they
        are prefix-shared: (ref - 1) per indexed column, in physical pages
        (x tp shards x n_layers)."""
        over = sum(max(r - 1, 0) for r in self._prefix_ref.values())
        return over * self.tp * self.cfg.n_layers

    def _pages_in_use(self) -> int:
        """Device-truth page count (syncs; for tests/inspection only)."""
        if self.state.kv is None:
            return 0
        return int(np.asarray(self.state.kv.page_used).sum())

    # -- the serving loop --------------------------------------------------
    #
    # The loop is factored into methods over an explicit ``_LoopState`` so
    # the disaggregated replicas (repro.serve.disagg) can drive admission
    # and decode separately; ``run`` below composes them into the original
    # monolithic engine (token streams are unchanged by the refactor —
    # the identity tests in tests/test_serve_engine.py are the proof).

    def _req_eos(self, req: Request) -> Optional[int]:
        return req.eos_id if req.eos_id is not None else self.eos_id

    def _req_stops(self, req: Request) -> Tuple[Tuple[int, ...], ...]:
        return (_norm_stops(req.stop_seqs) if req.stop_seqs is not None
                else self.stop_seqs)

    def _new_loop(self) -> _LoopState:
        return _LoopState(
            slot_req=[None] * self.n_slots,
            done=[False] * self.n_slots,
            reason=[""] * self.n_slots,
            emitted={}, admit_t={}, results={},
            cur=np.zeros((self.n_slots, 1), np.int32),
            slot_len=[0] * self.n_slots)

    def _track_peak(self, ls: _LoopState) -> None:
        pages = sum(self._pages_for_length(ls.slot_len[s])
                    for s, r in enumerate(ls.slot_req) if r is not None)
        if self.prefix_sharing:
            pages -= self._shared_page_overcount()
        ls.peak_pages = max(ls.peak_pages, pages)

    def _check_done(self, ls: _LoopState, s: int, req: Request) -> None:
        """Host-side termination check after each emitted token.  Priority
        when several fire on the same token: eos > stop_string > budget.
        Stop sequences are a rolling suffix match over the emitted tokens
        (evaluated as the host walks each fused window's token block, so a
        stop inside a window finishes the request at the match position and
        the slot idles to the window boundary — same convention as EOS)."""
        toks = ls.emitted[req.uid]
        eos = self._req_eos(req)
        if eos is not None and toks and toks[-1] == eos:
            ls.done[s], ls.reason[s] = True, "eos"
            return
        for ss in self._req_stops(req):
            if len(toks) >= len(ss) and toks[-len(ss):] == list(ss):
                ls.done[s], ls.reason[s] = True, "stop_string"
                return
        if len(toks) >= req.max_new_tokens:
            ls.done[s], ls.reason[s] = True, "budget"

    def _finish_ready(self, ls: _LoopState) -> List[RequestResult]:
        """Harvest done slots into results and evict them; returns the
        newly finished results (the disagg router forwards them)."""
        ready = [s for s, req in enumerate(ls.slot_req)
                 if req is not None and ls.done[s]]
        if not ready:
            return []
        fresh = []
        with self.tracer.span("finish", pid=self.name, tid=ENGINE_LANE,
                              finished=len(ready)):
            for s in ready:
                req = ls.slot_req[s]
                now = time.perf_counter()
                ft = ls.first_tok_t.pop(req.uid, None)
                sub = self.scheduler.submit_t.pop(req.uid, None)
                ttft = 0.0
                if ft is not None:
                    ttft = ft - (sub if sub is not None
                                 else ls.admit_t[req.uid])
                    ls.ttft_s[req.uid] = ttft
                res = RequestResult(
                    uid=req.uid, prompt_len=len(req.prompt),
                    tokens=ls.emitted[req.uid][:req.max_new_tokens],
                    latency_s=now - ls.admit_t[req.uid],
                    stop_reason=ls.reason[s], ttft_s=ttft)
                self.tracer.request_end(
                    req.uid, args={"stop_reason": res.stop_reason,
                                   "tokens": len(res.tokens)})
                ls.results[req.uid] = res
                fresh.append(res)
                ls.slot_req[s] = None
                ls.done[s], ls.reason[s] = False, ""
            self._free_slots(ready)
        return fresh

    def _free_slot_ids(self, ls: _LoopState) -> List[int]:
        return [s for s in range(self.n_slots) if ls.slot_req[s] is None]

    def _warm_wire(self, warm: List[List[bytes]]):
        """Assemble fetched warm payload columns into one import-ready
        ``PageWire`` (global view, leading shard axis; zero ring — warm
        restores are page-aligned by construction, so the partial-block
        ring is never read before it is overwritten)."""
        blk, w, k, esc_cap, npad = self._page_geometry()
        codec_on = bool(self.run_cfg.codec.cache)
        tp, L = self.tp, self.cfg.n_layers
        kv = transport.empty_page_fields(codec_on, tp, L, len(warm),
                                         blk, w, k, esc_cap, npad)
        for c, payloads in enumerate(warm):
            i = 0
            for t in range(tp):        # shard-major, the spill order
                for l in range(L):
                    transport.scatter_page_payload(
                        kv, codec_on, t, l, c, payloads[i], blk=blk,
                        w=w, k=k, esc_cap=esc_cap, npad=npad)
                    i += 1
        ring = jnp.zeros((tp, L, blk, w), jnp.bfloat16)
        if codec_on:
            return cache_mod.PageWire(
                signman=jnp.asarray(kv["signman"]),
                planes=jnp.asarray(kv["planes"]),
                dict_syms=jnp.asarray(kv["dict_syms"]),
                esc_pos=jnp.asarray(kv["esc_pos"]),
                esc_raw=jnp.asarray(kv["esc_raw"]),
                raw_pages=None, ring=ring)
        return cache_mod.PageWire(
            signman=None, planes=None, dict_syms=None, esc_pos=None,
            esc_raw=None, raw_pages=jnp.asarray(kv["raw_pages"]),
            ring=ring)

    def _admit_shared(self, ls: _LoopState, s: int, req: Request, m: int,
                      keys: List[bytes],
                      warm: List[List[bytes]]) -> None:
        """Prefix-cache hit: map the hot columns, import the warm ones
        (fetched payloads, no prefill FLOPs), replay the suffix.  Hot
        keys are acquired BEFORE the pool-pressure valve runs so a
        retained column this admission is about to map cannot be evicted
        to make room for its own warm import."""
        h = m - len(warm)
        ls.admit_t.setdefault(req.uid, time.perf_counter())
        self.tracer.stage(req.uid, "admit",
                          args={"mode": "warm" if warm else "shared",
                                "cols": m, "warm_cols": len(warm)})
        ids = np.zeros((self.tp, self._maxp), np.int32)
        for c in range(h):
            ids[:, c] = self.cache.acquire(keys[c])
            self._slot_keys[s].append(keys[c])
        if warm:
            self._ensure_free_pages(len(warm))
        if h:
            base_cols = h if warm else m    # import (below) sets the
            self.state = self._map_shared_for()(  # final length otherwise
                self.state, jnp.asarray(s, jnp.int32), jnp.asarray(ids),
                jnp.asarray(h, jnp.int32),
                jnp.asarray(base_cols * self.blk_tokens, jnp.int32))
        if warm:
            self.state = self._import_for(len(warm))(
                self.state, jnp.asarray(s, jnp.int32),
                self._warm_wire(warm), None,
                jnp.asarray(m * self.blk_tokens, jnp.int32),
                jnp.asarray(h, jnp.int32))
        ls.shared_hits += m
        ls.slot_req[s] = req
        self._slot_busy[s] = True
        ls.slot_len[s] = m * self.blk_tokens
        ls.emitted[req.uid] = []

    def _admit_snapshot(self, ls: _LoopState, s: int, req: Request,
                        keys: List[bytes], h: int,
                        warm: List[List[bytes]], snap) -> None:
        """Hybrid snapshot hit: map/import ALL page columns and restore
        the boundary SSM state — zero prefill FLOPs, zero replay.  The
        first greedy token comes from the snapshot, computed by the
        original admission at the same boundary, so the stream is
        bit-exact by construction."""
        n, nr = len(keys), len(warm)
        ls.admit_t.setdefault(req.uid, time.perf_counter())
        self.tracer.stage(req.uid, "admit",
                          args={"mode": "snapshot", "cols": n,
                                "warm_cols": nr})
        ids = np.zeros((self.tp, self._maxp), np.int32)
        for c in range(h):
            ids[:, c] = self.cache.acquire(keys[c])
            self._slot_keys[s].append(keys[c])
        if nr:
            self._ensure_free_pages(nr)
        if h:
            base_cols = h if nr else n
            self.state = self._map_shared_for()(
                self.state, jnp.asarray(s, jnp.int32), jnp.asarray(ids),
                jnp.asarray(h, jnp.int32),
                jnp.asarray(base_cols * self.blk_tokens, jnp.int32))
        ssm_dev = SSMState(*(jnp.asarray(a) for a in snap["ssm"]))
        if nr:
            self.state = self._import_for(nr)(
                self.state, jnp.asarray(s, jnp.int32),
                self._warm_wire(warm), ssm_dev,
                jnp.asarray(n * self.blk_tokens, jnp.int32),
                jnp.asarray(h, jnp.int32))
        else:
            self.state = self._restore_ssm_for()(
                self.state, jnp.asarray(s, jnp.int32), ssm_dev)
        t = int(snap["g0"])
        ls.shared_hits += n
        ls.slot_req[s] = req
        self._slot_busy[s] = True
        ls.slot_len[s] = n * self.blk_tokens
        ls.emitted[req.uid] = [t]
        ls.first_tok_t[req.uid] = time.perf_counter()
        self.tracer.stage_end(req.uid)
        ls.cur[s] = t
        self._check_done(ls, s, req)

    def _admit_cold_batch(self, ls: _LoopState, batch: List[Request],
                          slots: List[int], trunk: int, replays) -> None:
        """One vmapped-prefill dispatch admits the whole bucket."""
        fn = self._admit_for(trunk, len(batch))
        prompts = np.stack([r.prompt[:trunk] for r in batch])
        tr = self.tracer
        w0 = time.perf_counter()
        for r in batch:
            ls.admit_t.setdefault(r.uid, w0)
            tr.stage(r.uid, "admit", args={"mode": "cold",
                                           "bucket": trunk})
        blk = self.run_cfg.codec.cache_block
        self._ensure_free_pages(len(batch) * ((trunk // self.tp) // blk))
        with tr.span("admit_batch", pid=self.name, tid=ENGINE_LANE,
                     bucket=trunk, batch=len(batch)) as sp:
            toks, self.state = fn(self.params, self.state,
                                  jnp.asarray(prompts, jnp.int32),
                                  jnp.asarray(slots, jnp.int32))
            toks = np.asarray(toks)
        ls.admit_dispatches += 1
        ls.admit_window_s.append(sp.seconds)
        now = sp.end
        for j, (req, s) in enumerate(zip(batch, slots)):
            ls.slot_req[s] = req
            self._slot_busy[s] = True
            ls.slot_len[s] = trunk
            tail = req.prompt[trunk:]
            if len(tail):
                ls.emitted[req.uid] = []
                replays.append((s, np.asarray(tail, np.int32)))
            else:
                t = int(toks[j, 0])
                ls.emitted[req.uid] = [t]
                ls.first_tok_t[req.uid] = now
                tr.stage_end(req.uid)
                ls.cur[s] = t
                self._check_done(ls, s, req)
        if self.admit_progress_cb is not None:
            self.admit_progress_cb(ls)   # trunk pages exist: stream them

    def _run_replays(self, ls: _LoopState, replays) -> None:
        """Feed all admitted slots' leftover prompt tokens through
        fused paged replay dispatches (heterogeneous lengths share the
        dispatch via the feed mask); each slot's first generated token
        comes from the step consuming its last prompt token."""
        rem = {s: tail for s, tail in replays}
        off = {s: 0 for s in rem}
        ls.replay_tokens += sum(len(tail) for tail in rem.values())
        tr = self.tracer
        for s in rem:
            tr.stage(ls.slot_req[s].uid, "replay",
                     args={"tail_tokens": len(rem[s])})
        while rem:
            longest = max(len(rem[s]) - off[s] for s in rem)
            k = self._fuse_steps(longest)   # same policy as decode
            toks = np.zeros((k, self.n_slots, 1), np.int32)
            feed = np.zeros((k, self.n_slots), bool)
            for s in rem:
                t_s = rem[s][off[s]:off[s] + k]
                toks[:len(t_s), s, 0] = t_s
                feed[:len(t_s), s] = True
            if self.cache.lru:              # pool-pressure valve
                self._ensure_free_pages(sum(
                    self._page_growth(
                        ls.slot_len[s],
                        ls.slot_len[s] + min(k, len(rem[s]) - off[s]))
                    for s in rem))
            filled, compressed = self._flush_work(ls, feed)
            with tr.span("replay_window", pid=self.name, tid=ENGINE_LANE,
                         steps=k, slots=len(rem), rings_filled=filled,
                         rings_compressed=compressed) as sp:
                seq, self.state = self._replay_for(k)(
                    self.params, self.state, jnp.asarray(toks),
                    jnp.asarray(feed))
                seq = np.asarray(seq)
            ls.replay_dispatches += 1
            ls.admit_window_s.append(sp.seconds)
            now = sp.end
            for s in list(rem):
                n_fed = min(k, len(rem[s]) - off[s])
                off[s] += n_fed
                ls.slot_len[s] += n_fed
                if off[s] == len(rem[s]):
                    req = ls.slot_req[s]
                    t = int(seq[n_fed - 1, s, 0])
                    ls.emitted[req.uid] = [t]
                    ls.first_tok_t[req.uid] = now
                    tr.stage_end(req.uid)
                    ls.cur[s] = t
                    self._check_done(ls, s, req)
                    del rem[s]
            self._track_peak(ls)
            if self.admit_progress_cb is not None:
                self.admit_progress_cb(ls)   # ring flushes filled pages

    @_engine_span("admit_phase")
    def _admit_phase(self, ls: _LoopState) -> None:
        """Admit until slots or admissible requests run out: shared
        prefix hits first (queue order), then one batched cold
        dispatch per length bucket; finally replay leftover prompt
        tokens and index the new slots' full columns."""
        replays = []
        new_slots = []
        blocked = set()       # first-column keys cold-admitted now
        progress = True
        while progress:
            progress = False
            free = self._free_slot_ids(ls)
            if not free or not len(self.scheduler):
                break
            if self.prefix_sharing:       # pass A: prefix-cache hits
                hybrid = self.cfg.ssm is not None
                rest = deque()
                q = self.scheduler.queue
                while q and free:
                    req = q.popleft()
                    if hybrid:            # whole-prompt snapshot hits only
                        hit = self._snapshot_match(req.prompt)
                        if hit is not None:
                            s = free.pop(0)
                            self._admit_snapshot(ls, s, req, *hit)
                            new_slots.append(s)
                            progress = True
                        else:
                            rest.append(req)
                        continue
                    m, mkeys, warm = self._prefix_match_cols(req.prompt)
                    if m >= 1:
                        s = free.pop(0)
                        self._admit_shared(ls, s, req, m, mkeys, warm)
                        replays.append(
                            (s, np.asarray(req.prompt[m * self.blk_tokens:],
                                           np.int32)))
                        new_slots.append(s)
                        progress = True
                    else:
                        rest.append(req)
                while rest:
                    q.appendleft(rest.pop())
            free = self._free_slot_ids(ls)
            if free and len(self.scheduler):  # pass B: one cold bucket
                batch: List[Request] = []
                rest = deque()
                bucket = None
                q = self.scheduler.queue
                while q:
                    req = q.popleft()
                    b = self._bucket_of(len(req.prompt))
                    fk = (self._prefix_keys(req.prompt, 1)[0]
                          if self.prefix_sharing and
                          len(req.prompt) > self.blk_tokens else None)
                    ok = len(batch) < len(free)
                    if ok and fk is not None and fk in blocked:
                        ok = False    # dedupe: hits the index next round
                    if ok and bucket is not None and b != bucket:
                        ok = False
                    if ok:
                        bucket = b
                        batch.append(req)
                        if fk is not None:
                            blocked.add(fk)
                    else:
                        rest.append(req)
                while rest:
                    q.appendleft(rest.pop())
                if batch:
                    slots = free[:len(batch)]
                    self._admit_cold_batch(ls, batch, slots, bucket,
                                           replays)
                    new_slots.extend(slots)
                    progress = True
        self._run_replays(ls, replays)
        ls.prompt_tokens += sum(len(ls.slot_req[s].prompt)
                                for s in new_slots)
        self._register_prefixes(
            [(s, ls.slot_req[s].prompt, ls.slot_len[s]) for s in new_slots])
        if self.prefix_sharing and self.cfg.ssm is not None:
            self._capture_snapshots(ls, new_slots)

    def _capture_snapshots(self, ls: _LoopState,
                           new_slots: List[int]) -> None:
        """Capture boundary SSM snapshots for tail-less page-aligned
        admissions (hybrids only): the recurrent state after consuming
        exactly the prompt, plus the first greedy token — the unit that
        makes a later identical prompt replay-free.  One device read of
        the SSM leaves per admission round, only when needed."""
        todo = []
        for s in new_slots:
            req = ls.slot_req[s]
            if req is None:
                continue
            ln = ls.slot_len[s]
            if (ln != len(req.prompt) or ln % self.blk_tokens != 0
                    or not ls.emitted.get(req.uid)):
                continue
            keys = self._prefix_keys(req.prompt, ln // self.blk_tokens)
            if self.cache.get_snapshot(keys[-1]) is not None:
                continue
            todo.append((s, keys[-1], int(ls.emitted[req.uid][0])))
        if not todo:
            return
        leaves = [np.asarray(a) for a in self.state.ssm]
        for s, key, g0 in todo:
            snap = SSMState(*(a[:, :, s].copy() for a in leaves))
            self.cache.put_snapshot(key, {"ssm": snap, "g0": g0})

    def _decode_window(self, ls: _LoopState) -> None:
        """One fused decode dispatch: K steps as one scan, K bounded by the
        earliest slot-finish event computed host-side from the known token
        budgets — so eviction and admission still happen at window
        boundaries and token streams are byte-identical to the
        one-dispatch-per-token loop.  An EOS / stop-string inside a window
        finishes that request at its match position (its slot idles until
        the window ends; other slots are independent, so no stream
        changes — only the eviction happens at the boundary)."""
        live = ls.live_slots()
        if not live:
            return
        bound = min(ls.slot_req[s].max_new_tokens - len(ls.emitted[
            ls.slot_req[s].uid]) for s in live)
        n_steps = self._fuse_steps(bound)
        if self.cache.lru:                  # pool-pressure valve
            self._ensure_free_pages(sum(
                self._page_growth(ls.slot_len[s], ls.slot_len[s] + n_steps)
                for s in live))
        tr = self.tracer
        fed = np.zeros((n_steps, self.n_slots), bool)
        fed[:, live] = True
        filled, compressed = self._flush_work(ls, fed)
        with tr.span("decode_window", pid=self.name, tid=ENGINE_LANE,
                     steps=n_steps, slots=len(live),
                     weight_bytes=n_steps * self._weight_bytes[0],
                     rings_filled=filled,
                     rings_compressed=compressed) as sp:
            seq, self.state = self._decode_for(n_steps)(
                self.params, self.state, jnp.asarray(ls.cur))
            seq = np.asarray(seq)                 # (K, n_slots, 1)
        ls.steps += n_steps
        ls.dispatches += 1
        ls.decode_window_s.append(sp.seconds)
        if tr.enabled:
            for s in live:
                tr.request_span(ls.slot_req[s].uid, "decode", t0=sp.t0,
                                t1=sp.t1, args={"steps": n_steps})
        with tr.span("token_walk", pid=self.name, tid=ENGINE_LANE):
            for t_i in range(n_steps):
                for s in live:
                    req = ls.slot_req[s]
                    ls.slot_len[s] += 1  # device appends past host-done
                    if ls.done[s]:
                        continue
                    t = int(seq[t_i, s, 0])
                    ls.emitted[req.uid].append(t)
                    ls.cur[s] = t
                    self._check_done(ls, s, req)
                self._track_peak(ls)

    def _flush_work(self, ls: _LoopState, fed: np.ndarray
                    ) -> Tuple[int, int]:
        """``cache_mod.flush_work`` of one dispatch whose step j writes
        the slots ``fed[j]`` at their lengths + j; added to the loop's
        totals and returned for the dispatch's span."""
        if self.state.kv is None:
            return 0, 0
        lengths = (np.asarray(ls.slot_len)[None, :]
                   + np.arange(fed.shape[0])[:, None])
        filled, compressed = cache_mod.flush_work(
            lengths, fed, self.run_cfg.codec.cache_block, self.tp)
        ls.rings_filled += filled
        ls.rings_compressed += compressed
        return filled, compressed

    def sync_metrics(self, ls: _LoopState,
                     wall: Optional[float] = None) -> MetricsRegistry:
        """Refresh this engine's metrics registry from the loop state —
        absolute values, safe to call repeatedly (the METRICS RPC calls
        it on every snapshot; ``_stats`` reads through it, which is what
        makes ``ServeStats`` a view over the registry)."""
        reg = self.registry
        c = reg.counter
        n_tok = sum(len(r.tokens) for r in ls.results.values())
        c("serve.requests").set(len(ls.results))
        c("serve.tokens").set(n_tok)
        c("serve.decode_steps").set(ls.steps)
        c("serve.decode_dispatches").set(ls.dispatches)
        c("serve.admit_dispatches").set(ls.admit_dispatches)
        c("serve.replay_dispatches").set(ls.replay_dispatches)
        c("serve.admit_compiles").set(self.n_admit_compiles)
        c("serve.shared_page_hits").set(ls.shared_hits)
        c("serve.prompt_tokens_admitted").set(ls.prompt_tokens)
        c("serve.replay_tokens").set(ls.replay_tokens)
        reg.gauge("serve.peak_pages", agg="max").set(ls.peak_pages)
        reg.gauge("serve.pool_bytes").set(
            engine.paged_state_nbytes(self.state))
        if wall is not None:
            reg.gauge("serve.wall_s", agg="max").set(wall)
        for k, v in self.cache.counters().items():
            c(f"cache.{k}").set(v)
        c("cache.rings_filled").set(ls.rings_filled)
        c("cache.rings_compressed").set(ls.rings_compressed)
        reg.gauge("weights.bytes_per_step", agg="max").set(
            self._weight_bytes[0])
        reg.gauge("weights.raw_bytes_per_step", agg="max").set(
            self._weight_bytes[1])
        reg.gauge("weights.compressed", agg="max").set(
            int(self.compress_weights))
        c("weights.hbm_bytes").set(ls.steps * self._weight_bytes[0])
        reg.histogram("latency.request_s").set_values(
            [r.latency_s for r in ls.results.values()])
        reg.histogram("latency.ttft_s").set_values(list(ls.ttft_s.values()))
        reg.histogram("latency.admit_window_s").set_values(
            ls.admit_window_s)
        reg.histogram("latency.decode_window_s").set_values(
            ls.decode_window_s)
        return reg

    def _stats(self, ls: _LoopState, wall: float) -> ServeStats:
        stored_pb, raw_pb = cache_mod.page_bytes(self.cfg, self.run_cfg)
        reg = self.sync_metrics(ls, wall)
        v = reg.value
        lat = summarize_latencies(reg.values_of("latency.request_s"))
        ttft = summarize_latencies(reg.values_of("latency.ttft_s"))
        admitw = summarize_latencies(reg.values_of("latency.admit_window_s"))
        decw = summarize_latencies(reg.values_of("latency.decode_window_s"))
        n_req, n_tok = v("serve.requests"), v("serve.tokens")
        steps = v("serve.decode_steps")
        return ServeStats(
            n_requests=n_req, n_tokens=n_tok,
            decode_steps=steps,
            n_dispatches=v("serve.decode_dispatches"),
            n_admit_dispatches=v("serve.admit_dispatches"),
            n_replay_dispatches=v("serve.replay_dispatches"),
            n_admit_compiles=v("serve.admit_compiles"),
            shared_page_hits=v("serve.shared_page_hits"),
            wall_s=wall,
            requests_per_s=n_req / max(wall, 1e-9),
            tokens_per_s=n_tok / max(wall, 1e-9),
            peak_pages=v("serve.peak_pages"),
            peak_cache_bytes=v("serve.peak_pages") * stored_pb,
            peak_cache_raw_bytes=v("serve.peak_pages") * raw_pb,
            mean_latency_s=lat["mean"],
            latency_p50_s=lat["p50"], latency_p95_s=lat["p95"],
            decode_backend=kernel_ops.resolve_decode_backend(
                self.run_cfg.codec),
            cache_hot_hits=v("cache.hot_hits"),
            cache_spilled_pages=v("cache.spilled_pages"),
            cache_spilled_bytes=v("cache.spilled_bytes"),
            cache_fetched_pages=v("cache.fetched_pages"),
            cache_fetched_bytes=v("cache.fetched_bytes"),
            cache_reprefill_cols=v("cache.reprefill_cols"),
            cache_evicted_cols=v("cache.evicted_cols"),
            weights_compressed=self.compress_weights,
            weight_backend=self.weight_backend,
            weight_bytes_per_step=v("weights.bytes_per_step"),
            weight_raw_bytes_per_step=v("weights.raw_bytes_per_step"),
            ttft_mean_s=ttft["mean"], ttft_p50_s=ttft["p50"],
            ttft_p95_s=ttft["p95"],
            admit_window_mean_s=admitw["mean"],
            decode_window_mean_s=decw["mean"])

    def probe_logits(self, requests: List[Request]) -> np.ndarray:
        """Admit ``requests`` (at most ``n_slots``, through the normal
        admission path) and return the logits of the next decode step,
        (n_slots, vocab) f32, without advancing the served state — the
        served path's numerics, for checks against a reference engine."""
        if len(requests) > self.n_slots:
            raise ValueError("probe_logits admits at most n_slots requests")
        for r in requests:
            self.scheduler.submit(r)
        ls = self._new_loop()
        self._admit_phase(ls)

        def step(pp, st_g, toks):
            logits, _ = engine.paged_decode_step(
                self.cfg, self.run_cfg, pp, self.dims, self._squeeze(st_g),
                toks, self.tp)
            return logits.astype(jnp.float32)

        fn = jax.jit(cl.shmap(step, self.mesh,
                              (self._pspecs, self._sspec, P(None, None)),
                              P(None, None, "model")))
        out = fn(self.params, self.state, jnp.asarray(ls.cur))
        return np.asarray(out)[:, 0, :self.cfg.vocab_size]

    def run(self, requests: List[Request]
            ) -> Tuple[List[RequestResult], ServeStats]:
        """Serve a request list to completion; returns results in input
        order plus engine-level stats.  See ``_decode_window`` for the
        fused-dispatch / window-boundary semantics."""
        uids = [r.uid for r in requests]
        if len(set(uids)) != len(uids):
            raise ValueError("request uids must be unique (token streams "
                             "are keyed by uid)")
        for r in requests:
            self.scheduler.submit(r)
        ls = self._new_loop()
        t0 = time.perf_counter()
        while len(self.scheduler) or ls.live_slots():
            self._admit_phase(ls)
            self._track_peak(ls)
            self._finish_ready(ls)
            self._decode_window(ls)
            self._finish_ready(ls)
        wall = time.perf_counter() - t0
        stats = self._stats(ls, wall)
        return [ls.results[r.uid] for r in requests], stats


# ---------------------------------------------------------------------------
# demo helpers (shared by launch/serve.py, examples/serve_lm.py)
# ---------------------------------------------------------------------------

def demo_serving_setup(run: RunConfig, vocab_size: int, tp: int,
                       prompt_len: int, new_tokens: int, n_requests: int,
                       seed: int = 0):
    """(run', max_len, requests) for a demo request stream.

    Shrinks the cache block so the paged pool is exercised at demo prompt
    sizes and generates a mixed-length queue with SHARED PREFIXES: two base
    prompts cycle, repeats of a base reuse its exact tokens, and budgets
    are staggered (long-prompt requests run longer).  Zero-ref prefix
    columns stay RETAINED in the tiered PageCache, so even repeats that
    admit after the original released still hit the hot tier (watch
    ``shared_page_hits`` and ``cache_hot_hits``).
    """
    rng = np.random.default_rng(seed)
    blk = max(4, (prompt_len // tp) // 4)
    run = dataclasses.replace(
        run, codec=dataclasses.replace(run.codec, cache_block=blk))
    max_len = prompt_len + 2 * new_tokens + blk * tp
    lens = [prompt_len, max(tp, prompt_len // 2 // tp * tp)]
    bases = [rng.integers(0, vocab_size, (n,)).astype(np.int32)
             for n in lens]
    reqs = [Request(uid=i, prompt=bases[i % len(bases)],
                    max_new_tokens=new_tokens * (2 if i % 2 == 0 else 1))
            for i in range(n_requests)]
    return run, max_len, reqs


def format_stats(st: ServeStats) -> str:
    """Four-line human summary of a serving run (demo output)."""
    return (f"{st.n_requests} reqs, {st.decode_steps} decode steps in "
            f"{st.n_dispatches} dispatches ({st.decode_backend} backend), "
            f"{st.requests_per_s:.2f} req/s, {st.tokens_per_s:.1f} tok/s "
            f"(incl. compile)\n"
            f"admission: {st.n_admit_dispatches} batched prefill dispatches "
            f"+ {st.n_replay_dispatches} fused replay dispatches "
            f"({st.n_admit_compiles} admit compiles), "
            f"{st.shared_page_hits} shared-prefix page hits\n"
            f"paged cache peak {st.peak_pages} pages: "
            f"{st.peak_cache_bytes / 1e3:.1f} kB stored / "
            f"{st.peak_cache_raw_bytes / 1e3:.1f} kB raw "
            f"({st.cache_ratio:.2f}x); mean request latency "
            f"{st.mean_latency_s * 1e3:.0f} ms (incl. each bucket's "
            f"first-use compile)\n"
            f"retention: {st.cache_hot_hits} hot-tier re-acquires, "
            f"{st.cache_spilled_pages} pages spilled "
            f"({st.cache_spilled_bytes / 1e3:.1f} kB), "
            f"{st.cache_fetched_pages} fetched back "
            f"({st.cache_fetched_bytes / 1e3:.1f} kB), "
            f"{st.cache_evicted_cols} columns evicted, "
            f"{st.cache_reprefill_cols} re-prefills\n"
            f"weights: "
            f"{'packed' if st.weights_compressed else 'raw bf16'} "
            f"({st.weight_backend} backend), "
            f"{st.weight_bytes_per_step / 1e3:.1f} kB HBM per decode step / "
            f"{st.weight_raw_bytes_per_step / 1e3:.1f} kB raw "
            f"({st.weight_ratio:.2f}x)")
