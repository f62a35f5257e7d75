"""Serving engines: dense-batch and continuous-paged.

Serving architecture — two execution models:

* **Dense batch** (``Engine``): one synchronous fixed-shape batch at a time.
  Requests are padded to a power-of-two bucket and a shared prompt width;
  every request gets a dense per-request KV slab sized ``prompt + max_new``
  and the whole batch decodes for ``max_new_tokens`` steps regardless of
  where EOS lands. Simple, one jit cache entry per (bucket, prompt-len),
  ideal for offline evaluation sweeps where requests are homogeneous.

* **Continuous paged** (``ContinuousEngine``): a step-driven engine over a
  fixed number of serving *slots* and a shared paged KV pool
  (serving.cache.PagedKVCache + serving.scheduler.ContinuousScheduler).
  Each step admits pending requests into freed slots, decodes one token for
  every occupied slot, and retires requests at EOS / their own length cap —
  so KV memory tracks the tokens actually resident, every decode step is
  spent on a live request, and short requests never barrier on stragglers.
  Use for online serving with ragged prompt/output
  lengths; this is the substrate the hybrid router's small-model stream
  needs to realise its latency win (see serving.hybrid).

Chunked-admission state machine (``prefill_chunk > 0``, the default):
each slot moves QUEUED -> PREFILLING -> DECODING -> DONE. One ``step()``::

  1. ADMIT    pending requests claim free slots (state PREFILLING) while the
              pool can hold their full prompt *minus* pages already promised
              to other mid-prefill slots (reserve accounting, so incremental
              allocation can't strand a half-admitted prompt);
  2. PREFILL  a per-step token budget (default: one chunk width per slot)
              is spent on PREFILLING slots in admission order, at most one
              chunk per slot per step — so a decode slot's inter-token gap
              is bounded by single chunks, never a whole prompt. The budget
              is charged at each chunk's *bucketed dispatch width* (the
              shape actually launched — what per-step prefill latency
              scales with), and a slot whose width exceeds the leftover
              budget is skipped, not break-ed, so a ragged tail chunk later
              in admission order that fits the leftover budget still runs
              this step. The due chunks are page-extended in one batched
              call (serving.cache.extend_slots, per-row stall fallback),
              then *packed*: slots sharing a bucketed chunk width stack
              into one (B_chunk, width) batch and launch ONE paged
              prefill-attention kernel per (width, live-bound) bucket —
              O(width buckets) dispatches per step instead of O(PREFILLING
              slots) — which writes every chunk's K/V straight into pool
              pages, no host-side scatter round-trip. Chunk widths are
              bucketed (full chunks at ``prefill_chunk``, the ragged tail
              padded to a power of two) and the packed batch is padded to a
              power of two, so admission compiles one prefill shape per
              bucketed (batch, width, page-bound) triple, however ragged
              the prompt lengths. When a prompt's last chunk lands, the
              first token is sampled from its logits and the slot flips to
              DECODING. ``prefill_pack=0`` restores the per-slot B=1
              dispatch loop (the packed path's parity baseline);
  3. DECODE   every DECODING slot emits one token (paged decode kernel).
              Decode-time page growth also honours the prefill reservation,
              so a half-admitted prompt can never be stranded by decoders
              racing it for pages;
  4. RETIRE   EOS / per-request cap / context cap free the slot and record a
              ``finish_reason``.

  A long prompt therefore admits across several steps while live decode
  slots keep emitting every step — prefill never stalls decode.
  ``prefill_chunk=0`` selects the legacy one-shot path (whole prompt in one
  trace per distinct length, dense prefill + host-side page scatter).

Live-bounded page walks (``walk_bound="live"``, the default): both the
decode and prefill kernels take a static ``pages_bound`` on their sequential
page dimension, computed each dispatch from the engine's ``cache.seq_lens``
snapshot (ceil(live max / page_size), bucketed to powers of two so compiles
stay O(log max_pages)) — attention compute tracks the tokens actually
resident the same way paged memory already does, instead of walking the
engine-wide static ``max_pages_per_slot`` width with masked scratch-page
reads. ``walk_bound="static"`` restores the full-width walk (the parity
baseline). Within the bound the decode kernel copies and computes only
each slot's live pages, in blocks, and slots the step masks read none;
``ContinuousStats.decode_blocks_walked`` / ``decode_blocks_live`` count
its grid steps and the live ones, from the same snapshot.

Layer kinds beyond uniform-global attention (gemma3/jamba-style edge
tiers): sliding-window layers mask the paged kernels by global position
with a static per-layer ``window`` and additionally START their walk at
the dispatch's first live window page (``window_start``, floored to a
power of two — see _window_start), so window compute scales with the
window, not the resident prefix. SSM/hybrid layers keep constant-size
per-slot recurrent state (SSD matrix + conv tail) in a
``RecurrentStatePool`` beside the page pool; it streams through chunked
prefill (one-shot admission is refused), padding rows use the reserved
scratch row 0, and decode freezes rows of inactive slots. All of it stays
greedy-exact vs the dense engine (tests/test_window_ssm_serving.py).

Robustness layer (priorities, deadlines, preemption, shedding): requests
carry a ``priority`` class (higher admits first, FIFO within a class), an
optional ``deadline_s`` (from submission) and ``timeout_s`` (from first
admission) — an expired request is cancelled with finish reason
"deadline", mid-stream if necessary, and its slot reclaimed. Admission
uses a bounded head-of-line lookahead (``admit_lookahead``): when the
best-priority head cannot be admitted but a later pending request fits
the pool now, the later one overtakes it. When the pool is exhausted and
a strictly higher-priority request waits (``preempt_after_s`` past its
submission), the lowest-priority DECODING slot is PREEMPTED: its pages
are freed and its prompt *plus generated prefix* re-queues as one chunked
prefill (recompute-from-pages — the resumed prefill's final logits yield
the next token, so preemption stays greedy-exact). A per-request
preemption cap (``max_preemptions``) makes much-evicted requests immune,
so none starves. Overload degrades gracefully instead of wedging: the
pending queue is bounded (``max_pending``) with load shedding (finish
reason "rejected", lowest-priority latest-arrival first), prompts that
could never fit the pool are rejected at submit rather than head-of-line
blocking, and a zero-progress step with no externally held pages evicts
its way out before declaring deadlock.

``Engine.stats`` exposes compile counts and padding waste so bucket
recompiles show up in benchmarks; ``ContinuousEngine.stats`` + its cache
stats expose occupancy, admission stalls, prefill chunk/dispatch/compile
counts, decode bound compiles, the KV high-water mark, and the robustness
counters (preemptions, re-prefill tokens, sheds, deadline misses).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.data import tokenizer as tok
from repro.kernels.paged_decode_attention.kernel import walk_blocks
from repro.models.model import ModelBundle
from .cache import PagedKVCache, RecurrentStatePool
from .generate import build_generate_fn, _sample, _sample_rows
from .scheduler import (DECODING, DONE as SCHED_DONE, DRAFTING, VERIFYING,
                        ContinuousScheduler, Request)


def _bucket(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclasses.dataclass
class ServeStats:
    requests: int = 0
    batches: int = 0
    gen_tokens: int = 0
    wall_s: float = 0.0
    compiles: int = 0            # distinct (bucket, prompt-len) generate shapes
    pad_slots: int = 0           # bucket-padding rows across batches
    slot_count: int = 0          # total rows (incl. padding) across batches
    kv_high_water_bytes: int = 0  # largest dense KV slab held by one batch

    @property
    def padding_waste(self) -> float:
        """Fraction of batch rows that were bucket padding, not requests."""
        return self.pad_slots / self.slot_count if self.slot_count else 0.0


class Engine:
    """Serves one model, dense-batch mode. Queries are padded token arrays
    (N, Lq)."""

    def __init__(self, bundle: ModelBundle, params, max_new_tokens: int = 16,
                 temperature: float = 0.0):
        self.bundle = bundle
        self.params = params
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self._gen = build_generate_fn(bundle, max_new_tokens, temperature)
        self._shapes: set = set()   # (bucket, prompt_len) already compiled
        self.stats = ServeStats()

    def warmup(self, prompt_len: int, max_batch: int):
        """Precompile the generate fn for every bucket up to ``max_batch`` at
        ``prompt_len``, so first-request latency doesn't eat the compiles."""
        b = 1
        while b <= _bucket(max_batch):
            dummy = np.full((b, prompt_len), tok.PAD, np.int32)
            self._gen(self.params, {"tokens": jnp.asarray(dummy)},
                      jax.random.PRNGKey(0))
            if (b, prompt_len) not in self._shapes:
                self._shapes.add((b, prompt_len))
                self.stats.compiles += 1
            b *= 2

    def _kv_slab_bytes(self, batch: int, prompt_len: int) -> int:
        cfg = self.bundle.cfg
        if not cfg.n_kv_heads:
            return 0
        extra = cfg.num_frontend_tokens if cfg.frontend == "vision_stub" else 0
        seq = prompt_len + extra + self.max_new_tokens
        itemsize = 4 if cfg.dtype == "float32" else 2
        return (cfg.n_layers * batch * seq * cfg.n_kv_heads
                * cfg.resolved_head_dim * 2 * itemsize)

    def serve(self, query_tokens: np.ndarray, seed: int = 0
              ) -> tuple[np.ndarray, np.ndarray]:
        """Returns (responses (N, T), lengths (N,))."""
        n = len(query_tokens)
        b = _bucket(n)
        Lq = query_tokens.shape[1]
        padded = np.full((b, Lq), tok.PAD, np.int32)
        padded[:n] = query_tokens
        if (b, Lq) not in self._shapes:   # jit compiles on first use
            self._shapes.add((b, Lq))
            self.stats.compiles += 1
        t0 = time.monotonic()
        toks, lens = self._gen(self.params, {"tokens": jnp.asarray(padded)},
                               jax.random.PRNGKey(seed))
        toks, lens = np.asarray(toks)[:n], np.asarray(lens)[:n]
        self.stats.requests += n
        self.stats.batches += 1
        self.stats.gen_tokens += int(lens.sum())
        self.stats.wall_s += time.monotonic() - t0
        self.stats.pad_slots += b - n
        self.stats.slot_count += b
        self.stats.kv_high_water_bytes = max(
            self.stats.kv_high_water_bytes, self._kv_slab_bytes(b, Lq))
        return toks, lens


def make_engine(bundle: ModelBundle, params, **kw):
    """Engine factory honouring the config's cache-layout flag:
    ``cfg.cache_layout == "paged"`` selects the continuous-batching paged
    engine (when the architecture supports it — decoder-only stacks of any
    mixer mix; see ArchConfig.paged_unsupported_reason), anything else the
    dense-batch engine. Continuous-only kwargs (n_slots, max_seq, ...) are
    dropped for dense."""
    if bundle.cfg.cache_layout == "paged" and bundle.decode_step_paged:
        return ContinuousEngine(bundle, params, **kw)
    return Engine(bundle, params, **{k: v for k, v in kw.items()
                                     if k in ("max_new_tokens", "temperature")})


# --------------------------------------------------------------- continuous
@dataclasses.dataclass
class ContinuousStats:
    steps: int = 0               # steps that did any work (decode, prefill,
                                 # admission, or retirement) — prefill-only
                                 # steps count too, so mean occupancy is
                                 # over every step that did work
    decode_steps: int = 0        # steps that dispatched a decode kernel
    prefill_steps: int = 0       # steps that advanced at least one chunk
    prefill_only_steps: int = 0  # steps that prefilled but decoded nothing
    admitted: int = 0
    retired: int = 0
    prefill_tokens: int = 0
    decode_tokens: int = 0
    prefill_chunks: int = 0      # slot-chunks advanced (one slot, one chunk)
    prefill_dispatches: int = 0  # prefill kernel launches (packed: one per
                                 # (batch, width, bound) bucket, <= chunks)
    prefill_compiles: int = 0    # distinct (batch, width, bound, wstart)
                                 # prefill shapes traced
    decode_compiles: int = 0     # distinct (bound, wstart) decode page
                                 # walks traced
    decode_blocks_walked: int = 0  # paged decode kernel grid steps, every
                                   # attention layer of every decode step
    decode_blocks_live: int = 0    # of those, the steps that copied and
                                   # computed a block of a slot's live pages
    prefill_stalls: int = 0      # chunk extensions deferred for pool space
    occupancy_sum: int = 0       # busy slots (decoded + prefill-advanced)
                                 # summed over steps
    admission_stalls: int = 0    # admissions deferred for page-pool space
    preemptions: int = 0         # DECODING slots evicted (recompute-from-
                                 # pages: prompt + prefix re-queued)
    reprefill_tokens: int = 0    # tokens queued for re-prefill by evictions
                                 # (the compute cost of preemption)
    escalations: int = 0         # DECODING slots quality-aborted up a tier
                                 # (EscalationMonitor; pages freed, prompt +
                                 # prefix handed to the pool — the cost is
                                 # the UPPER tier's prefill, so no
                                 # reprefill_tokens are charged here)
    sheds: int = 0               # requests load-shed with reason "rejected"
                                 # (bounded-queue overflow or never-fits)
    deadline_misses: int = 0     # requests cancelled with reason "deadline"
    stall_steps: int = 0         # zero-progress steps waited out because
                                 # pages were held externally (hold_pages)
    # cross-tier speculative decoding (attach_draft; all zero otherwise)
    spec_rounds: int = 0         # speculative rounds run (draft + verify)
    draft_steps: int = 0         # draft-model micro-step kernel launches
    verify_steps: int = 0        # target verify-chunk kernel launches
    drafted_tokens: int = 0      # candidate tokens the draft proposed
    accepted_tokens: int = 0     # draft tokens the target emitted verbatim
    rejected_tokens: int = 0     # draft tokens rolled back (truncate_slot)
    spec_fallbacks: int = 0      # steps where a spec-configured engine
                                 # plain-decoded at least one slot (draft
                                 # tier stalled, page pressure, context cap)
    # shared-prefix KV reuse (prefix_cache > 0; all zero otherwise)
    prefix_hits: int = 0         # admissions that mapped >= 1 cached token
    prefix_misses: int = 0       # admissions the tree had nothing for
    prefix_hit_tokens: int = 0   # prompt tokens whose prefill was skipped
    prefix_hit_pages: int = 0    # pages mapped read-only at admission
    cow_splits: int = 0          # copy-on-write page copies dispatched

    @property
    def mean_occupancy(self) -> float:
        return self.occupancy_sum / self.steps if self.steps else 0.0

    @property
    def acceptance_rate(self) -> float:
        """Fraction of drafted tokens the target accepted verbatim — the
        number that decides whether speculation pays (expected emitted
        tokens per verify launch is 1 + acceptance_rate * gamma at the
        deterministic limit)."""
        return self.accepted_tokens / self.drafted_tokens \
            if self.drafted_tokens else 0.0


@dataclasses.dataclass
class EscalationMonitor:
    """Mid-stream quality watch over one tier's decode logits.

    Every plain-decode dispatch computes, inside the decode jit, a per-slot
    uncertainty score from that step's next-token distribution: the mean of
    normalized entropy and (1 - top-2 probability margin), both in [0, 1].
    The monitor EMA-smooths it per stream and records each stream's peak in
    ``Request.esc_peak_score``. With ``abort_threshold=None`` that is all
    it does (the observe-only calibration pass —
    ``core.thresholds.calibrate_abort_threshold`` turns the collected peaks
    into a threshold at an escalation-fraction budget). With a threshold
    set, a DECODING stream whose running score reaches it after at least
    ``min_tokens`` emitted tokens is cancelled through the preemption
    mechanics (pages freed, prompt + emitted prefix kept as
    ``serve_tokens``) and lands in the engine's escalated buffer for the
    pool to re-admit ONE TIER UP as one chunked prefill — escalation costs
    a prefill, not a restart.

    Speculative slots bypass the monitor: a drafted-and-verified round
    never passes through the plain decode dispatch that scores uncertainty
    (and its accept rule already embeds the target's own judgement).
    Monitors belong on a pool's tiers below the priciest; a bare engine
    has nowhere to send the escalated buffer.
    """
    abort_threshold: Optional[float] = None   # None = observe-only
    min_tokens: int = 4     # emitted tokens before a stream may abort
    ema: float = 0.5        # smoothing weight on the newest step's score

    def __post_init__(self):
        if self.min_tokens < 1:
            raise ValueError(f"min_tokens={self.min_tokens}: a stream must "
                             "emit at least one token before escalating "
                             "(its prefix is the hand-off payload)")
        if not 0.0 < self.ema <= 1.0:
            raise ValueError(f"ema={self.ema}: the smoothing weight must "
                             "be in (0, 1] (1 = no smoothing)")


class ContinuousEngine:
    """Step-driven continuous-batching engine over a paged KV cache (plus,
    for SSM/hybrid stacks, a per-slot recurrent-state pool).

    ``submit`` enqueues a request (its own ``max_new_tokens`` cap allowed);
    ``step`` advances the world by one decode token per occupied slot,
    admitting and retiring as it goes; ``run`` drains the queue. ``serve``
    is the batch-API compatibility wrapper.

    Units throughout: prompts/outputs are counted in TOKENS, cache
    capacity/bounds in PAGES (``page_size`` tokens each), progress in
    engine STEPS (one step = at most one decode token per live slot).

    Greedy-exactness guarantee: at temperature 0, for any admission
    interleaving, the engine emits per request exactly the tokens the
    dense-batch ``Engine`` emits — chunked/packed prefill, live-bounded
    and window-started page walks, and recurrent-state streaming are
    dispatch optimisations, never semantic changes (parity tests:
    tests/test_continuous_serving.py, tests/test_chunked_prefill.py,
    tests/test_window_ssm_serving.py).
    """

    def __init__(self, bundle: ModelBundle, params, max_new_tokens: int = 16,
                 temperature: float = 0.0, *, n_slots: int = 8,
                 page_size: Optional[int] = None, max_seq: int = 256,
                 num_pages: Optional[int] = None, seed: int = 0,
                 rng_salt: int = 0, prefill_chunk: Optional[int] = None,
                 prefill_budget: Optional[int] = None,
                 prefill_pack: Optional[int] = None,
                 walk_bound: str = "live",
                 max_pending: Optional[int] = None,
                 max_preemptions: int = 3,
                 preempt_after_s: float = 0.0,
                 admit_lookahead: Optional[int] = None,
                 prefix_cache: int = 0,
                 escalation: Optional[EscalationMonitor] = None):
        if bundle.decode_step_paged is None:
            raise ValueError(f"{bundle.cfg.name}: no paged decode path "
                             "(ArchConfig.supports_paged_kv is False)")
        if prefix_cache < 0:
            raise ValueError(f"prefix_cache={prefix_cache}: the prefix "
                             "tree's page budget must be non-negative "
                             "(0 disables sharing)")
        self.bundle = bundle
        self.params = params
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        ps = page_size or bundle.cfg.kv_page_size
        mp = _round_up(max_seq, ps) // ps
        # shared-prefix KV reuse: tiers that can't share fall back to plain
        # recomputation with the reason recorded (never an error — the pool
        # mixes sharing and non-sharing tiers freely). Eligibility needs the
        # *effective* chunk size, resolved before the cache is sized below.
        self.prefix_reason: Optional[str] = None
        if prefix_cache:
            chunk_eff = bundle.cfg.prefill_chunk if prefill_chunk is None \
                else prefill_chunk
            if bundle.prefill_paged_chunk is None or bundle.lm_head is None:
                chunk_eff = 0
            if bundle.init_recurrent_state is not None:
                self.prefix_reason = (
                    "recurrent state: SSM/hybrid state is position-dependent "
                    "and has no page form to share — prefixes recompute")
            elif bundle.cfg.has_window_layers:
                self.prefix_reason = (
                    "sliding-window layers: K/V behind the window horizon "
                    "is never written, so cached pages are incomplete — "
                    "prefixes recompute")
            elif chunk_eff == 0:
                self.prefix_reason = (
                    "one-shot prefill: admission scatters whole prompts "
                    "into fresh pages with no fork point — prefixes "
                    "recompute (set prefill_chunk > 0 to share)")
            if self.prefix_reason is not None:
                prefix_cache = 0
        self.prefix_cache = prefix_cache
        if num_pages is None:
            # page 0 reserved; the tree's budget rides on top of the slots'
            # worst case so sharing never *shrinks* usable slot capacity
            num_pages = 1 + n_slots * mp + prefix_cache
        self.cache = PagedKVCache(bundle, n_slots, num_pages, ps, mp,
                                  prefix_pages=prefix_cache)
        # COW split: device-copies one page's K/V src -> dst before a slot's
        # first write into a page it shares (donated pools, one trace)
        def copy_page(kp, vp, src, dst):
            return (kp.at[:, dst].set(kp[:, src]),
                    vp.at[:, dst].set(vp[:, src]))

        self._copy_page = jax.jit(copy_page, donate_argnums=(0, 1)) \
            if prefix_cache else None
        # SSM/hybrid stacks keep constant-size per-slot recurrent state
        # beside the page pool (serving.cache.RecurrentStatePool)
        self.rstate = RecurrentStatePool(bundle, n_slots) \
            if bundle.init_recurrent_state is not None else None
        self.sched = ContinuousScheduler(n_slots)
        self.stats = ContinuousStats()
        self.n_slots = n_slots
        # chunked admission: prefill_chunk tokens per chunk (None -> the
        # config's knob; 0 -> legacy one-shot whole-prompt prefill);
        # prefill_budget tokens of prefill per step. The default budget
        # scales with the slot count — admission demand does too, and a
        # single-chunk budget throttles occupancy under bursty arrivals;
        # tighten it to bound per-step prefill time (inter-token latency)
        if prefill_chunk is None:
            prefill_chunk = bundle.cfg.prefill_chunk
        if prefill_chunk < 0 or (prefill_budget or 0) < 0:
            raise ValueError(f"prefill_chunk={prefill_chunk} / "
                             f"prefill_budget={prefill_budget}: chunked "
                             "admission needs non-negative sizes "
                             "(0 disables chunking)")
        if bundle.prefill_paged_chunk is None or bundle.lm_head is None:
            prefill_chunk = 0
        if self.rstate is not None and prefill_chunk == 0:
            # one-shot admission scatters a dense KV cache into pages;
            # recurrent state has no page-shaped form to scatter, so
            # SSM/hybrid prompts must stream through chunked prefill
            raise ValueError(f"{bundle.cfg.name}: recurrent-state stacks "
                             "admit through chunked prefill; prefill_chunk "
                             "must be > 0")
        self.prefill_chunk = prefill_chunk
        self.prefill_budget = prefill_budget if prefill_budget is not None \
            else n_slots * prefill_chunk
        # packed prefill: up to prefill_pack PREFILLING slots stack into one
        # kernel launch per bucketed chunk width (0 = legacy per-slot B=1
        # dispatch, the packed path's parity baseline)
        if prefill_pack is None:
            prefill_pack = n_slots
        if prefill_pack < 0:
            raise ValueError(f"prefill_pack={prefill_pack}: packed prefill "
                             "needs a non-negative pack size (0 disables "
                             "packing)")
        self.prefill_pack = prefill_pack
        # live-bounded page walks: bound both kernels' sequential page dim
        # by the bucketed live max context ("static" = full-width walk, the
        # parity baseline)
        if walk_bound not in ("live", "static"):
            raise ValueError(f"walk_bound={walk_bound!r}: expected 'live' "
                             "or 'static'")
        self.walk_bound = walk_bound
        # robustness knobs: bounded pending queue with load shedding
        # (max_pending=None keeps the queue unbounded), per-request
        # preemption cap (an evicted-this-often request becomes immune, so
        # preemption can't starve anyone), minimum wait before a
        # higher-priority arrival may evict (0 = preempt on demand), and
        # the head-of-line admission lookahead window (None = n_slots)
        if max_pending is not None and max_pending < 1:
            raise ValueError(f"max_pending={max_pending}: a bounded queue "
                             "needs room for at least one request")
        if max_preemptions < 0 or preempt_after_s < 0:
            raise ValueError(f"max_preemptions={max_preemptions} / "
                             f"preempt_after_s={preempt_after_s}: "
                             "preemption limits must be non-negative")
        self.max_pending = max_pending
        self.max_preemptions = max_preemptions
        self.preempt_after_s = preempt_after_s
        self.admit_lookahead = n_slots if admit_lookahead is None \
            else max(1, admit_lookahead)
        self._shed_buf: List[Request] = []   # retired outside step(),
                                             # drained into the next
                                             # step/run result
        self._chunk_shapes: set = set()   # (batch, width, bound, wstart)
        self._decode_bounds: set = set()  # (bound, wstart) pairs traced
        # attention layers per sliding window (0 = global): each decode
        # step's kernel calls, for the walked/live block counters
        cfg = bundle.cfg
        self._attn_windows: Dict[int, int] = {}
        for i in range(cfg.n_layers):
            if cfg.layer_kind(i)["attn"]:
                w = cfg.layer_window(i)
                self._attn_windows[w] = self._attn_windows.get(w, 0) + 1
        self._next_in = np.full((n_slots,), tok.PAD, np.int32)
        self._seed = seed
        self._rng_salt = rng_salt
        self._serve_calls = 0
        self._key = jax.random.fold_in(jax.random.PRNGKey(seed), rng_salt)
        self._prefill = jax.jit(bundle.prefill, static_argnums=(2,))
        self._decode = self._build_decode()
        self._prefill_chunk_fn = self._build_prefill_chunk() \
            if self.prefill_chunk else None
        # LM head applied once per dispatch whose pack finished a prompt,
        # on the (B_pack, 1, D) final-chunk hidden states — one
        # width-independent trace per pack-batch bucket, so non-final
        # chunks never pay the vocab projection
        def lm_head(params, x):
            return bundle.lm_head(params, x)

        self._lm_head = jax.jit(lm_head) if self.prefill_chunk else None
        # donated pools: scatter updates in place rather than copying
        self._scatter = jax.jit(self.scatter_prompt, donate_argnums=(0, 1))
        # per-slot sampling temperature: a request's own temperature (or the
        # engine default) lands here at admission, so one decode trace
        # serves any greedy/sampled mix (see generate._sample_rows)
        self._temps = np.full((n_slots,), temperature, np.float32)
        # cross-tier speculative decoding: attach_draft installs a cheap
        # sibling whose paged cache mirrors this engine's slot geometry
        self.draft_bundle: Optional[ModelBundle] = None
        self.draft_params = None
        self.draft_cache: Optional[PagedKVCache] = None
        self.spec_gamma = 0
        self._draft_prefill_fn = None
        self._draft_decode_fn = None
        self._verify_fn = None
        self._draft_bounds: set = set()
        self._verify_shapes: set = set()
        # mid-stream quality escalation: the monitor (settable any time,
        # None = off), the per-slot EMA-smoothed running uncertainty score,
        # and the buffer of streams cancelled up a tier this step — the
        # pool drains it (``drain_escalated``) and re-admits each request
        # one tier up via that engine's ``resubmit``
        self.escalation = escalation
        self._esc_score = np.zeros((n_slots,), np.float32)
        self._escalated_buf: List[Request] = []

    # ------------------------------------------------------------ jit pieces
    def _build_decode(self):
        bundle = self.bundle

        def decode_step(params, k_pages, v_pages, rec, token, page_table,
                        seq_lens, active, key, temps, pages_bound,
                        window_start):
            cache = {"k_pages": k_pages, "v_pages": v_pages}
            if rec is not None:
                cache["rec"] = rec
            with jax.named_scope("decode"):
                logits, cache = bundle.decode_step_paged(
                    params, cache, token, page_table, seq_lens, active,
                    pages_bound=pages_bound, window_start=window_start)
            # per-slot temperatures (engine default unless the request set
            # its own): greedy rows take the argmax, sampled rows draw at
            # their own temperature — one trace for any mix
            nxt = _sample_rows(key, logits, temps)
            nxt = jnp.where(active, nxt, jnp.int32(tok.PAD))
            # per-slot uncertainty for the escalation monitor, from the
            # same distribution the token was sampled from: mean of
            # normalized entropy and (1 - top-2 margin), both in [0, 1].
            # Computed unconditionally — a handful of vector ops against a
            # full decode launch, and branching on it would double the
            # trace count. Inactive slots produce garbage the step ignores.
            lg = logits.reshape(logits.shape[0], -1)
            p = jax.nn.softmax(lg, axis=-1)
            ent = -(p * jnp.log(p + 1e-9)).sum(-1) / jnp.log(lg.shape[-1])
            top2 = jax.lax.top_k(p, 2)[0]
            unc = 0.5 * ent + 0.5 * (1.0 - (top2[:, 0] - top2[:, 1]))
            return (nxt, unc, cache["k_pages"], cache["v_pages"],
                    cache.get("rec"))

        # donate the pools (and the recurrent-state slabs): the step
        # updates them in place instead of copying per decoded token
        # (engine reassigns cache.pool / rstate.state from the outputs
        # immediately). pages_bound and window_start are static: one trace
        # per bucketed (live bound, window start) pair
        return jax.jit(decode_step, donate_argnums=(1, 2, 3),
                       static_argnums=(10, 11))

    def _build_prefill_chunk(self):
        bundle = self.bundle

        def prefill_chunk(params, k_pages, v_pages, rec, tokens, page_table,
                          start, n_new, state_rows, pages_bound,
                          window_start):
            cache = {"k_pages": k_pages, "v_pages": v_pages}
            if rec is not None:
                cache["rec"] = rec
            with jax.named_scope("prefill"):
                x_last, cache = bundle.prefill_paged_chunk(
                    params, cache, tokens, page_table, start, n_new,
                    pages_bound=pages_bound, window_start=window_start,
                    state_rows=state_rows)
            return x_last, cache["k_pages"], cache["v_pages"], \
                cache.get("rec")

        # donated pools: the chunk's K/V are written into the pool pages in
        # place — this is what retires the one-shot path's host _scatter —
        # and recurrent rows advance in place the same way. pages_bound and
        # window_start are static: one trace per bucketed pair
        return jax.jit(prefill_chunk, donate_argnums=(1, 2, 3),
                       static_argnums=(9, 10))

    def _build_draft_prefill(self):
        """Jit the draft sibling's chunked prefill (the draft-cache mirror
        of every admitted chunk). x_last is discarded — the draft never
        samples a request's first token, the target does."""
        bundle = self.draft_bundle

        def draft_prefill(params, k_pages, v_pages, tokens, page_table, start,
                          n_new, pages_bound, window_start):
            cache = {"k_pages": k_pages, "v_pages": v_pages}
            with jax.named_scope("prefill"):
                _, cache = bundle.prefill_paged_chunk(
                    params, cache, tokens, page_table, start, n_new,
                    pages_bound=pages_bound, window_start=window_start)
            return cache["k_pages"], cache["v_pages"]

        return jax.jit(draft_prefill, donate_argnums=(1, 2),
                       static_argnums=(7, 8))

    def _build_draft_decode(self):
        """Jit one draft micro-step: decode + per-slot-temperature sample,
        returning the sampled candidates AND the full logits row (standard
        speculative acceptance needs the draft's proposal distribution)."""
        bundle = self.draft_bundle

        def draft_decode(params, k_pages, v_pages, token, page_table,
                         seq_lens, active, key, temps, pages_bound):
            cache = {"k_pages": k_pages, "v_pages": v_pages}
            with jax.named_scope("decode"):
                logits, cache = bundle.decode_step_paged(
                    params, cache, token, page_table, seq_lens, active,
                    pages_bound=pages_bound, window_start=0)
            nxt = _sample_rows(key, logits, temps)
            nxt = jnp.where(active, nxt, jnp.int32(tok.PAD))
            return nxt, logits, cache["k_pages"], cache["v_pages"]

        return jax.jit(draft_decode, donate_argnums=(1, 2),
                       static_argnums=(9,))

    def _build_verify(self):
        """Jit the target's verify chunk: per-position logits for the whole
        drafted chunk in ONE launch (the chunked-prefill shape), K/V landing
        in the pool pages exactly as a prefill chunk's would — rejected
        suffixes roll back via truncate_slot."""
        bundle = self.bundle

        def verify_chunk(params, k_pages, v_pages, tokens, page_table, start,
                         n_new, pages_bound, window_start):
            cache = {"k_pages": k_pages, "v_pages": v_pages}
            with jax.named_scope("verify"):
                x, cache = bundle.verify_paged_chunk(
                    params, cache, tokens, page_table, start, n_new,
                    pages_bound=pages_bound, window_start=window_start)
            logits = bundle.lm_head(params, x)
            return logits, cache["k_pages"], cache["v_pages"]

        return jax.jit(verify_chunk, donate_argnums=(1, 2),
                       static_argnums=(7, 8))

    def attach_draft(self, bundle: ModelBundle, params,
                     gamma: int = 2) -> "ContinuousEngine":
        """Host a cheap *draft* sibling inside this engine for cross-tier
        speculative decoding. The draft gets a second ``PagedKVCache`` over
        the SAME slot geometry (slot s of the target is slot s of the
        draft), kept in lockstep: admission chunks mirror into it, retire /
        preempt free both, and rejected suffixes truncate both. Each
        ``step()`` then runs a speculative round over eligible DECODING
        slots — the draft streams ``gamma`` candidate tokens per slot, the
        target scores the whole chunk in one verify launch, and standard
        speculative sampling accepts a prefix (greedy-exact at
        temperature 0: byte-identical output to the non-speculative
        engine, just fewer target launches).

        The draft may trail the target (a full accept leaves the last
        emitted token unseen by the draft; a draft-tier stall leaves whole
        plain-decoded steps unseen) — the next round runs that many
        catch-up micro-steps first, feeding the already-known tokens, so
        speculation degrades and recovers without any cache rebuild.

        Requires a rollback-capable target (``verify_paged_chunk``: pure
        global attention) and a pure-global-attention paged draft, both on
        the chunked-prefill path."""
        if gamma < 1:
            raise ValueError(f"gamma={gamma}: a speculative round needs at "
                             "least one drafted token")
        if self.bundle.verify_paged_chunk is None:
            raise ValueError(
                f"{self.bundle.cfg.name}: no verify path — recurrent state "
                "or sliding-window layers cannot roll back a rejected "
                "suffix; this tier serves non-speculatively")
        if self.prefill_chunk == 0:
            raise ValueError("speculative decoding rides the chunked-"
                             "prefill machinery (the verify chunk IS a "
                             "prefill-shaped chunk and the draft cache "
                             "mirrors admission chunk-by-chunk); "
                             "prefill_chunk must be > 0")
        if bundle.decode_step_paged is None \
                or bundle.prefill_paged_chunk is None:
            raise ValueError(f"{bundle.cfg.name}: a draft model must serve "
                             "paged (decode + chunked prefill)")
        if bundle.init_recurrent_state is not None \
                or bundle.cfg.has_window_layers:
            raise ValueError(f"{bundle.cfg.name}: draft stacks must be pure "
                             "global attention — the draft cache mirrors "
                             "the target's page geometry and rolls back "
                             "with it")
        if self.cache.prefix is not None:
            # the draft mirror replays every admission chunk to build its
            # own K/V; a prefix hit skips chunks the draft never sees, so
            # the mirrors would desync. Speculation wins the trade: drop
            # the tree (slot-mapped pages survive until their slots free)
            self.cache.drop_prefix()
            self.prefix_cache = 0
            self.prefix_reason = (
                "speculative draft mirror: the draft cache replays every "
                "admission chunk, so prefill skipping would desync the "
                "mirrors — prefixes recompute on this tier")
        self.draft_bundle, self.draft_params = bundle, params
        self.spec_gamma = gamma
        self.draft_cache = PagedKVCache(bundle, self.n_slots,
                                        self.cache.num_pages,
                                        self.cache.page_size,
                                        self.cache.max_pages_per_slot)
        self._draft_prefill_fn = self._build_draft_prefill()
        self._draft_decode_fn = self._build_draft_decode()
        self._verify_fn = self._build_verify()
        return self

    def _pages_bound(self, max_tokens: int,
                     cache: Optional[PagedKVCache] = None) -> int:
        """Static page bound for a dispatch whose live contexts reach at
        most ``max_tokens``: the live page count rounded up to a power of
        two (distinct compiles stay O(log max_pages)), capped at the static
        table width. ``walk_bound="static"`` always returns the full
        width. ``cache`` defaults to the target's; pass ``draft_cache``
        for draft dispatches."""
        cache = cache or self.cache
        mp = cache.max_pages_per_slot
        if self.walk_bound != "live":
            return mp
        return min(_bucket(cache.pages_for(max(max_tokens, 1))), mp)

    def _window_start(self, min_first_key: int) -> int:
        """Static first page of the sliding-window layers' page walk, for a
        dispatch whose earliest in-window key position (over the rows
        actually dispatched) is ``min_first_key``: the containing page
        FLOORED to a power of two, so distinct (bound, start) compiles stay
        O(log^2 max_pages) and the walk always covers every row's window.
        0 when the stack has no window layers or walks are static."""
        if not self.bundle.cfg.has_window_layers \
                or self.walk_bound != "live" or min_first_key <= 0:
            return 0
        page = min_first_key // self.cache.page_size
        b = 1
        while b * 2 <= page:
            b *= 2
        return b if page else 0

    @staticmethod
    def scatter_prompt(k_pool, v_pool, ks, vs, page_ids):
        """Scatter a prefilled dense cache (L, 1, Spad, K, D) into the pool
        pages listed in ``page_ids`` (Spad = len(page_ids) * page_size).
        Pools are donated — updated in place, not copied."""
        L, _, Spad, K, D = ks.shape
        n = page_ids.shape[0]
        # (L, Spad, K, D) -> the pool's per-page (L, n, K, ps, D) layout
        ksr = ks[:, 0].reshape(L, n, Spad // n, K, D).swapaxes(2, 3)
        vsr = vs[:, 0].reshape(L, n, Spad // n, K, D).swapaxes(2, 3)
        return (k_pool.at[:, page_ids].set(ksr),
                v_pool.at[:, page_ids].set(vsr))

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def set_rng_salt(self, salt: int):
        """Give this engine a distinct sampling stream. Sibling engines in a
        hybrid are typically built with the same default seed; without a
        salt their temperature>0 partitions would draw correlated samples."""
        self._rng_salt = salt
        self._key = jax.random.fold_in(jax.random.PRNGKey(self._seed), salt)

    def reseed(self, seed: int):
        """Start a fresh deterministic sampling stream for one serve call:
        folds the caller's seed, this engine's salt, and a per-call counter,
        so repeated calls (and sibling engines) never reuse a stream."""
        key = jax.random.fold_in(jax.random.PRNGKey(seed), self._rng_salt)
        self._key = jax.random.fold_in(key, self._serve_calls)
        self._serve_calls += 1

    # -------------------------------------------------------------- requests
    def _req_temp(self, req: Request) -> float:
        """A request's effective sampling temperature: its own override, or
        the engine default."""
        return self.temperature if req.temperature is None \
            else req.temperature

    def submit(self, tokens: np.ndarray, max_new_tokens: Optional[int] = None,
               *, priority: int = 0, deadline_s: Optional[float] = None,
               timeout_s: Optional[float] = None,
               temperature: Optional[float] = None) -> Request:
        """Enqueue one request. ``tokens``: 1-d int32 prompt (no padding);
        ``max_new_tokens``: per-request output cap in tokens (None = the
        engine default); ``priority``: admission class (higher first);
        ``deadline_s`` / ``timeout_s``: completion deadline from submission
        / in-flight cap from first admission, in seconds; ``temperature``:
        per-request sampling temperature (None = the engine default, 0 =
        greedy) — greedy and sampled streams coexist in one batch, and the
        speculative accept/reject rule follows each request's own
        temperature.

        Malformed requests (empty prompt, max_new < 1) raise — they are
        caller bugs. Well-formed requests that could never complete —
        prompts past the per-slot context cap (max_pages_per_slot *
        page_size tokens) or whose worst-case page footprint exceeds the
        whole pool — and bounded-queue overflow are *load-shed*: the
        request comes back already done with finish reason "rejected"
        instead of head-of-line blocking or wedging the queue."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if len(tokens) == 0:
            raise ValueError("empty prompt: a request needs at least one "
                             "token to prefill")
        max_new = self.max_new_tokens if max_new_tokens is None \
            else max_new_tokens
        if max_new < 1:
            raise ValueError(f"max_new_tokens={max_new}: a request must be "
                             "allowed at least one output token")
        if temperature is not None and temperature < 0:
            raise ValueError(f"temperature={temperature}: negative "
                             "temperatures are meaningless (0 = greedy)")
        req = Request(tokens=tokens, max_new_tokens=max_new,
                      priority=priority, deadline_s=deadline_s,
                      timeout_s=timeout_s, temperature=temperature)
        req.submit_t = time.monotonic()
        cap = self.cache.max_pages_per_slot * self.cache.page_size
        # worst-case cache footprint if this request runs alone: prompt plus
        # every generated token but the last (which is sampled, not written),
        # bounded by the per-slot context cap. Beyond the pool it can never
        # finish even after every other slot retires.
        peak = self.cache.pages_for(min(len(tokens) + max_new - 1, cap))
        if len(tokens) + 1 > cap or peak > self.cache.stats.num_pages:
            return self._shed(req)
        if self.max_pending is not None \
                and len(self.sched.pending) >= self.max_pending:
            # bounded queue overflow: shed the least urgent of (new arrival,
            # worst queued) — lowest priority, latest arrival loses, so a
            # high-priority burst displaces stale low-priority backlog
            # rather than bouncing off it
            victim = min(self.sched.pending,
                         key=lambda r: (r.priority, -r.rid))
            if (victim.priority, -victim.rid) < (req.priority, -req.rid):
                self.sched.drop_pending(victim)
                self._shed(victim)
            else:
                return self._shed(req)
        return self.sched.submit(req)

    def _finish_unslotted(self, req: Request, reason: str,
                          sink: Optional[List[Request]] = None) -> Request:
        """Retire a request that holds no slot (shed at submit, dropped from
        the queue). Lands in ``sink`` when the caller is mid-step, else in
        the shed buffer for the next step()/run() result."""
        req.done = True
        req.state = SCHED_DONE
        req.finish_reason = reason
        req.finish_t = time.monotonic()
        (self._shed_buf if sink is None else sink).append(req)
        return req

    def _shed(self, req: Request) -> Request:
        self.stats.sheds += 1
        return self._finish_unslotted(req, "rejected")

    def drain_shed(self) -> List[Request]:
        """Requests retired outside a step (load-shed at submit, expired in
        the queue) since the last drain. step()/run() fold these into their
        returns; pool engines drain after every submit for accounting."""
        out, self._shed_buf = self._shed_buf, []
        return out

    def _publish_resident(self, slot: int) -> None:
        """Publish a freeing slot's completed full pages into the prefix
        tree just before retirement/preemption releases them — a multi-turn
        session's next turn (prompt = history + new user text) or a
        preempted request's resume re-prefill walks straight onto this
        context. Keyed by prompt + emitted tokens truncated to the resident
        length (``seq_lens`` trails the last sampled token, whose K/V was
        never written)."""
        if self.cache.prefix is None:
            return
        req = self.sched.running[slot]
        resident = int(self.cache.seq_lens[slot])
        seq = np.concatenate([req.tokens, np.asarray(req.out, np.int32)])
        self.cache.prefix_publish(slot, seq[:resident], resident)

    def _retire(self, slot: int, reason: str) -> Request:
        self._publish_resident(slot)
        self.cache.free_slot(slot)
        if self.draft_cache is not None:
            self.draft_cache.free_slot(slot)   # lockstep: draft mirror too
        self._next_in[slot] = tok.PAD
        self._temps[slot] = self.temperature
        self._esc_score[slot] = 0.0
        self.stats.retired += 1
        req = self.sched.retire(slot)
        req.finish_reason = reason
        if reason == "deadline":
            self.stats.deadline_misses += 1
        return req

    def _preempt(self, slot: int) -> Request:
        """Evict ``slot`` mid-decode (recompute-from-pages): free its pages
        and re-queue the request with prompt + everything generated so far
        as its new prefill source. The resumed prefill's final-chunk logits
        sample the token decode would have emitted next, so the output
        stream is greedy-exact across any number of evictions. Fit is
        guaranteed: a live slot has seq_lens + 1 <= context cap and at
        most max_new - 1 generated tokens (the cap-th retires it), so
        serve_tokens never outgrows the admission bounds submit checked."""
        req = self.sched.running[slot]
        self._publish_resident(slot)
        self.cache.free_slot(slot)
        if self.draft_cache is not None:
            self.draft_cache.free_slot(slot)   # resumption re-mirrors both
        self._next_in[slot] = tok.PAD
        self._temps[slot] = self.temperature
        self._esc_score[slot] = 0.0
        req.serve_tokens = np.concatenate(
            [req.tokens, np.asarray(req.out, np.int32)])
        req.prefill_pos = 0
        req.preemptions += 1
        req.reprefill_tokens += len(req.serve_tokens)
        self.stats.preemptions += 1
        self.stats.reprefill_tokens += len(req.serve_tokens)
        return self.sched.preempt(slot)

    def _watch_escalation(self, slots: List[int], unc: np.ndarray) -> None:
        """Feed this step's per-slot uncertainty scores to the escalation
        monitor: EMA-smooth per stream, track each stream's peak, and
        cancel any DECODING stream whose running score has reached the
        abort threshold (observe-only when the threshold is None). Runs
        after the step's retirements — a stream that just finished never
        escalates — and only over the plain-decode slots (speculative
        rounds bypass the monitor, see EscalationMonitor)."""
        mon = self.escalation
        for slot in slots:
            req = self.sched.running.get(slot)
            if req is None or req.state != DECODING:
                continue
            s = mon.ema * float(unc[slot]) \
                + (1.0 - mon.ema) * float(self._esc_score[slot])
            self._esc_score[slot] = s
            req.esc_peak_score = max(req.esc_peak_score, s)
            if mon.abort_threshold is not None \
                    and req.n_generated >= mon.min_tokens \
                    and s >= mon.abort_threshold:
                self._escalated_buf.append(self._escalate(slot))

    def _escalate(self, slot: int) -> Request:
        """Cancel ``slot`` mid-decode for cross-tier escalation: the same
        eviction mechanics as ``_preempt`` (pages freed, prompt + emitted
        prefix rebuilt as ``serve_tokens``), but the request leaves this
        tier — it lands in the escalated buffer for the pool to re-admit
        one tier up, where resumption is ONE chunked prefill whose
        final-chunk logits sample the upper tier's own next token. No
        ``reprefill_tokens`` are charged here: the re-prefill runs on (and
        is billed to) the tier above."""
        req = self.sched.running[slot]
        self._publish_resident(slot)
        self.cache.free_slot(slot)
        if self.draft_cache is not None:
            self.draft_cache.free_slot(slot)
        self._next_in[slot] = tok.PAD
        self._temps[slot] = self.temperature
        self._esc_score[slot] = 0.0
        req.serve_tokens = np.concatenate(
            [req.tokens, np.asarray(req.out, np.int32)])
        req.prefill_pos = 0
        req.escalations += 1
        self.stats.escalations += 1
        return self.sched.escalate(slot)

    def drain_escalated(self) -> List[Request]:
        """Streams cancelled up a tier since the last drain. The pool
        drains this every step and hands each request to the next tier's
        ``resubmit``; a bare engine with a monitor set should drain it
        too, or escalated streams are silently parked."""
        out, self._escalated_buf = self._escalated_buf, []
        return out

    def resubmit(self, req: Request) -> Request:
        """Accept an escalated hand-off from the tier below: re-queue the
        in-flight request for ordinary re-admission (its ``serve_tokens``
        — prompt + emitted prefix — prefills as one chunk stream). The
        bounded-queue cap does not apply — this is a continuation already
        admitted by the pool's policy, not a new arrival — but the
        capacity shed does: a continuation this tier could never fit
        (longer context than the slot cap, or a worst-case footprint past
        the whole pool) retires "rejected" instead of wedging the queue."""
        cap = self.cache.max_pages_per_slot * self.cache.page_size
        remaining = req.max_new_tokens - req.n_generated
        peak = self.cache.pages_for(
            min(len(req.serve_tokens) + remaining - 1, cap))
        if len(req.serve_tokens) + 1 > cap \
                or peak > self.cache.stats.num_pages:
            return self._shed(req)
        return self.sched.requeue(req)

    def _preemptible(self, floor_priority: Optional[int] = None) -> List[int]:
        """DECODING slots eligible for eviction: under the per-request
        preemption cap and (when ``floor_priority`` is given) strictly
        lower priority than the contender. Mid-prefill slots are never
        victims — evicting one reclaims pages a re-admission immediately
        re-needs, pure waste."""
        out = []
        for slot, req in self.sched.running.items():
            if req.state != DECODING \
                    or req.preemptions >= self.max_preemptions:
                continue
            if floor_priority is not None \
                    and req.priority >= floor_priority:
                continue
            out.append(slot)
        return out

    def _try_preempt(self, incoming: Request) -> bool:
        """Evict the lowest-priority latest-arrival eligible DECODING slot
        to make room for ``incoming`` (strictly higher priority, waiting at
        least ``preempt_after_s``). Returns whether a slot was freed."""
        if time.monotonic() - incoming.submit_t < self.preempt_after_s:
            return False
        victims = self._preemptible(floor_priority=incoming.priority)
        if not victims:
            return False
        slot = min(victims, key=lambda s: (self.sched.running[s].priority,
                                           -self.sched.running[s].rid))
        self._preempt(slot)
        return True

    def _resolve_stall(self) -> bool:
        """Zero-progress escape hatch: evict one running slot so its pages
        unwedge the rest. Only fires when eviction can help — someone else
        is waiting for the pages (pending work, or at least two occupied
        slots mutually stuck); a lone request that cannot step will never
        benefit from evicting itself. Ignores priority: any slot under the
        preemption cap is fair game, lowest priority first."""
        if not self.sched.pending and len(self.sched.running) < 2:
            return False
        victims = self._preemptible()
        if not victims:
            return False
        slot = min(victims, key=lambda s: (self.sched.running[s].priority,
                                           -self.sched.running[s].rid))
        self._preempt(slot)
        return True

    def _expire(self, retired: List[Request]) -> None:
        """Cancel every request past its deadline/timeout — queued ones are
        dropped, running ones reclaimed mid-stream (tokens already emitted
        are kept). Finish reason "deadline" either way."""
        now = time.monotonic()
        for req in [r for r in self.sched.pending if r.expired(now)]:
            self.sched.drop_pending(req)
            self.stats.deadline_misses += 1
            self._finish_unslotted(req, "deadline", sink=retired)
        for slot in [s for s, r in self.sched.running.items()
                     if r.expired(now)]:
            retired.append(self._retire(slot, "deadline"))

    def _push_token(self, req: Request, token: int) -> Optional[Request]:
        """Record an emitted token; retire on EOS / request cap."""
        req.out.append(int(token))
        req.token_t.append(time.monotonic())
        if token == tok.EOS:
            return self._retire(req.slot, "eos")
        if req.n_generated >= req.max_new_tokens:
            return self._retire(req.slot, "length")
        self._next_in[req.slot] = token
        return None

    def _reserved_prefill_pages(
            self, cache: Optional[PagedKVCache] = None) -> int:
        """Pages the mid-prefill slots still need for the rest of their
        prompts. Chunked admission allocates incrementally, so these pages
        are not in the pool's in-use count yet; admission control must not
        hand them to a new request. ``cache`` defaults to the target's;
        the draft mirror owes its pool the same promise."""
        cache = cache or self.cache
        r = 0
        for slot in self.sched.prefilling_slots():
            req = self.sched.running[slot]
            r += cache.pages_for(len(req.serve_tokens)) \
                - cache.owned_pages(slot)
            if cache.page_is_shared(slot, req.prefill_pos):
                # a prefix hit forked mid-page: the slot's next chunk must
                # COW-split that page, which costs one page the footprint
                # arithmetic above doesn't see
                r += 1
        return r

    def _admit(self, retired: List[Request]) -> int:
        """Claim free slots for pending requests, priority-then-FIFO with a
        bounded head-of-line lookahead: when the head doesn't fit the pool
        right now, the first of the next ``admit_lookahead - 1`` queued
        requests that does fit overtakes it (FIFO is preserved within
        whatever fits — a skipped head stays ahead of everyone behind it
        for the next attempt). When nothing in the window fits — or no
        slot is free — and the head outranks a running request, the
        lowest-priority DECODING slot is preempted to make room. Chunked
        mode just assigns the slot (chunks run in ``_prefill_step``);
        one-shot mode prefills the whole prompt and scatters it into
        freshly allocated pages. Returns slots-worth of progress (admitted
        + preempted-for-admission)."""
        admitted = 0
        while self.sched.pending:
            if not self.sched.has_free_slot:
                if self._try_preempt(self.sched.pending[0]):
                    admitted += 1   # progress: a slot was freed for the head
                    continue
                break
            reserve = self._reserved_prefill_pages() if self.prefill_chunk \
                else 0
            d_reserve = self._reserved_prefill_pages(self.draft_cache) \
                if self.draft_cache is not None else 0

            def fits(r):
                # a spec engine admits only what BOTH pools can hold — the
                # draft mirror grows chunk-for-chunk with the target. Full
                # pages a prefix walk would map shared discount the demand
                hp = self.cache.prefix.peek_pages(r.serve_tokens[:-1]) \
                    if self.cache.prefix is not None else 0
                return self.cache.can_admit(len(r.serve_tokens),
                                            reserve=reserve, hit_pages=hp) \
                    and (self.draft_cache is None
                         or self.draft_cache.can_admit(len(r.serve_tokens),
                                                       reserve=d_reserve))
            idx = next(
                (i for i, r in enumerate(
                    self.sched.pending[:self.admit_lookahead])
                 if fits(r)), None)
            if idx is None:
                self.stats.admission_stalls += 1
                if self._try_preempt(self.sched.pending[0]):
                    continue   # freed pages: rescan the window
                break
            req = self.sched.admit(idx)
            self._temps[req.slot] = self._req_temp(req)
            admitted += 1
            self.stats.admitted += 1
            if self.cache.prefix is not None:
                self._prefix_admit(req)
            if self.prefill_chunk:
                continue   # state PREFILLING; chunks run this same step
            n_tok = len(req.serve_tokens)
            spad = _round_up(n_tok, self.cache.page_size)
            logits, kv = self._prefill(
                self.params,
                {"tokens": jnp.asarray(req.serve_tokens[None])}, spad)
            pages = self.cache.alloc_slot(req.slot, n_tok)
            kp, vp = self._scatter(self.cache.pool["k_pages"],
                                   self.cache.pool["v_pages"],
                                   kv["k"], kv["v"], jnp.asarray(pages))
            self.cache.pool = {"k_pages": kp, "v_pages": vp}
            self.stats.prefill_tokens += n_tok
            req.prefill_pos = n_tok
            req.state = DECODING
            first = int(_sample(self._next_key(), logits,
                                self._req_temp(req))[0])
            done = self._push_token(req, first)
            if done is not None:
                retired.append(done)
        return admitted

    def _prefix_admit(self, req: Request) -> None:
        """Walk the prefix tree with the freshly admitted prompt (minus its
        final token — that token's logits sample the first output, so it
        always recomputes and every admission prefills at least one chunk)
        and map the longest cached prefix read-only into the slot.
        ``prefill_pos`` jumps to the fork point: the matched pages' chunks
        never launch, never charge the step's prefill budget, and never
        count as dispatches — TTFT drops to the fork-tail prefill."""
        toks = req.serve_tokens
        pages, matched = self.cache.prefix.match(toks[:len(toks) - 1])
        if not matched:
            self.stats.prefix_misses += 1
            return
        self.cache.map_shared(req.slot, pages, matched)
        req.prefill_pos = matched
        req.prefix_hit_tokens += matched
        self.stats.prefix_hits += 1
        self.stats.prefix_hit_tokens += matched
        self.stats.prefix_hit_pages += len(pages)

    def _cow_split(self, slot: int, pos: int) -> bool:
        """First write into a page ``slot`` shares: allocate a private
        replacement, device-copy the page's K/V, repoint the slot's table
        entry (``PagedKVCache.cow_page``). False when the pool can't supply
        the replacement page even after tree eviction — the caller stalls
        the write like any other page stall."""
        pair = self.cache.cow_page(slot, pos)
        if pair is None:
            return False
        src, dst = pair
        kp, vp = self._copy_page(self.cache.pool["k_pages"],
                                 self.cache.pool["v_pages"],
                                 jnp.asarray(src), jnp.asarray(dst))
        self.cache.pool = {"k_pages": kp, "v_pages": vp}
        self.stats.cow_splits += 1
        return True

    def _chunk_width(self, remaining: int) -> int:
        """Bucketed width of the next chunk: full chunks at prefill_chunk,
        ragged tails at a power of two capped by the chunk width (a
        non-power-of-two prefill_chunk must not widen the tail shape past
        the per-chunk latency bound the knob sets)."""
        return self.prefill_chunk if remaining >= self.prefill_chunk \
            else min(_bucket(remaining), self.prefill_chunk)

    def chunk_widths(self, prompt_len: int) -> List[int]:
        """The bucketed chunk widths a prompt of ``prompt_len`` tokens will
        trace, in admission order — warm one prompt per distinct width to
        keep every prefill compile out of a timed window."""
        widths, r = [], prompt_len
        while r > 0 and self.prefill_chunk:
            w = self._chunk_width(r)
            widths.append(w)
            r -= min(r, w)
        return widths

    def _dispatch_prefill(self, group: List[tuple], width: int,
                          retired: List[Request]) -> None:
        """Launch ONE prefill kernel over the stacked chunks of ``group``
        ((req, n_new) rows sharing the bucketed chunk ``width``), the batch
        padded to a power of two so packed compiles stay bounded. Padding
        rows carry n_new=0, an all-zero page-table row, and state row 0, so
        their K/V writes land on the reserved scratch page, their attention
        is fully masked, and their recurrent-state writes land on the
        reserved scratch row. The page walk is bounded by the group's live
        maximum context (see _pages_bound); sliding-window runs may start
        it at the group's first live window page (see _window_start)."""
        with TraceAnnotation("engine.prefill.dispatch"):
            B = _bucket(len(group))
            mp = self.cache.max_pages_per_slot
            chunk = np.full((B, width), tok.PAD, np.int32)
            # np copies throughout: the allocator mutates the page table
            # while the dispatched kernel may still be reading it (CPU
            # zero-copy alias)
            pt = np.zeros((B, mp), np.int32)
            start = np.zeros((B,), np.int32)
            n_new = np.zeros((B,), np.int32)
            rows = np.zeros((B,), np.int32)          # 0 = scratch state row
            for i, (req, n) in enumerate(group):
                chunk[i, :n] = req.serve_tokens[req.prefill_pos:
                                                req.prefill_pos + n]
                pt[i] = self.cache.page_table[req.slot]
                start[i] = req.prefill_pos
                n_new[i] = n
                rows[i] = req.slot + 1
            bound = self._pages_bound(int((start + n_new).max()))
            # earliest position any REAL row's first chunk query can see
            # under the window: min(start) - (window - 1)
            w = self.bundle.cfg.sliding_window
            wstart = self._window_start(
                int(start[:len(group)].min()) - max(w - 1, 0))
            if (B, width, bound, wstart) not in self._chunk_shapes:
                self._chunk_shapes.add((B, width, bound, wstart))
                self.stats.prefill_compiles += 1
            rec = self.rstate.state if self.rstate is not None else None
            x_last, kp, vp, rec = self._prefill_chunk_fn(
                self.params, self.cache.pool["k_pages"],
                self.cache.pool["v_pages"], rec, jnp.asarray(chunk),
                jnp.asarray(pt), jnp.asarray(start), jnp.asarray(n_new),
                jnp.asarray(rows), bound, wstart)
            self.cache.pool = {"k_pages": kp, "v_pages": vp}
            if self.rstate is not None:
                self.rstate.state = rec
            if self.draft_cache is not None:
                # mirror the same chunk rows into the draft sibling's cache
                # so every DECODING slot's draft context is ready to
                # speculate the moment its prompt lands (draft pages were
                # extended alongside the target's in _prefill_step). Draft
                # stacks are pure global attention, so window_start is 0
                dpt = np.zeros((B, mp), np.int32)
                for i, (req, n) in enumerate(group):
                    dpt[i] = self.draft_cache.page_table[req.slot]
                d_bound = self._pages_bound(int((start + n_new).max()),
                                            cache=self.draft_cache)
                kp, vp = self._draft_prefill_fn(
                    self.draft_params, self.draft_cache.pool["k_pages"],
                    self.draft_cache.pool["v_pages"], jnp.asarray(chunk),
                    jnp.asarray(dpt), jnp.asarray(start), jnp.asarray(n_new),
                    d_bound, 0)
                self.draft_cache.pool = {"k_pages": kp, "v_pages": vp}
        self.stats.prefill_dispatches += 1
        finishing = []
        for i, (req, n) in enumerate(group):
            req.prefill_pos += n
            self.stats.prefill_tokens += n
            self.stats.prefill_chunks += 1
            if self.cache.prefix is not None:
                # completed full pages are shareable the moment their K/V
                # lands: a fan-out sibling admitted next step forks here
                self.cache.prefix_publish(req.slot, req.serve_tokens,
                                          req.prefill_pos)
            if req.prefill_pos == len(req.serve_tokens):
                finishing.append((i, req))
        if finishing:
            # one vocab projection per dispatch, and only when a prompt
            # finished: its row's logits sample that request's first token
            with TraceAnnotation("engine.prefill.sync"):
                logits = self._lm_head(self.params, x_last)[:, 0]
            for i, req in finishing:
                req.state = DECODING
                with TraceAnnotation("engine.prefill.sync"):
                    first = int(_sample(self._next_key(), logits[i:i + 1],
                                        self._req_temp(req))[0])
                done = self._push_token(req, first)
                if done is not None:
                    retired.append(done)

    def _prefill_step(self, retired: List[Request]) -> List[int]:
        """Advance each PREFILLING slot by AT MOST one chunk, in admission
        order, within the step's token budget. One chunk per slot per step
        is what bounds a decode slot's inter-token gap to a single chunk's
        prefill — a greedy drain of one prompt's chunks would recreate the
        one-shot stall the chunked path exists to remove. The due chunks
        are page-extended in one batched call (per-row stall fallback: a
        stalled row drops out of this step's pack, the rest proceed), then
        dispatched packed — slots sharing a bucketed width stack into one
        kernel launch of up to ``prefill_pack`` rows (``prefill_pack=0``
        restores the per-slot B=1 loop). Returns the slots advanced."""
        budget = self.prefill_budget
        ready: List[tuple] = []       # (req, n_new, width) advancing
        advanced: List[int] = []      # their slots, captured pre-dispatch
        pending = self.sched.prefilling_slots()
        while pending:
            cand: List[tuple] = []
            cand_slots: List[int] = []
            skipped: List[int] = []
            for slot in pending:
                req = self.sched.running[slot]
                remaining = len(req.serve_tokens) - req.prefill_pos
                width = self._chunk_width(remaining)
                # the budget is charged at the bucketed dispatch width —
                # the shape actually launched, which is what per-step
                # prefill latency scales with — not the unbucketed token
                # count. The first chunk always runs (a budget under one
                # chunk still progresses), and an over-budget slot is
                # skipped rather than breaking the scan: a ragged tail
                # chunk later in admission order that fits the leftover
                # budget still runs this step
                if (ready or cand) and budget < width:
                    skipped.append(slot)
                    continue
                cand.append((req, min(remaining, width), width))
                cand_slots.append(slot)
                budget -= width
            if not cand:
                break
            got = self.cache.extend_slots(cand_slots,
                                          [n for _, n, _ in cand])
            refunded = False
            for slot, (req, n, width), pages in zip(cand_slots, cand, got):
                if pages is not None and self.draft_cache is not None \
                        and self.draft_cache.extend_slot(slot, n) is None:
                    # draft pool stalled: undo the target extension so the
                    # mirrors stay in lockstep, and stall the row
                    self.cache.truncate_slot(slot, req.prefill_pos)
                    pages = None
                if pages is not None \
                        and self.cache.page_is_shared(slot, req.prefill_pos) \
                        and not self._cow_split(slot, req.prefill_pos):
                    # the chunk's first write lands in a shared page (a
                    # mid-page prefix fork) and the COW replacement page is
                    # unavailable: undo the extension and stall the row
                    self.cache.truncate_slot(slot, req.prefill_pos)
                    pages = None
                if pages is None:     # page stall: row drops out, rest run
                    self.stats.prefill_stalls += 1
                    # the chunk never dispatches, so its budget goes back —
                    # a slot skipped for budget above may fit after all
                    budget += width
                    refunded = True
                else:
                    ready.append((req, n, width))
                    advanced.append(slot)
            pending = skipped if refunded else []
        if not ready:
            return advanced
        if self.prefill_pack == 0:    # legacy per-slot dispatch (B=1)
            for req, n, width in ready:
                self._dispatch_prefill([(req, n)], width, retired)
        else:
            by_width: Dict[int, List[tuple]] = {}
            for req, n, width in ready:
                by_width.setdefault(width, []).append((req, n))
            for width, rows in by_width.items():
                for i in range(0, len(rows), self.prefill_pack):
                    self._dispatch_prefill(rows[i:i + self.prefill_pack],
                                           width, retired)
        return advanced

    # ----------------------------------------------------------- speculative
    def _spec_accept_sampled(self, row: np.ndarray, cand: List[int],
                             dlog: np.ndarray, tau: float
                             ) -> tuple[List[int], int]:
        """Standard speculative sampling over one slot's drafted chunk:
        accept draft token d_i with probability min(1, p_t(d_i)/p_d(d_i));
        at the first rejection resample from the residual
        norm(max(p_t - p_d, 0)); after a full acceptance draw the bonus
        token from the target's last-position distribution. The emitted
        stream is distributed exactly as target-only sampling. ``row``
        (gamma+1, V) target logits, ``dlog`` (gamma, V) draft logits, both
        softmaxed at ``tau``. Returns (tokens to emit, accepted count)."""
        gamma = len(cand)
        u = np.asarray(jax.random.uniform(self._next_key(), (gamma + 1,)),
                       np.float64)

        def softmax(z):
            z = np.asarray(z, np.float64) / max(tau, 1e-6)
            z = z - z.max(axis=-1, keepdims=True)
            e = np.exp(z)
            return e / e.sum(axis=-1, keepdims=True)

        def draw(p, uu):
            return int(min(np.searchsorted(np.cumsum(p), uu), len(p) - 1))

        p_t, p_d = softmax(row), softmax(dlog)
        n = 0
        while n < gamma:
            d = cand[n]
            if u[n] < min(1.0, p_t[n, d] / max(p_d[n, d], 1e-30)):
                n += 1
            else:
                break
        if n < gamma:
            res = np.maximum(p_t[n] - p_d[n], 0.0)
            tot = res.sum()
            probs = res / tot if tot > 0 else p_t[n]
        else:
            probs = p_t[gamma]
        return cand[:n] + [draw(probs, u[gamma])], n

    def _spec_round(self, retired: List[Request]) -> List[int]:
        """One cross-tier speculative round: the draft sibling streams
        ``spec_gamma`` candidate tokens for every eligible DECODING slot
        (batched micro-steps over the draft cache), then ONE target verify
        launch scores all the chunks and each slot emits its accepted
        prefix plus the target's correction/bonus token. Rejected suffixes
        roll back both caches via ``truncate_slot``. Returns the slots the
        round emitted for (they are done decoding this step).

        Eligibility is per-slot and conservative: the round must fit the
        slot's context cap (target grows by gamma+1 tokens) and BOTH pools'
        free pages, budgeted cumulatively across the selected slots against
        one snapshot — so the mid-round ``ensure_append``/``extend_slot``
        calls can never fail. An ineligible slot simply falls back to plain
        decode this step.

        Lag bookkeeping: the draft may trail the target by any number of
        tokens (1 after a full accept — the bonus token's K/V was never
        drafted; more after plain-decoded fallback steps). A slot with lag
        ℓ runs ℓ catch-up micro-steps first, feeding the already-known
        tokens (outputs discarded), so every slot always produces exactly
        gamma candidates and speculation recovers from degradation without
        any cache rebuild."""
        gamma = self.spec_gamma
        cap = self.cache.max_pages_per_slot * self.cache.page_size
        t_reserve = self._reserved_prefill_pages()
        d_reserve = self._reserved_prefill_pages(self.draft_cache)
        t_avail = self.cache.free_pages - t_reserve
        d_avail = self.draft_cache.free_pages - d_reserve
        slots: List[int] = []
        lags: Dict[int, int] = {}
        for slot in self.sched.decoding_slots():
            Lt = int(self.cache.seq_lens[slot])
            Ld = int(self.draft_cache.seq_lens[slot])
            if Lt + gamma + 1 > cap:
                continue   # the round would overrun the slot's context cap
            t_need = self.cache.pages_for(Lt + gamma + 1) \
                - self.cache.owned_pages(slot)
            d_need = self.draft_cache.pages_for(Lt + gamma) \
                - self.draft_cache.owned_pages(slot)
            if t_need > t_avail or d_need > d_avail:
                continue   # page pressure: plain decode this step instead
            t_avail -= t_need
            d_avail -= d_need
            slots.append(slot)
            lags[slot] = Lt - Ld
        if not slots:
            return []
        for s in slots:
            self.sched.running[s].state = DRAFTING
        # ---- draft phase: gamma + max_lag batched micro-steps. A slot
        # with lag ℓ joins at micro-step max_lag - ℓ (catch-up first), so
        # all slots finish together with gamma candidates each and the
        # draft cache resident exactly through the last candidate's
        # predecessor (L_target + gamma tokens).
        max_lag = max(lags.values())
        full: Dict[int, np.ndarray] = {}    # prompt+output, for catch-up
        cand: Dict[int, List[int]] = {s: [] for s in slots}
        dlog: Dict[int, List[np.ndarray]] = {s: [] for s in slots}
        inputs = np.full((self.n_slots,), tok.PAD, np.int32)
        for j in range(gamma + max_lag):
            act = [s for s in slots if j >= max_lag - lags[s]]
            if not act:
                continue
            for s in act:
                rel = j - (max_lag - lags[s])
                if rel < lags[s]:          # catch-up: feed the known token
                    if s not in full:
                        req = self.sched.running[s]
                        full[s] = np.concatenate(
                            [req.serve_tokens,
                             np.asarray(req.out, np.int32)])
                    inputs[s] = full[s][int(self.draft_cache.seq_lens[s])]
                elif rel == lags[s]:       # first candidate: feed next_in
                    inputs[s] = self._next_in[s]
                # else: inputs[s] already holds the previous draw
                ok = self.draft_cache.ensure_append(s, reserve=d_reserve)
                assert ok, "spec pre-check under-counted draft pages"
            active = np.zeros((self.n_slots,), bool)
            active[act] = True
            pt, sl = self.draft_cache.device_tables()
            bound = self._pages_bound(
                int(self.draft_cache.seq_lens[act].max()) + 1,
                cache=self.draft_cache)
            if bound not in self._draft_bounds:
                self._draft_bounds.add(bound)
                self.stats.decode_compiles += 1
            nxt, logits, kp, vp = self._draft_decode_fn(
                self.draft_params, self.draft_cache.pool["k_pages"],
                self.draft_cache.pool["v_pages"],
                jnp.asarray(inputs[:, None].copy()), pt, sl,
                jnp.asarray(active), self._next_key(),
                jnp.asarray(self._temps.copy()), bound)
            self.draft_cache.pool = {"k_pages": kp, "v_pages": vp}
            self.draft_cache.seq_lens[act] += 1
            self.stats.draft_steps += 1
            nxt, logits = np.asarray(nxt), np.asarray(logits)
            for s in act:
                if j - (max_lag - lags[s]) >= lags[s]:
                    cand[s].append(int(nxt[s]))
                    dlog[s].append(logits[s])
                inputs[s] = nxt[s]
        # ---- verify phase: one prefill-shaped launch scores every slot's
        # chunk [next_in, d_1..d_gamma] at positions Lt..Lt+gamma; position
        # c's logits give the target's next-token distribution after chunk
        # token c. extend_slot pre-advances seq_lens — safe, the chunk
        # kernel takes explicit start/n_new — and rollback truncates.
        for s in slots:
            self.sched.running[s].state = VERIFYING
        W = gamma + 1
        B = _bucket(len(slots))
        mp = self.cache.max_pages_per_slot
        chunk = np.full((B, W), tok.PAD, np.int32)
        pt = np.zeros((B, mp), np.int32)
        start = np.zeros((B,), np.int32)
        n_new = np.zeros((B,), np.int32)
        base: Dict[int, int] = {}
        for i, s in enumerate(slots):
            base[s] = int(self.cache.seq_lens[s])
            got = self.cache.extend_slot(s, W)
            assert got is not None, "spec pre-check under-counted pages"
            chunk[i, 0] = self._next_in[s]
            chunk[i, 1:] = cand[s]
            pt[i] = self.cache.page_table[s]
            start[i] = base[s]
            n_new[i] = W
        bound = self._pages_bound(int((start + n_new).max()))
        if (B, bound) not in self._verify_shapes:
            self._verify_shapes.add((B, bound))
            self.stats.prefill_compiles += 1
        logits, kp, vp = self._verify_fn(
            self.params, self.cache.pool["k_pages"],
            self.cache.pool["v_pages"], jnp.asarray(chunk),
            jnp.asarray(pt), jnp.asarray(start), jnp.asarray(n_new),
            bound, 0)
        self.cache.pool = {"k_pages": kp, "v_pages": vp}
        self.stats.verify_steps += 1
        logits = np.asarray(logits, np.float32)
        # ---- accept / emit / roll back, per slot (host-side)
        stepped: List[int] = []
        for i, s in enumerate(slots):
            req = self.sched.running[s]
            req.state = DECODING
            tau = float(self._temps[s])
            row = logits[i]
            if tau <= 0.0:
                # greedy-exact contract: accept the longest prefix matching
                # the target argmax, then emit the target's own pick —
                # byte-identical to non-speculative greedy decoding
                tgt = row.argmax(axis=-1).astype(np.int32)
                n = 0
                while n < gamma and cand[s][n] == int(tgt[n]):
                    n += 1
                emit = cand[s][:n] + [int(tgt[min(n, gamma)])]
            else:
                emit, n = self._spec_accept_sampled(
                    row, cand[s], np.stack(dlog[s]), tau)
            req.drafted_tokens += gamma
            self.stats.drafted_tokens += gamma
            done, k = None, 0
            for t, token in enumerate(emit):
                if t < n:
                    k += 1      # draft tokens actually emitted
                self.stats.decode_tokens += 1
                done = self._push_token(req, int(token))
                if done is not None:
                    retired.append(done)   # EOS / cap mid-chunk: slot freed
                    break
            req.accepted_tokens += k
            req.rejected_tokens += gamma - k
            self.stats.accepted_tokens += k
            self.stats.rejected_tokens += gamma - k
            if done is None:
                # roll the rejected suffix back: target keeps the accepted
                # prefix + the chunk token it was conditioned on; the draft
                # trails at min (full accept leaves it one behind — the
                # bonus token — which next round's catch-up repays)
                keep = base[s] + n + 1
                self.cache.truncate_slot(s, keep)
                self.draft_cache.truncate_slot(
                    s, min(int(self.draft_cache.seq_lens[s]), keep))
            stepped.append(s)
        self.stats.spec_rounds += 1
        return stepped

    # ------------------------------------------------------------------ step
    def step(self, spec: bool = True) -> List[Request]:
        """Cancel expired requests, admit (preempting if priority demands),
        advance prefill chunks under the step budget, decode — one
        speculative round over eligible slots when a draft is attached
        (draft gamma candidates, one target verify, emit the accepted
        prefix + correction/bonus), one token per remaining DECODING slot
        otherwise — and retire. ``spec=False`` forces every slot onto the
        plain decode path this step (the pool's degradation hook while a
        draft tier is stalled). Returns the requests completed during this
        step, including any shed at submit since the last step.

        Each phase opens a profiler span (``engine.step`` around
        ``engine.expire``, ``.admit``, ``.prefill``, ``.spec``,
        ``.decode.prep``, ``.dispatch``, ``.sync``, ``.emit``): on the
        device trace's clock while a profiler session runs, and nearly
        free otherwise. Spans read no clock."""
        with TraceAnnotation("engine.step"):
            retired: List[Request] = self.drain_shed()
            with TraceAnnotation("engine.expire"):
                self._expire(retired)
            with TraceAnnotation("engine.admit"):
                progressed = self._admit(retired)
            prefilled: List[int] = []
            if self.prefill_chunk:
                with TraceAnnotation("engine.prefill"):
                    prefilled = self._prefill_step(retired)
                progressed += len(prefilled)
            spec_slots: List[int] = []
            if self.spec_gamma and spec:
                with TraceAnnotation("engine.spec"):
                    spec_slots = self._spec_round(retired)
            with TraceAnnotation("engine.decode.prep"):
                steppable = self._grow_decode_slots(spec_slots, retired)
                if steppable:
                    args = self._decode_inputs(steppable)
            if steppable:
                with TraceAnnotation("engine.decode.dispatch"):
                    nxt, unc, kp, vp, rec = self._decode(*args)
                    self.cache.pool = {"k_pages": kp, "v_pages": vp}
                    if self.rstate is not None:
                        self.rstate.state = rec
                    self.cache.seq_lens[steppable] += 1
                with TraceAnnotation("engine.decode.sync"):
                    nxt = np.asarray(nxt)
                    if self.escalation is not None:
                        unc = np.asarray(unc)
                with TraceAnnotation("engine.decode.emit"):
                    for slot in steppable:
                        self.stats.decode_tokens += 1
                        done = self._push_token(self.sched.running[slot],
                                                int(nxt[slot]))
                        if done is not None:
                            retired.append(done)
                    self.stats.decode_steps += 1
                    if self.escalation is not None:
                        self._watch_escalation(steppable, unc)
            elif not spec_slots and not progressed and not retired \
                    and (self.sched.running or self.sched.pending):
                # nothing decoded, no prefill advanced, nothing admitted or
                # retired, yet work remains. Resolution ladder: (1) pages
                # held externally (hold_pages pressure) make the stall
                # transient back-pressure — wait it out; (2) else evict a
                # running slot if that can unwedge anyone
                # (_resolve_stall); (3) otherwise occupied slots all
                # stalled on pages, or a pending request can't admit into
                # an otherwise idle pool — neither can ever resolve
                if self.cache.held_pages:
                    self.stats.stall_steps += 1
                elif self._resolve_stall():
                    progressed += 1
                else:
                    raise RuntimeError(
                        "page pool deadlock: no slot could step and no "
                        "request could admit or retire; provision more "
                        "pages")
            if steppable or spec_slots or progressed or retired:
                # prefill-only steps count too: leaving them out of
                # ``steps`` would overstate mean occupancy under heavy
                # admission. Union, not sum: a slot whose final chunk
                # landed this step decodes this same step and is busy once
                self.stats.steps += 1
                self.stats.occupancy_sum += len(
                    set(steppable) | set(prefilled) | set(spec_slots))
                if prefilled:
                    self.stats.prefill_steps += 1
                    if not steppable and not spec_slots:
                        self.stats.prefill_only_steps += 1
            return retired

    def _grow_decode_slots(self, spec_slots: List[int],
                           retired: List[Request]) -> List[int]:
        """The DECODING slots that plain-decode this step, each with room
        for the token it writes: a slot at its context cap retires, one
        whose write page is shared and cannot be split waits a step.
        ``spec_slots`` already emitted this step."""
        cap = self.cache.max_pages_per_slot * self.cache.page_size
        # decode growth must not eat pages promised to mid-prefill slots
        reserve = self._reserved_prefill_pages() if self.prefill_chunk else 0
        steppable = []
        for slot in self.sched.decoding_slots():
            if slot in spec_slots:
                continue          # already emitted this step's token(s)
            pos = int(self.cache.seq_lens[slot])
            if pos + 1 > cap:
                retired.append(self._retire(slot, "context_cap"))
            elif self.cache.page_is_shared(slot, pos) \
                    and not self._cow_split(slot, pos):
                pass   # shared write page, COW stalled: skip this step
            elif self.cache.ensure_append(slot, reserve=reserve):
                steppable.append(slot)
        if self.spec_gamma and steppable:
            # spec was configured but at least one slot plain-decodes this
            # step — disabled (draft tier stalled), page pressure, or the
            # round would overrun its context cap
            self.stats.spec_fallbacks += 1
        return steppable

    def _decode_inputs(self, steppable: List[int]) -> tuple:
        """The decode step's arguments for ``steppable``, in its order."""
        active = np.zeros((self.n_slots,), bool)
        active[steppable] = True
        pt, sl = self.cache.device_tables()
        # live walk bound: every steppable slot's context — including the
        # token this step writes — fits in ``bound`` pages, so the decode
        # kernel's page walk scales with the live max context, not the
        # engine-wide static table width. Inactive slots may exceed the
        # bound; their output is garbage the step masks
        bound = self._pages_bound(int(self.cache.seq_lens[steppable].max())
                                  + 1)
        # sliding-window runs start their walk at the steppable slots'
        # first live window page: the earliest in-window key of slot b is
        # (seq_lens[b] + 1) - window
        wstart = self._window_start(
            int(self.cache.seq_lens[steppable].min()) + 1
            - self.bundle.cfg.sliding_window)
        if (bound, wstart) not in self._decode_bounds:
            self._decode_bounds.add((bound, wstart))
            self.stats.decode_compiles += 1
        self._count_decode_blocks(active, bound, wstart)
        rec = self.rstate.state if self.rstate is not None else None
        # host copies: _next_in is mutated after the launch while the
        # dispatched step may still be reading it (CPU zero-copy alias;
        # jnp.array of a numpy array makes no host copy)
        return (self.params, self.cache.pool["k_pages"],
                self.cache.pool["v_pages"], rec,
                jnp.asarray(self._next_in[:, None].copy()), pt, sl,
                jnp.asarray(active), self._next_key(),
                jnp.asarray(self._temps.copy()), bound, wstart)

    def _count_decode_blocks(self, active: np.ndarray, bound: int,
                             wstart: int) -> None:
        """Add the decode kernel's walked and live blocks for this step,
        from the lengths it sees: each active slot's context with the
        token it writes, 0 for a slot the step masks."""
        if not self._attn_windows:
            return
        cap = self.cache.max_pages_per_slot * self.cache.page_size
        lens = np.where(active, np.minimum(self.cache.seq_lens + 1, cap), 0)
        kp = self.cache.pool["k_pages"]
        for window, n_layers in self._attn_windows.items():
            walked, live = walk_blocks(
                lens, page_size=self.cache.page_size, n_kv_heads=kp.shape[2],
                head_dim=kp.shape[4], itemsize=kp.dtype.itemsize,
                pages_bound=bound, pages_start=wstart if window else 0,
                window=window)
            self.stats.decode_blocks_walked += n_layers * walked
            self.stats.decode_blocks_live += n_layers * live

    def run(self) -> List[Request]:
        """Drain the queue; returns all requests retired during the drain
        (requests shed at submit included)."""
        done: List[Request] = self.drain_shed()
        while self.sched.has_work:
            done.extend(self.step())
        return done

    # ----------------------------------------------------------- compat API
    def serve(self, query_tokens: np.ndarray, seed: int = 0
              ) -> tuple[np.ndarray, np.ndarray]:
        """Batch-API wrapper: submit every row of ``query_tokens`` (N, L)
        int32, drain, return (responses (N, T) int32 PAD-tailed, lengths
        (N,) generated-token counts) like ``Engine.serve`` — elementwise
        identical to it at temperature 0."""
        self.reseed(seed)
        reqs = [self.submit(row) for row in query_tokens]
        self.run()
        T = self.max_new_tokens
        out = np.full((len(reqs), T), tok.PAD, np.int32)
        lens = np.zeros((len(reqs),), np.int32)
        for i, r in enumerate(reqs):
            lens[i] = r.n_generated
            out[i, :r.n_generated] = r.out[:T]
        return out, lens
