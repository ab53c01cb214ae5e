"""Continuous-batching engine core: jitted programs of static shape, zero
recompiles.

The one-shot path (inference.make_generate_fn) compiles prefill + a
`lax.scan` of decode steps into ONE program per (batch, prompt_len,
max_new_tokens) triple — a new request shape means a new XLA program,
and nothing can join until the scan returns. This engine splits the
same `decode_apply` primitive into separately-jitted functions with
STATIC shapes, so batch composition can churn at token granularity:

- `prefill+admit` (one compile per prompt bucket width): run the new
  request's prompt through a batch-1 scratch cache at slot-local
  positions [0, w), then scatter the scratch rows into the slot's
  freshly allocated blocks of the paged pool (serve/kv_pages.py) and the
  next-token logits into the slot's row; with the prefix cache or
  chunked prefill the prompt is instead appended through the slot's page
  table (`_prefix_prefill`, one compile per suffix bucket);
- `decode step` (one compile, ever): sample one token per slot from the
  carried last-logits, apply the model batch-wide at s=1 with every slot
  writing at its OWN position through its page-table row, return new
  logits/tokens. Free slots ride along emitting pad tokens — their
  writes land in the garbage block and are invisible by masking. A
  lax.scan runs `decode_burst` such steps per dispatch (multi-step
  scheduling) so the constant host/dispatch cost amortizes over K
  tokens; releases become burst-granular, the tokens do not change
  (pinned in tests/test_serve_engine.py).

Prompts are padded into a small set of bucket widths
(EngineConfig.prompt_buckets), so the prefill jit cache is bounded by
the bucket count however many distinct prompt lengths arrive — the
"no recompilation churn" property the scheduler tests pin via
`compile_stats()`.

Sampling is per-slot (each request carries its own fold_in'd PRNG
chain), so a request's tokens do not depend on what else shares the
batch — the property that makes continuous batching transparent to
clients. Greedy decode matches the one-shot generator
(tests/test_serve_equivalence.py) because both paths run the same
`decode_apply` and the same `sample_logits`.

There is ONE engine, `PagedEngine`; the scheduler drives it through
`admit_gate` / `admit` / `step_burst` / `release`, and `serve`
(serve/cli.py), the in-process router and the worker process all build
it — the engine the benchmark measures.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ddp_practice_tpu.inference import (
    decode_apply,
    make_cache,
    sample_logits,
    sample_logits_batch,
)
from ddp_practice_tpu.serve.kv_pages import (
    GARBAGE_BLOCK,
    INDEX_LEAF,
    LATENT_LEAF,
    SLOT_STATS_LEAF,
    WINDOW_STATS_LEAF,
    BlockAllocator,
    PageGroup,
    RadixPrefixCache,
    SlotAllocator,
    cache_spec,
    copy_block,
    leaf_kind,
    leaf_name,
    make_paged_cache,
    per_slot,
    rewind_block_tail,
    scatter_prompt_blocks,
)
from ddp_practice_tpu.serve.spec import DraftSource, PromptLookupDraft
from ddp_practice_tpu.utils import backend
from ddp_practice_tpu.utils.trace import (
    ENGINE_LANE,
    NULL_SPAN as _NULL,
    SLOT_LANE_BASE,
)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Compile-time serving knobs (all closed over by the jitted fns)."""

    max_slots: int = 4
    # pool positions per slot; 0 = the model's max_len. This sizes the
    # DEFAULTS of the block pool (num_blocks / max_blocks_per_slot
    # below), not a hard span — per-slot capacity is
    # max_blocks_per_slot * block_size and may exceed the model's
    # max_len (RoPE positions are unbounded).
    max_len: int = 0
    # padded prompt widths for the bucketed prefill compile cache
    prompt_buckets: Tuple[int, ...] = (8, 16, 32, 64)
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 0.0
    eos_id: Optional[int] = None
    pad_id: int = 0
    # decode steps per dispatch (multi-step scheduling): a lax.scan of K
    # single-token steps amortizes the per-dispatch host overhead K-fold
    # at the cost of slot-release granularity — a request finishing
    # mid-burst holds its slot (and the scheduler discards its surplus
    # tokens) until the burst boundary, E[K/2] wasted slot-steps per
    # request vs the static baseline's E[max - asked]. K=1 is exact
    # token-granularity scheduling (the deterministic-test setting).
    decode_burst: int = 1
    # ---- the block pool ----
    # positions per pool block; the allocation granule. Multiples of 8
    # keep the TPU kernel's sublane tiling happy (ops/decode_attention).
    block_size: int = 16
    # pool blocks; 0 = 1 garbage block + max_slots * max_blocks_per_slot
    # (full backing — every slot can reach its capacity simultaneously).
    # Set smaller to oversubscribe (admission then gates on blocks).
    num_blocks: int = 0
    # per-slot page-table width = context cap in blocks; 0 =
    # ceil(max_len / block_size). THIS is a slot's attention span — size
    # it to the workload's real contexts, not the pool.
    max_blocks_per_slot: int = 0
    # pool blocks of the WINDOW page group (a model whose `cache_spec`
    # names window layers); 0 = 1 garbage block + max_slots * the most a
    # slot ever holds there, ceil((window + prefill_chunk) / block_size) + 1
    # (full backing). Smaller oversubscribes: admission and growth then gate
    # on this group's pages as on the global one's.
    window_blocks: int = 0
    # radix prefix cache over the block pool (serve/kv_pages.py
    # RadixPrefixCache): admissions whose prompt prefix is already
    # resident share those blocks refcounted and prefill only the
    # suffix. Changes the admission layout from left-padded to
    # canonical right-padded positions (sharing needs every request to
    # agree where token i of a prefix lives), so the prefill program is
    # `_prefix_prefill`, not the scratch+scatter pair — greedy tokens
    # stay equivalent (RoPE; pinned in tests/test_serve_equivalence.py).
    prefix_cache: bool = False
    # ---- speculative decoding (greedy only) ----
    # draft-free speculation (serve/spec.py): a host-side prompt-lookup
    # drafter proposes up to spec_k tokens per slot and ONE jitted
    # verify dispatch (`step_verify`) scores the whole window — a short
    # paged prefill — accepting the longest prefix that matches the
    # model's own argmaxes plus one corrected token. Greedy-exact:
    # emitted tokens are what plain decode would have produced, so this
    # is purely a latency lever. Requires temperature == 0.0 (exact
    # acceptance IS greedy string matching).
    spec_decode: bool = False
    # drafted window length per verify dispatch (tokens per proposal)
    spec_k: int = 4
    # prompt-lookup n-gram match lengths, tried longest-first
    spec_ngram_max: int = 3
    spec_ngram_min: int = 1
    # ---- per-slot sampling ----
    # temperature / top_k / top_p stop being compile-time constants:
    # every slot carries its own (temp, k, p) in small device arrays
    # shipped per dispatch (like the page table), and the decode
    # program samples through inference.sample_logits_batch — ONE
    # jitted program serves a batch mixing greedy and sampled requests,
    # and a request's params can never cause a recompile. Slots get
    # their params at admit (`admit(..., sampling=(t, k, p))`, None
    # fields falling back to the config values above). Excludes
    # spec_decode: exact acceptance is greedy string matching, which
    # per-request temperatures would break.
    per_slot_sampling: bool = False
    # ---- chunked prefill (with prefix_cache, or without it for a model
    # with recurrent state: chunks over the slot's own state, every
    # admission then a chunk admission at canonical positions) ----
    # split long COLD prompts into chunks of at most this many tokens,
    # prefilled one chunk per scheduler tick interleaved with decode
    # bursts (Sarathi-style): a long admit no longer stalls every
    # running stream for its whole prefill, so TTFT jitter is bounded
    # by one chunk's forward instead of the longest prompt's. 0 = off
    # (whole-prompt admission, the pre-16 behavior). Chunks ride the
    # `_prefix_prefill` program at canonical right-padded slot-local
    # positions — the prefix cache's layout — and a prompt
    # may now EXCEED the largest bucket: servability is bounded by the
    # per-slot block capacity, not the bucket table.
    prefill_chunk: int = 0
    # chunk forwards a scheduler tick runs beside its decode burst, handed
    # out oldest admission first (a slot may take several). 0 = one for
    # EVERY mid-prefill slot, whatever their number: under a flood of long
    # prompts every slot is mid-prefill at once and a tick is as many chunk
    # forwards, seconds between two tokens of a running stream; a cap
    # bounds that gap and finishes prompts in order
    prefill_chunks_per_tick: int = 0


def _sample_step(cfg: EngineConfig, last_logits, active, keys,
                 sampling=None):
    """One sampling step: per-slot PRNG chains, greedy fast path, pad
    tokens for free slots. Returns (tokens int32, new_keys).

    `sampling` is None (params baked from cfg — the legacy single-
    compile path, pytree-empty so it costs no trace arg) or a triple of
    traced (s,) arrays (temperature, top_k, top_p) — the
    per_slot_sampling path, where every slot samples under its own
    params via sample_logits_batch and the key chains ALWAYS advance
    (greedy rows discard their draw), so a request's stream never
    depends on its batchmates' params.

    Every caller runs it, and its own finite-logits and accept logic,
    under `jax.named_scope("sample")`: the token by which a profiler
    trace's device time is given to sampling (perf/lib/scopes.py)."""
    if sampling is not None:
        temp, tk, tp = sampling
        split = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
        subs, new_keys = split[:, 0], split[:, 1]
        toks = sample_logits_batch(
            last_logits, subs, temperature=temp, top_k=tk, top_p=tp
        )
    elif cfg.temperature == 0.0:
        toks = sample_logits(last_logits, None, temperature=0.0)
        new_keys = keys
    else:
        split = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
        subs, new_keys = split[:, 0], split[:, 1]
        toks = jax.vmap(
            lambda lg, k: sample_logits(
                lg[None], k, temperature=cfg.temperature,
                top_k=cfg.top_k, top_p=cfg.top_p,
            )[0]
        )(last_logits, subs)
    toks = jnp.where(
        active, toks.astype(jnp.int32), jnp.int32(cfg.pad_id)
    )
    return toks, new_keys


def _decode_donate(pool_argnum: int = 1) -> tuple:
    """donate_argnums for a pool-rewriting dispatch: the cache pool
    (arg 1 after params for decode, arg 0 for the CoW copy) is donated
    on TPU so XLA reuses its HBM in place — with a paged pool the buffer
    is the whole serving memory, big enough to care (ROADMAP
    engine-level item). Gated off on CPU, where donation is
    unimplemented and every dispatch would warn."""
    return (pool_argnum,) if backend.on_tpu() else ()


def _await_dispatch(*state) -> None:
    """Block until a dispatch's outputs are fully materialized — CPU
    backend only.

    XLA:CPU's thunk runtime can report a dispatch's small outputs
    (tokens, logits) ready while writes into the big cache buffers are
    still in flight; chaining the next dispatch off that state races
    the tail of the previous one, and the corrupted reads flip near-tie
    argmaxes run to run. One barrier per dispatch restores
    bit-determinism — every token-identity pin and bench identity gate
    in this repo relies on it. (Empirically: fresh engines replaying
    the same trace diverged with logit deltas of O(0.1-1), far beyond
    FP reassociation noise, and a block_until_ready on the dispatch
    state makes the divergence vanish.) On TPU execution is
    stream-ordered per core, so the barrier would only break dispatch
    pipelining — skip it.
    """
    if not backend.on_tpu():
        jax.block_until_ready(state)


def warm_engine(engine, widths=None) -> None:
    """Compile an engine's programs outside any timed/traced window:
    one admit per bucket width in play + one decode burst, then release.
    THE one warmup recipe — the in-process router's ReplicaHandle and
    the worker process (serve/worker.py) both call it, so a restarted
    replica re-warms exactly like a fresh one.
    The admit budgets only the one warmup burst: the default admit
    reserves the whole per-slot capacity, which an oversubscribed block
    pool can't cover even though the gated scheduler path serves it
    fine."""
    for w in widths or engine.buckets:
        slot = engine.admit([1] * w,
                            max_positions=engine.config.decode_burst)
        # chunk-admitted prompts (prefill_chunk) activate only once
        # every chunk has run — drive the chunk program to completion
        # so its compiles land in warmup too
        while engine.is_prefilling(slot):
            engine.prefill_step(slot)
        engine.step_burst()
        engine.release(slot)
        if engine.radix is not None:
            # the warm-up prompt's blocks must not stay cached: the next
            # width's prompt would match them and compile a NARROWER
            # suffix bucket than its own, and no request wants them
            engine.radix.clear()
    if engine.drafter is not None:
        # speculation on: the verify program is a THIRD compile that
        # must also land outside the timed/traced window. An all-ones
        # prompt makes the lookup drafter propose a full window (every
        # trailing n-gram recurs), so the real verify shape compiles.
        slot = engine.admit([1] * engine.buckets[0],
                            max_positions=engine.config.spec_k + 1)
        drafts, draft_lens, _ = engine.propose_drafts()
        engine.step_verify(drafts, draft_lens)
        engine.release(slot)
        # the warm dispatch must not pollute the metrics plane: flight
        # records and the delta-exported counters both reconcile against
        # these cumulative fields, and warmup tokens belong to no request
        engine.spec_drafted_tokens = 0
        engine.spec_accepted_tokens = 0
        engine.spec_dispatches = 0
    # warmup picks, scans and walks belong to no request either
    engine.moe_rows_held = engine.moe_rows_routed = 0
    engine.moe_rows_moved = engine.moe_rows_layout = 0
    engine.ssm_scan_tokens = engine.ssm_scan_padded_tokens = 0
    engine.sparse_pages_walked = engine.sparse_pages_held = 0
    engine.window_pages_walked = engine.window_pages_whole = 0
    if engine.wgroup is not None:
        engine.wgroup.freed = 0


class PagedEngine:
    """Paged-KV continuous batching: per-slot page tables, no shared clock.

    Pure mechanism: WHAT to admit/release and WHEN is the scheduler's
    job (serve/scheduler.py), which drives this class through
    `admit_gate` / `admit` / `step_burst` / `release`; the class owns the
    device state (the block pool, last-logits, per-slot PRNG keys) and
    the jitted programs. The cache is a pool of fixed-size blocks
    (serve/kv_pages.py) and every slot decodes at its OWN slot-local
    write position:

    - `admit` prefills the bucketed prompt into a batch-1 contiguous
      scratch cache at positions [0, w) and scatters it into freshly
      allocated blocks (one compile per bucket width);
    - `step_burst` appends each active slot's token at `lengths[slot]`
      through the page table and attends only that slot's occupied
      pages (ops/decode_attention.paged_decode_attention) — a step's
      attention span is the request's own context, not a pool-global
      [0, max_len);
    - `release` DEREFS the slot's blocks (serve/kv_pages.py refcounts):
      a block shared with the prefix cache or a fork sibling survives,
      a sole-owned one returns to the free list. Nothing ever drains
      and nothing rewinds;
    - a request may decode past the model's max_len: per-slot capacity
      is `max_blocks_per_slot * block_size` and RoPE positions are
      unbounded.

    PR 6 turned the pool into a MULTIPLIER instead of a partition:

    - **Prefix sharing** (`EngineConfig.prefix_cache`): admission walks
      a radix tree of previously served prompt blocks; matched blocks
      join the new slot's page table refcounted and only the prompt
      SUFFIX is prefilled (`_prefix_prefill`, one compile per suffix
      bucket — the hit's prefill chunks are skipped entirely). Sharing
      needs canonical slot-local positions, so this mode right-pads
      (attn_start 0) instead of left-padding.
    - **Copy-on-write**: before a burst writes into a block some other
      holder also references (a fork sibling's tail block), the block
      is first copied into a private one (`copy_block`, one compile
      ever) — which is what makes `fork` (n>1 sampling per prompt)
      memory-cheap: siblings share every prefix block and split only
      where they diverge.
    - **Block-aware preemption** replaces the PR-3 worst-case admission
      reservation: admission takes only the prompt blocks, and when
      growth finds the pool empty the engine first evicts unreferenced
      prefix-cache blocks (LRU), then preempts the YOUNGEST-admitted
      slot — its non-shared blocks free, the victim lands in
      `take_preempted()` and the scheduler re-prefills it on
      readmission (serve/scheduler.py). Admission at the same pool goes
      up because nobody holds blocks they may never use; the solo-fit
      admission gate ("never" when a request outgrows the whole pool)
      keeps the preemption cascade terminating.

    The optional tracer (`set_tracer`) records per-dispatch `prefill` /
    `decode_burst` / `verify` lane spans, each split into what the host
    prepares (`prefill_host`, `burst_plan`), the jitted call
    (`prefill_dispatch`, `burst_dispatch`) and the wait for the device
    (`burst_readback`). `set_tracer` also hands the recorder
    `jax.profiler.TraceAnnotation` (TraceRecorder.set_annotate), so
    while a profiler session is open every one of these spans is
    mirrored on the profiler's host line under a FIXED name
    (`serve:prefill`, `serve:burst_dispatch`, ...; request ids are span
    attrs, never names). tracer=None (default) keeps the dispatch path
    free of spans and annotations alike.

    The jitted methods' names are a contract too: a device trace shows
    each program as `jit_<method name>` ("XLA Modules" line), and the
    benchmark's readers find the prefill and decode programs by the
    substrings `prefill_admit`, `prefix_prefill`, `decode_burst` and
    `verify` (PERF.md §3; pinned by tests/test_span_tree.py). Rename
    `_prefill_admit` / `_prefix_prefill` / `_decode_burst` / `_verify`
    only together with those readers."""

    tracer = None
    replica = 0
    # per-burst surfacing for the streaming plane: how many decode
    # dispatches this engine ever ran and how many slots were live in
    # the last one. The scheduler stamps `burst_seq` onto each
    # TokenChunk's telemetry line, so per-chunk flight accounting can
    # tell "no bursts ran" (a stalled engine) from "bursts ran without
    # this request" (preempted / queued) when attributing a resume gap.
    burst_seq = 0
    last_burst_active = 0
    # (picks that landed on held experts, held experts with a row summed
    # over expert layers and steps, most rows one expert took) of the last
    # decode burst; None for a model without held experts
    last_burst_experts = None
    last_burst_sparse = None
    # (pages the window layers' walks read, pages whole walks would have) of
    # the last decode burst, as the program counted them; None for a model
    # without a window group
    last_burst_window = None

    def __init__(self, model, params, config: EngineConfig = EngineConfig(),
                 *, batch_stats: Any = None,
                 draft_source: Optional[DraftSource] = None) -> None:
        # a model with recurrent state (models/hybrid_lm.py): its layers
        # carry position, so attention may have no positional embedding
        # at all, and beside the pages every slot owns a fixed-size state
        self._recurrent = bool(getattr(model, "recurrent", False))
        pos_ok = ("rope", "none") if self._recurrent else ("rope",)
        if getattr(model, "pos_emb", None) not in pos_ok:
            raise ValueError(
                "PagedEngine needs pos_emb='rope' — slots decode at "
                "slot-local positions, which only relative positions "
                "survive (models/lm.py); pos_emb='none' is admitted "
                "where recurrent layers carry position"
            )
        if self._recurrent:
            # pages cannot re-derive a state: each of these needs a
            # sequence's state at a position that is not its end (ROADMAP
            # M6: snapshots at block boundaries). Chunks of ONE prompt run
            # in order need only the state at the sequence's end, which is
            # what the slot holds: `prefill_chunk` is admitted, through the
            # slot's own table, with nothing published to a radix tree
            for option, why in (
                ("prefix_cache", "a shared prefix's pages come without "
                 "the state at the prefix's end"),
                ("spec_decode", "a rejected draft cannot be rolled back "
                 "out of the state"),
            ):
                if getattr(config, option):
                    raise ValueError(
                        f"{option} is refused for a model with recurrent "
                        f"state: {why}")
        # a model whose window layers' pages are a group of their own
        # (serve/kv_pages.py CacheSpec): the pages behind a slot's window go
        # back to that group's allocator, so whatever needs them again is
        # refused (ROADMAP M3), and every admission is a chunk admission,
        # whose `window_prefill` walks the window's pages and no others
        spec = cache_spec(model)
        self._window = int(spec.window) if spec.window_layers else 0
        if self._window:
            for option, why in (
                ("prefix_cache", "a window layer's pages behind the window "
                 "are given back, so a published prefix has none to share"),
                ("spec_decode", "a rejected draft's positions cannot be "
                 "rewound over pages the window already gave back"),
            ):
                if getattr(config, option):
                    raise ValueError(
                        f"{option} is refused for a model with a window "
                        f"page group: {why}")
            if not config.prefill_chunk:
                raise ValueError(
                    "a model with a window page group is admitted in "
                    "chunks: set prefill_chunk (a whole prompt scattered "
                    "from a scratch prefill would need every page of its "
                    "window layers at once)")
        # every admission a chunk admission at canonical positions, told
        # where its padding starts: the recurrent models' and this one's
        self._chunk_only = self._recurrent or bool(self._window)
        if not config.prompt_buckets:
            raise ValueError("prompt_buckets must be non-empty")
        if config.decode_burst < 1:
            raise ValueError("decode_burst must be >= 1")
        if config.block_size < 1:
            raise ValueError("block_size must be positive")
        if config.spec_decode:
            if config.temperature != 0.0:
                raise ValueError(
                    "spec_decode needs temperature=0.0 — exact "
                    "acceptance is greedy string matching against the "
                    "model's own argmaxes (serve/spec.py)"
                )
            if config.spec_k < 1:
                raise ValueError("spec_k must be >= 1")
            if config.per_slot_sampling:
                raise ValueError(
                    "spec_decode excludes per_slot_sampling — exact "
                    "acceptance is greedy string matching, which a "
                    "slot sampling at its own temperature would break"
                )
        if config.prefill_chunk:
            if not config.prefix_cache and not self._chunk_only:
                raise ValueError(
                    "prefill_chunk needs prefix_cache=True — chunks "
                    "append at canonical right-padded positions through "
                    "the page table (_prefix_prefill), the layout only "
                    "the prefix-cache mode uses"
                )
            if config.prefill_chunk < 1:
                raise ValueError("prefill_chunk must be >= 1 (0 = off)")
            if config.prefill_chunk > max(config.prompt_buckets):
                raise ValueError(
                    f"prefill_chunk {config.prefill_chunk} exceeds the "
                    f"largest prompt bucket "
                    f"{max(config.prompt_buckets)} — each chunk is "
                    f"bucketed for the prefill compile cache"
                )
        self.model = model
        self.params = params
        self.batch_stats = batch_stats
        self.config = config
        self.max_len = config.max_len or model.max_len
        self.buckets = tuple(sorted(set(config.prompt_buckets)))
        bs = config.block_size
        self.max_blocks_per_slot = (
            config.max_blocks_per_slot or -(-self.max_len // bs)
        )
        self.max_context = self.max_blocks_per_slot * bs
        if self.buckets[-1] > min(self.max_context - 1, model.max_len):
            raise ValueError(
                f"largest prompt bucket {self.buckets[-1]} must fit the "
                f"scratch prefill (model max_len {model.max_len}) and "
                f"leave decode room in the per-slot capacity "
                f"{self.max_context}"
            )
        s = config.max_slots
        num_blocks = (
            config.num_blocks or 1 + s * self.max_blocks_per_slot
        )
        self.allocator = SlotAllocator(s)     # slot ids (metrics reads it)
        self.blocks = BlockAllocator(num_blocks)
        self.radix = (
            RadixPrefixCache(self.blocks, bs) if config.prefix_cache
            else None
        )
        # canonical slot-local positions: a prompt starts at position 0 and
        # is RIGHT-padded (the prefix cache's layout, and every admission of
        # a recurrent model under `prefill_chunk`: its chunks continue the
        # slot's own state, and a layer that counts blocks from position 0
        # needs the sequence to start there); else a bucket's LEFT padding
        # counts as positions
        self._canonical = config.prefix_cache or (
            self._chunk_only and bool(config.prefill_chunk))
        # matched tokens of the MOST RECENT admit (None = no prefix
        # cache): the scheduler reads this right after admit() to book
        # prefix_hit_tokens into the request's flight record
        self.last_prefix_hit: Optional[int] = None
        # the window group's host side: its own allocator and table, a slot
        # never past `window_pages` of it whatever its context
        self.wgroup = None
        if self._window:
            self.window_pages_a_slot = spec.window_pages(
                bs, config.prefill_chunk)
            self.wgroup = PageGroup(
                config.window_blocks or 1 + s * self.window_pages_a_slot,
                s, self.max_blocks_per_slot)
            if self.window_pages_a_slot > self.wgroup.blocks.num_blocks - 1:
                raise ValueError(
                    f"window_blocks {self.wgroup.blocks.num_blocks} cannot "
                    f"hold one slot's {self.window_pages_a_slot} pages")
        self._cache = make_paged_cache(
            model, num_blocks, bs, max_slots=s,
            window_blocks=self.wgroup.blocks.num_blocks if self._window
            else 0)
        flat = jax.tree_util.tree_flatten_with_path(self._cache)[0]
        # bytes of the per-slot state pool (gauge `ssm_state_bytes`), and
        # the expert layers' picks a decode step routes: slots x top-k x
        # layers (every row of the batch is computed, retired slots' too)
        self.ssm_state_bytes = int(sum(
            a.nbytes for path, a in flat if leaf_kind(path) == "state"))
        # bytes of the latent pools (gauge `latent_cache_bytes`): a model
        # with latent attention caches one row a token, not K and V
        self.latent_cache_bytes = int(sum(
            a.nbytes for path, a in flat if leaf_name(path) == LATENT_LEAF))
        # bytes of the compressed-key pools (gauge `index_cache_bytes`): a
        # block-sparse attention layer scores them every decode step
        self.index_cache_bytes = int(sum(
            a.nbytes for path, a in flat if leaf_name(path) == INDEX_LEAF))
        self._sparse_layers = sum(
            1 for path, _ in flat if leaf_name(path) == SLOT_STATS_LEAF)
        # attention layers by page group (a window layer declares
        # `window_stats`; every layer with K pages a `cached_key`)
        self._window_layers = sum(
            1 for path, _ in flat if leaf_name(path) == WINDOW_STATS_LEAF)
        self._global_layers = sum(
            1 for path, _ in flat if leaf_name(path) == "cached_key"
        ) - self._window_layers
        self.window_pages_walked = 0   # cumulative (metrics export)
        self.window_pages_whole = 0
        self._burst_freed = 0          # window pages the last burst gave back
        self._dense_len = int(getattr(
            getattr(model, "sparse", None), "dense_len", 0))
        self.sparse_pages_walked = 0   # cumulative (metrics export)
        self.sparse_pages_held = 0
        self._moe_layers = sum(
            1 for path, _ in flat if leaf_kind(path) == "stats")
        self._picks_a_step = (
            s * int(getattr(model, "top_k", 0)) * self._moe_layers)
        self.moe_rows_held = 0       # cumulative (metrics export)
        self.moe_rows_routed = 0
        self.moe_rows_moved = 0
        self.moe_rows_layout = 0
        # positions the recurrent layers' prefill scans ran over: the
        # prompts' own, and their buckets' left padding beside them
        self.ssm_scan_tokens = 0
        self.ssm_scan_padded_tokens = 0
        self._last_logits = jnp.zeros((s, model.vocab_size), model.dtype)
        self._keys = jnp.zeros((s, 2), jnp.uint32)
        self._active = np.zeros((s,), bool)
        # per-slot sampling mirrors (host side, shipped per dispatch
        # like _active when per_slot_sampling is on); config-filled, so
        # a slot admitted without overrides samples under the config's
        # own values
        self._temp = np.full((s,), config.temperature, np.float32)
        self._topk = np.full((s,), config.top_k, np.int32)
        self._topp = np.full((s,), config.top_p, np.float32)
        # chunk-admitted slots mid-prefill: slot -> {"prompt", "done"}.
        # The slot holds blocks and a page table but stays INACTIVE
        # (decode bursts pad it, preemption never picks it) until
        # prefill_step lands the final chunk.
        self._pending_prompt: dict = {}
        # host-side per-slot state; tiny, shipped to device per dispatch
        self._pt = np.zeros((s, self.max_blocks_per_slot), np.int32)
        self._len = np.zeros((s,), np.int32)
        self._attn = np.zeros((s,), np.int32)
        self._nblk = np.zeros((s,), np.int64)   # blocks in the table
        self._budget = np.zeros((s,), np.int64)  # admit-time block cap
        self._seq = np.zeros((s,), np.int64)     # admission order (LIFO
        self._admit_seq = 0                      # preemption victims)
        self._preempted: list = []   # slots evicted since last drain
        self.preemptions = 0         # cumulative (metrics export)
        self.last_finite = np.ones((1, s), bool)
        self._slot_trace: dict = {}  # slot -> trace_id (tracer attached)
        # replayable fork seeds: slot -> the request's SEED PATH — the
        # admit seed plus one fork ordinal per ancestor fork, e.g.
        # (seed,) for an admitted request, (seed, 2) for its second
        # fork child. fork() derives the child key by folding the path,
        # so a sibling's sample stream is a function of (admit seed,
        # fork order) alone — replayable across slot layouts and
        # independent of how many decode steps ran before the fork.
        self._slot_seed: dict = {}
        self._fork_n: dict = {}      # slot -> forks taken off it so far
        # speculative decoding (serve/spec.py): the host-side drafter
        # tracks every slot's context; its proposals feed step_verify.
        # Cumulative counters are the metrics-plane observable
        # (delta-exported by serve/metrics.py, same idiom as
        # `preemptions`).
        if config.spec_decode:
            self.drafter: Optional[DraftSource] = (
                draft_source if draft_source is not None
                else PromptLookupDraft(config.spec_ngram_max,
                                       config.spec_ngram_min)
            )
        else:
            self.drafter = None
        self.spec_drafted_tokens = 0
        self.spec_accepted_tokens = 0
        self.spec_dispatches = 0
        # the state pool is donated to the prefill too: it is gigabytes
        # (every slot's float32 state), and admission rewrites one row
        self._prefill_jit = jax.jit(
            self._prefill_admit,
            donate_argnums=_decode_donate() if self._recurrent else (),
        )
        self._decode_jit = jax.jit(
            self._decode_burst, donate_argnums=_decode_donate()
        )
        # the verify program (speculative decoding): one compile for the
        # (max_slots, spec_k) window shape, always in compile_stats so
        # the churn pins cover it even before the first dispatch
        self._verify_jit = jax.jit(
            self._verify, donate_argnums=_decode_donate()
        )
        # prefix-mode suffix prefill (one compile per suffix bucket) and
        # the copy-on-write block split (one compile, ever) — both in
        # compile_stats so the churn pins cover the new admission paths
        # (the pool is donated: a chunk rewrites a few pages of gigabytes)
        self._prefix_jit = jax.jit(
            self._prefix_prefill, donate_argnums=_decode_donate())
        self._cow_jit = jax.jit(
            copy_block, donate_argnums=_decode_donate(pool_argnum=0)
        )
        self._fork_jit = jax.jit(self._fork_rows)

    # ---------------------------------------------------------------- jitted
    def _prefill_admit(self, params, pool, last_logits, tokens,
                       attn_start, block_ids, slot):
        """tokens (1, w) left-padded; one compile per bucket width w.

        The scratch cache starts at cursor 0 — slot-local coordinates —
        so admission is placement-free: no alignment to anyone else's
        cursor, just a scatter of the w prefilled rows into this slot's
        blocks."""
        w = tokens.shape[1]
        scratch = make_cache(self.model, 1, w)
        scratch, logits = decode_apply(
            self.model, params, scratch, tokens,
            attn_start=attn_start[None], batch_stats=self.batch_stats,
        )
        pool = scatter_prompt_blocks(
            pool, scratch, block_ids, w, self.config.block_size, slot
        )
        with jax.named_scope("sample"):
            last_logits = lax.dynamic_update_slice(
                last_logits, logits[:, -1].astype(last_logits.dtype),
                (slot, 0),
            )
        return pool, last_logits

    def _prefix_prefill(self, params, pool, last_logits, tokens,
                        pos0, true_len, pt_row, slot):
        """Prefix-cache admission prefill: tokens (1, w) RIGHT-padded —
        the real suffix in rows [0, true_len) — appended at slot-local
        positions [pos0, pos0+w) THROUGH the page table, attending the
        shared prefix blocks [0, pos0) in place (models/vit.py paged
        s>1 path). One compile per suffix bucket width w. The pad rows
        write garbage K/V at positions past the context, which the
        causal mask hides until decode overwrites them; the next-token
        logits are the last REAL row's (dynamic true_len - 1).

        A recurrent model's chunk continues the slot's OWN state: the
        per-slot leaves are cut to row `slot` for the batch-1 call (zeros
        for a prompt's first chunk, pos0 == 0) and written back after, and
        the model is told where the padding starts (`real_lengths`), which
        its scans must not advance over."""
        extra, whole = {}, pool
        if self._recurrent:
            row = lambda a: lax.dynamic_slice(
                a, (slot,) + (0,) * (a.ndim - 1), (1,) + a.shape[1:])
            pool = jax.tree_util.tree_map_with_path(
                lambda path, a: jnp.where(pos0 == 0, 0, row(a)).astype(
                    a.dtype) if per_slot(path) else a, whole)
        if self._chunk_only:
            extra = {"real_lengths": true_len[None]}
        pool, logits = decode_apply(
            self.model, params, pool, tokens,
            batch_stats=self.batch_stats,
            page_table=pt_row, kv_lengths=pos0[None], **extra,
        )
        if self._recurrent:
            pool = jax.tree_util.tree_map_with_path(
                lambda path, a, w: lax.dynamic_update_slice(
                    w, a, (slot,) + (0,) * (a.ndim - 1))
                if per_slot(path) else a, pool, whole)
        with jax.named_scope("sample"):
            # (a model told `real_lengths` hands back that one row alone)
            last = logits[:, 0] if logits.shape[1] == 1 \
                else lax.dynamic_slice(
                    logits, (0, true_len - 1, 0), (1, 1, logits.shape[2])
                )[:, 0]
            last_logits = lax.dynamic_update_slice(
                last_logits, last.astype(last_logits.dtype), (slot, 0)
            )
        return pool, last_logits

    @staticmethod
    def _fork_rows(last_logits, keys, src, dst, key):
        """Duplicate one slot's carried sampling state into another
        (fork): same pending logits, a FRESH PRNG chain — siblings
        diverge by sampling, not by context."""
        row = lax.dynamic_slice(
            last_logits, (src, 0), (1, last_logits.shape[1])
        )
        last_logits = lax.dynamic_update_slice(last_logits, row, (dst, 0))
        keys = lax.dynamic_update_slice(keys, key[None], (dst, 0))
        return last_logits, keys

    def _decode_burst(self, params, pool, last_logits, attn_starts,
                      active, keys, page_table, lengths, sampling):
        """lax.scan of `decode_burst` paged single-token steps. Each step
        writes active slots' K/V at their own `lengths` position and
        advances only active lengths; retired slots keep scattering into
        the garbage block (kv_pages.GARBAGE_BLOCK) so shapes stay static."""

        def body(carry, _):
            pool, last_logits, keys, lengths = carry
            with jax.named_scope("sample"):
                finite = jnp.isfinite(last_logits).all(axis=-1)
                toks, keys = _sample_step(self.config, last_logits, active,
                                          keys, sampling)
            pool, logits = decode_apply(
                self.model, params, pool, toks[:, None],
                attn_start=attn_starts, batch_stats=self.batch_stats,
                page_table=page_table, kv_lengths=lengths, **idle,
            )
            lengths = lengths + active.astype(lengths.dtype)
            return (pool, logits[:, -1], keys, lengths), (toks, finite)

        # a recurrent model's slot between two chunks of its prompt is not
        # active and must keep its state through these steps: the model is
        # told which rows are padding
        idle = {"real_lengths": active.astype(jnp.int32)} \
            if self._recurrent and self.config.prefill_chunk else {}

        def rows_moved(pool):
            """(rows moved, rows of the whole layouts) the expert layers
            have counted into their `moe_rows` leaves."""
            return sum(a for path, a
                       in jax.tree_util.tree_flatten_with_path(pool)[0]
                       if leaf_kind(path) == "rows")

        # what the admissions since the last burst left in `moe_rows`
        admitted = rows_moved(pool)
        # expert layers count into their `moe_stats` leaf: zeroed here so
        # that after the scan it holds this burst's own sums
        # ... and sparse attention layers into `sparse_stats`, a row a slot
        pool = jax.tree_util.tree_map_with_path(
            lambda path, a: jnp.zeros_like(a)
            if leaf_kind(path) in ("stats", "slots") else a, pool)
        (pool, last_logits, keys, _), (toks, finite) = lax.scan(
            body, (pool, last_logits, keys, lengths), None,
            length=self.config.decode_burst,
        )
        stats = [a for path, a
                 in jax.tree_util.tree_flatten_with_path(pool)[0]
                 if leaf_kind(path) == "stats"]
        if stats:   # (rows on held experts, experts touched, most rows)
            stats = jnp.stack(stats)
            stats = jnp.stack([stats[:, 0].sum(), stats[:, 1].sum(),
                               stats[:, 2].max()])
            # ... and (rows moved, rows of the layouts): the burst's own,
            # then the admissions' before it; zeroed for the next ones
            stats = jnp.concatenate(
                [stats, rows_moved(pool) - admitted, admitted])
            pool = jax.tree_util.tree_map_with_path(
                lambda path, a: jnp.zeros_like(a)
                if leaf_kind(path) == "rows" else a, pool)
        by_name = lambda name: [
            a for path, a in jax.tree_util.tree_flatten_with_path(pool)[0]
            if leaf_name(path) == name]
        sparse = by_name(SLOT_STATS_LEAF)
        if sparse:   # (pages walked, pages a dense walk reads, sparse slots)
            # of the ACTIVE slots, over the layers and the burst's steps
            by_slot = jnp.where(active[:, None], sum(sparse), 0)
            sparse = jnp.concatenate([by_slot.sum(axis=0), jnp.sum(
                active & (lengths + 1 - attn_starts > self._dense_len)
            )[None]])
        window = by_name(WINDOW_STATS_LEAF)
        if window:   # (pages the window walks read, pages whole walks read)
            window = jnp.where(active[:, None], sum(window), 0).sum(axis=0)
        return pool, last_logits, toks, keys, finite, (stats, sparse, window)

    def _verify(self, params, pool, last_logits, attn_starts, active,
                drafts, draft_lens, page_table, lengths):
        """Speculative verify: score a k-token drafted window in ONE
        forward, accept greedily, append one corrected token.

        `drafts` (max_slots, k) are the drafter's proposals for each
        slot's next positions, `draft_lens` how many are real. The
        window forward is a paged PREFILL at positions
        `lengths[b] + [0, k)` (models/vit.py s>1 paged path — the same
        program shape as prefix-cache suffix admission), writing the
        drafted tokens' K/V through the page table.

        Acceptance is exact: stack the carried next-token logits in
        front of the window logits — row i of the stack predicts the
        token at position lengths+i — and take `g = argmax` (the very
        op plain greedy decode runs, inference.sample_logits). Draft
        token i is accepted iff it equals g[:, i] AND every earlier
        draft matched (cumprod); with m accepted, the emitted run is
        `g[:, :m+1]`: the m accepted drafts (which ARE the leading
        argmaxes) plus the model's own token at the first divergence —
        or the bonus token after a fully-accepted window. A final
        fused s=1 decode step writes that correction token's K/V at
        the per-slot position `lengths + m` — overwriting the rejected
        draft's K/V row — and carries its logits as the next sampling
        input.

        Rollback is positional, not a copy: rejected window positions
        `lengths+m+1 .. lengths+k-1` hold garbage K/V inside the
        slot's own blocks, invisible to attention (masked to
        kv_lengths) and overwritten by whatever decodes there next;
        the host side rewinds `kv_lengths` to `lengths + m + 1` and
        returns this dispatch's surplus grown blocks to the pool
        (kv_pages.rewind_block_tail). Free slots ride along on the
        garbage block as in `_decode_burst`.

        Returns (pool, last_logits, g (s, k+1), accepted (s,),
        finite (s, k+1)) — finite row i flags the logits token i was
        argmaxed from, the scheduler's per-token "error" signal.
        """
        k = drafts.shape[1]
        pool, win_logits = decode_apply(
            self.model, params, pool, drafts,
            attn_start=attn_starts, batch_stats=self.batch_stats,
            page_table=page_table, kv_lengths=lengths,
        )
        with jax.named_scope("sample"):
            all_logits = jnp.concatenate(
                [last_logits[:, None],
                 win_logits.astype(last_logits.dtype)], axis=1,
            )                                               # (s, k+1, v)
            g = sample_logits(all_logits, None, temperature=0.0)
            g = g.astype(jnp.int32)                         # (s, k+1)
            matches = (drafts == g[:, :k]) & (
                jnp.arange(k, dtype=jnp.int32)[None, :]
                < draft_lens[:, None]
            )
            accepted = jnp.cumprod(
                matches.astype(jnp.int32), axis=1
            ).sum(axis=1)                                   # (s,) in [0, k]
            accepted = jnp.where(active, accepted, 0)
            finite = jnp.isfinite(all_logits).all(axis=-1)  # (s, k+1)
            correction = jnp.take_along_axis(g, accepted[:, None], axis=1)
            correction = jnp.where(
                active[:, None], correction, jnp.int32(self.config.pad_id)
            )
        pool, nxt_logits = decode_apply(
            self.model, params, pool, correction,
            attn_start=attn_starts, batch_stats=self.batch_stats,
            page_table=page_table, kv_lengths=lengths + accepted,
        )
        with jax.named_scope("sample"):
            last_logits = jnp.where(
                active[:, None],
                nxt_logits[:, -1].astype(last_logits.dtype), last_logits,
            )
            toks = jnp.where(
                active[:, None], g, jnp.int32(self.config.pad_id)
            )
        return pool, last_logits, toks, accepted, finite

    # ----------------------------------------------------------------- host
    def set_tracer(self, tracer, replica: int = 0) -> None:
        """Attach a utils/trace.py TraceRecorder; `replica` is this
        engine's pid in the exported timeline (lane conventions:
        trace.label_replica)."""
        self.tracer = tracer
        self.replica = replica
        if tracer is not None:
            tracer.set_annotate(jax.profiler.TraceAnnotation, "serve")

    def _span(self, name: str, tid: int = ENGINE_LANE, **attrs):
        """One of this engine's lane spans (callers have tested
        `tracer.enabled`). Engine-lane spans name no request, so under
        head sampling they ride only while a SAMPLED request is in
        flight (`sampled_only`) — otherwise an idle 1%-sampled fleet
        would still record every burst and the plane would never
        shrink; a slot-lane span carries its request's trace_id and
        follows that request's own verdict."""
        return self.tracer.span(name, pid=self.replica, tid=tid,
                                sampled_only="trace_id" not in attrs,
                                **attrs)

    def _prefill_spans(self, name: str, slot: int, trace_id: str,
                       **attrs) -> tuple:
        """(`name`, its `prefill_host` half, its `prefill_dispatch`
        half) on the slot's lane, all under the request's trace_id."""
        lane = SLOT_LANE_BASE + slot
        return (self._span(name, lane, trace_id=trace_id, slot=slot,
                           **attrs),
                self._span("prefill_host", lane, trace_id=trace_id),
                self._span("prefill_dispatch", lane, trace_id=trace_id))

    def _burst_spans(self, name: str, **attrs) -> tuple:
        """(`name`, its `burst_dispatch` half, its `burst_readback`
        half) on the engine lane."""
        return (self._span(name, active=int(np.count_nonzero(self._active)),
                           **attrs),
                self._span("burst_dispatch"), self._span("burst_readback"))

    def bucket_for(self, prompt_len: int) -> int:
        """Smallest bucket width holding `prompt_len` (raises if none)."""
        for w in self.buckets:
            if prompt_len <= w:
                return w
        raise ValueError(
            f"prompt length {prompt_len} exceeds the largest bucket "
            f"{self.buckets[-1]}"
        )

    def fits_prompt(self, prompt_len: int) -> bool:
        """Can this engine EVER serve a prompt of this length? The
        feasibility probe the router's salvage/failover path asks
        before re-targeting a request — bounded by the buckets, or, with
        `prefill_chunk`, by capacity: any prompt whose tokens + one
        decode position fit the per-slot capacity and the pool can be
        chunk-prefilled."""
        if self.config.prefill_chunk:
            return (prompt_len + 1 <= self.max_context
                    and self._blocks_for(prompt_len + 1)
                    <= self.blocks.num_blocks - 1)
        return prompt_len <= self.buckets[-1]

    def _sampling_args(self):
        """Per-slot sampling params for the next decode dispatch: a
        triple of (s,) device arrays when per_slot_sampling, else None.
        None is an EMPTY pytree, so the config-baked decode program
        keeps its single compile and the per-slot path adds exactly
        one — the churn pins (compile_stats) cover both."""
        if not self.config.per_slot_sampling:
            return None
        return (jnp.asarray(self._temp), jnp.asarray(self._topk),
                jnp.asarray(self._topp))

    def _set_sampling(self, slot: int, sampling) -> None:
        """Record a slot's sampling params at admit. `sampling` is
        (temperature, top_k, top_p) with None fields falling back to
        the engine config — the scheduler passes a request's overrides
        verbatim. Overrides without per_slot_sampling raise: silently
        sampling at the WRONG params is the one outcome this must
        never produce (the decode program bakes the config values in)."""
        cfg = self.config
        t, k, p = sampling if sampling is not None else (None, None, None)
        t = cfg.temperature if t is None else float(t)
        k = cfg.top_k if k is None else int(k)
        p = cfg.top_p if p is None else float(p)
        if not cfg.per_slot_sampling and (
                t != cfg.temperature or k != cfg.top_k
                or p != cfg.top_p):
            raise ValueError(
                "per-request sampling params need "
                "EngineConfig.per_slot_sampling=True"
            )
        self._temp[slot] = t
        self._topk[slot] = k
        self._topp[slot] = p

    @property
    def num_active(self) -> int:
        return self.allocator.num_used

    @property
    def num_free(self) -> int:
        return self.allocator.num_free

    def step(self) -> np.ndarray:
        """One decode step for the whole pool; tokens (max_slots,).
        Token-granular stepping — requires decode_burst=1 (use
        step_burst for the amortized path)."""
        if self.config.decode_burst != 1:
            raise RuntimeError("step() needs decode_burst=1")
        return self.step_burst()[0]

    def _blocks_for(self, positions: int) -> int:
        return -(-positions // self.config.block_size)

    @property
    def blocks_available(self) -> int:
        """Blocks admission can promise RIGHT NOW: the free list plus
        unreferenced prefix-cache blocks (evicted on demand). No
        reservation term any more — future growth is backed by releases
        and block-aware preemption, not by up-front hoarding."""
        free = self.blocks.num_free
        if self.radix is not None:
            free += self.radix.evictable()
        return free

    @property
    def headroom(self) -> int:
        """Promisable pool positions (informational — admission gates on
        blocks per request, not on a global span)."""
        return self.blocks_available * self.config.block_size

    def _probe_prefix(self, prompt: Sequence[int]) -> int:
        """Read-only longest-cached-prefix length for `prompt` (0 with
        the cache off) — what the admission gate subtracts before
        bucketing: a prompt whose cached prefix leaves a bucketable
        suffix is servable even when the WHOLE prompt outgrows every
        bucket (long shared system prompts)."""
        if self.radix is None:
            return 0
        return self.radix.peek(prompt)

    def _tables(self, rows=slice(None)):
        """The page tables a dispatch ships: the global group's array, as
        ever, or a dict by group where the model has a window group."""
        if self.wgroup is None:
            return jnp.asarray(self._pt[rows])
        return {"global": jnp.asarray(self._pt[rows]),
                "window": jnp.asarray(self.wgroup.table[rows])}

    def pages_held(self, a_slot: bool = False) -> dict:
        """Pages the slots hold now, by page group (gauge
        `kv_pages_held{group=}`); `a_slot`: the most any one slot holds."""
        of = np.max if a_slot else np.sum
        held = {"global": int(of(self._nblk))}
        if self.wgroup is not None:
            held["window"] = int(of(self.wgroup.end - self.wgroup.first))
        return held

    @property
    def window_pages_freed(self) -> int:
        """Pages given back behind a window, cumulative (counter
        `kv_window_pages_freed_total`)."""
        return 0 if self.wgroup is None else self.wgroup.freed

    def _admit_plan(self, prompt_len: int,
                    prompt: Optional[Sequence[int]] = None):
        """(matched, bucket_w, need_now) for an admission, or None when
        no bucket fits the uncached suffix. need_now = prompt-table
        blocks not already cached + one decode block — THE one place the
        gate, make_room, and preempt_headroom derive it, so the three
        can never disagree on what an admission must take right now.
        With prefill_chunk on, a suffix longer than one chunk is
        bucketed at the CHUNK width (the first chunk is all an
        admission prefills; later chunks grow like decode), so prompts
        past the largest bucket stop being "never"."""
        matched = self._probe_prefix(prompt) if prompt is not None else 0
        suffix = prompt_len - matched
        if self.config.prefill_chunk:
            suffix = min(suffix, self.config.prefill_chunk)
        try:
            w = self.bucket_for(suffix)
        except ValueError:
            return None
        need_now = self._blocks_for(matched + w) \
            - matched // self.config.block_size + 1
        return matched, w, need_now

    def admit_gate(self, prompt_len: int, needed_positions: int,
                   prompt: Optional[Sequence[int]] = None) -> str:
        """"ok" | "later" (blocks free as running requests release, get
        preempted, or prefix blocks age out) | "never" (outgrows every
        bucket even after the cached prefix, the per-slot capacity, or
        the whole pool). Passing the `prompt` itself lets the gate probe
        the prefix cache; without it the gate judges the full length."""
        plan = self._admit_plan(prompt_len, prompt)
        if plan is None:
            return "never"
        matched, w, need_now = plan
        if not self._canonical:
            end = w + needed_positions
        else:
            end = max(matched + w, prompt_len + needed_positions)
        if end > self.max_context:
            return "never"
        if self._blocks_for(end) > self.blocks.num_blocks - 1:
            return "never"  # outgrows the whole pool, even empty
        # prompt blocks now + one decode block; growth is backed by
        # releases / preemption, not a reservation
        if need_now > self.blocks_available:
            return "later"
        # ... and the same of the window group, counted on its own: its
        # first chunk's pages and one more (a slot's bound there was
        # checked against the pool at construction)
        if self.wgroup is not None \
                and self._blocks_for(w) + 1 > self.wgroup.blocks.num_free:
            return "later"
        return "ok"

    def preempt_headroom(self, slots: Sequence[int], prompt_len: int,
                         prompt: Optional[Sequence[int]] = None) -> bool:
        """Could evicting every slot in `slots` possibly admit a blocked
        request of this shape? Upper bound: a victim's whole table
        surfaces (in truth blocks shared with another RUNNING slot
        stay). False means preemption is pure churn — the scheduler
        skips it and the head just waits for releases."""
        plan = self._admit_plan(prompt_len, prompt)
        if plan is None:
            return False
        bound = self.blocks_available \
            + int(sum(self._nblk[s] for s in slots))
        if self.wgroup is not None and self._blocks_for(plan[1]) + 1 \
                > self.wgroup.blocks.num_free \
                + sum(self.wgroup.held(s) for s in slots):
            return False
        return plan[2] <= bound

    def make_room(self, prompt_len: Optional[int] = None,
                  needed_positions: Optional[int] = None,
                  prompt: Optional[Sequence[int]] = None) -> bool:
        """Evict unreferenced prefix-cache blocks (LRU) back to the free
        list; True if anything freed. Eviction helps a blocked admission
        only by EXPOSURE: `blocks_available` already counts evictable
        leaves, so the win is interior chain nodes becoming evictable as
        their leaves drop. With the blocked request's shape (the same
        args its admit_gate saw) the pass is TARGETED: the request's own
        matched prefix is pinned first — a blanket evict would consume
        the very blocks that made a long prompt servable, flipping a
        feasible "later" into "never" — and only the shortfall against
        the gate's need is freed, so one blocked tick no longer wipes
        the whole warm cache. Preempting a RUNNING victim for a queued
        request is the scheduler's call (it knows arrival order —
        serve/scheduler.py preempts only young victims for older
        requests, which keeps the cascade terminating); the engine-side
        lever here is only the cache that nobody is attending through."""
        if self.radix is None:
            return False
        keep = self.radix.ref_prefix(prompt) if prompt is not None else []
        try:
            if prompt_len is not None and needed_positions is not None:
                plan = self._admit_plan(prompt_len, prompt)
                if plan is None:
                    return False      # no bucket fits: room cannot help
                # the FULL shortfall, not min(shortfall, evictable()):
                # evictable() counts only current leaves, but evict()'s
                # exposure loop drains interior chain blocks too — a
                # deep single-leaf chain can cover a 3-block shortfall
                want = max(0, plan[2] - self.blocks.num_free)
            else:
                want = self.radix.evictable()
            return want > 0 and self.radix.evict(want) > 0
        finally:
            if keep:
                self.blocks.free(keep)

    # ------------------------------------------------- block acquisition
    def _acquire_admit(self, n: int):
        """n blocks for an admission: free list first, then on-demand
        LRU eviction of unreferenced prefix-cache blocks. Admission
        never preempts runners — the scheduler's gate queues instead."""
        ids = self.blocks.alloc(n)
        if ids is None and self.radix is not None:
            self.radix.evict(n - self.blocks.num_free)
            ids = self.blocks.alloc(n)
        if ids is None:
            raise RuntimeError(
                "not enough free blocks — scheduler must gate admits"
            )
        return ids

    def _acquire_decode(self, n: int, protect: int, blocks=None):
        """n blocks for mid-decode growth / a CoW split: free list, then
        prefix-cache eviction, then BLOCK-AWARE PREEMPTION — evict the
        youngest-admitted active slot's non-shared blocks (LIFO victims,
        vLLM-style) and let the scheduler re-prefill it. `protect` is
        the slot being grown (never preempts itself while older slots
        could yield). Raises only when even preempting everyone else
        cannot cover — impossible for scheduler-gated traffic (the
        "never" gate bounds one request's whole-pool need), reachable by
        direct users who oversubscribe fork budgets. `blocks`: the
        allocator to take from, the global group's unless the window
        group's is named (a victim gives back both groups' pages)."""
        blocks = self.blocks if blocks is None else blocks
        while True:
            ids = blocks.alloc(n)
            if ids is not None:
                return ids
            if self.radix is not None \
                    and self.radix.evict(n - self.blocks.num_free):
                continue
            victims = [
                s for s in np.flatnonzero(self._active) if s != protect
            ]
            if not victims:
                raise RuntimeError(
                    f"paged pool exhausted: {n} blocks needed with no "
                    f"victim left to preempt (slot {protect} already "
                    f"holds {int(self._nblk[protect])})"
                )
            victim = max(victims, key=lambda s: self._seq[s])
            self.preempt(int(victim))

    def preempt(self, slot: int) -> None:
        """Evict one active slot: deref its blocks (shared ones — prefix
        blocks, fork siblings' — survive for their other holders), clear
        the slot, and queue it on `take_preempted()` for the scheduler's
        readmission path (re-prefill prompt + generated-so-far).
        Callable by the scheduler (preempt-for-admission) and by
        `_acquire_decode` (growth exhaustion)."""
        if not self._active[slot]:
            raise ValueError(f"slot {slot} is not active")
        self._clear_slot(slot)
        self.preemptions += 1
        self._preempted.append(slot)
        tr = self.tracer
        if tr is not None and tr.enabled:
            tr.instant("preempt", trace_id=self._slot_trace.get(slot),
                       pid=self.replica, tid=ENGINE_LANE, slot=slot,
                       blocks_free=self.blocks.num_free)
        self._slot_trace.pop(slot, None)
        self._slot_seed.pop(slot, None)
        self._fork_n.pop(slot, None)

    def take_preempted(self) -> list:
        """Slots preempted since the last drain (the scheduler calls
        this after `step_burst` and after its admission loop, re-queues
        the victims' requests, and re-prefills them when room returns)."""
        out, self._preempted = self._preempted, []
        return out

    # ---------------------------------------------------------- admission
    def admit(self, prompt: Sequence[int], *, seed: int = 0,
              max_positions: Optional[int] = None,
              trace_id: Optional[str] = None,
              sampling: Optional[Tuple] = None) -> int:
        """Prefill `prompt` into a free slot + blocks; the slot id.

        `max_positions` is the request's decode-position budget
        (burst-rounded max_new_tokens from the scheduler): no longer a
        reservation, just the growth CAP (`_grow_tables` refuses past
        it) and the whole-pool feasibility check. None caps at the
        per-slot capacity.

        With `EngineConfig.prefix_cache` the prompt first walks the
        radix tree: matched blocks join this slot's page table
        refcounted (their prefill is SKIPPED), only the suffix runs
        through `_prefix_prefill` at canonical positions, and the
        prompt's own full blocks are inserted for future admissions.

        With `EngineConfig.prefill_chunk`, an uncached suffix longer
        than one chunk makes this a CHUNK admission: bookkeeping only
        here (the slot stays inactive, holding just the shared prefix
        blocks), and the caller drives `prefill_step(slot)` once per
        tick until it returns True — Sarathi-style prefill/decode
        interleaving (the scheduler's chunk pump).

        `sampling` = per-request (temperature, top_k, top_p) overrides,
        None fields defaulting to the config
        (EngineConfig.per_slot_sampling).
        """
        p = len(prompt)
        if p == 0:
            raise ValueError("prompt must contain at least one token")
        bs = self.config.block_size
        shared: list = []
        matched = 0
        if self.radix is not None:
            shared, matched = self.radix.match(prompt)
            self.last_prefix_hit = matched
        chunk = self.config.prefill_chunk
        # a recurrent model's every admission is a chunk admission: one
        # program (`_prefix_prefill`) at canonical positions, however short
        chunked = bool(chunk) and (
            (p - matched) > chunk or self._chunk_only)
        try:
            w = self.bucket_for(min(p - matched, chunk) if chunked
                                else p - matched)
        except ValueError:
            self.blocks.free(shared)
            raise
        # the slot's context END: the plain path starts at length w
        # (left-padding counts as positions), the prefix path at the
        # true p — but its prefill pad rows touch up to matched + w
        if max_positions is None:
            max_positions = self.max_context - (
                w if not self._canonical else max(matched + w, p)
            )
        if not self._canonical:
            end = w + max_positions
        else:
            end = max(matched + w, p + max_positions)
        if end > self.max_context:
            self.blocks.free(shared)
            raise ValueError(
                f"prompt {p} (prefill span {matched + w}) + max_positions "
                f"{max_positions} exceeds the per-slot capacity "
                f"{self.max_context} (= max_blocks_per_slot * block_size)"
            )
        if self._blocks_for(end) > self.blocks.num_blocks - 1:
            self.blocks.free(shared)
            raise ValueError(
                f"prompt {p} + max_positions {max_positions} outgrows "
                f"the whole pool ({self.blocks.num_blocks - 1} blocks)"
            )
        slot = self.allocator.alloc()
        if slot is None:
            self.blocks.free(shared)
            raise RuntimeError("no free slot — scheduler must gate admits")
        try:
            self._set_sampling(slot, sampling)
        except ValueError:
            self.allocator.free(slot)
            self.blocks.free(shared)
            raise
        n_shared = len(shared)
        if chunked:
            # chunk admission: bookkeeping only. The shared prefix
            # joins the table refcounted; every uncached token —
            # including the first chunk — lands through prefill_step,
            # which grows blocks like decode does (_acquire_decode).
            # The slot stays INACTIVE until the final chunk: decode
            # bursts pad it (their garbage write at _len[slot] is
            # overwritten by the next chunk, or lands in the garbage
            # block while unallocated) and preemption never picks it.
            self._pt[slot, :] = 0
            self._pt[slot, :n_shared] = shared
            self._nblk[slot] = n_shared
            self._budget[slot] = min(
                max(self._blocks_for(end), n_shared),
                self.max_blocks_per_slot,
            )
            self._seq[slot] = self._admit_seq
            self._admit_seq += 1
            self._len[slot] = matched
            self._attn[slot] = 0
            self._pending_prompt[slot] = {
                "prompt": [int(t) for t in prompt], "done": matched,
                "hit": matched,
            }
            tr = self.tracer
            if tr is not None and tr.enabled:
                tid = trace_id or f"slot{slot}"
                self._slot_trace[slot] = tid
                # mirrored: the ONE event of a chunk admission, so its
                # `prefix_hit` and `prompt_len` are counted once
                tr.instant("chunk_admit", trace_id=tid,
                           pid=self.replica, tid=SLOT_LANE_BASE + slot,
                           mirror=True, prompt_len=p, prefix_hit=matched,
                           chunk=chunk, slot=slot)
            self._keys = self._keys.at[slot].set(jax.random.PRNGKey(seed))
            self._slot_seed[slot] = (seed,)
            self._fork_n.pop(slot, None)
            return slot
        n_table = self._blocks_for(matched + w)
        try:
            ids = self._acquire_admit(n_table - n_shared)
        except RuntimeError:
            self.allocator.free(slot)
            self.blocks.free(shared)
            raise
        self._pt[slot, :] = 0
        self._pt[slot, :n_shared] = shared
        self._pt[slot, n_shared:n_table] = ids
        self._nblk[slot] = n_table
        self._budget[slot] = min(
            max(self._blocks_for(end), n_table), self.max_blocks_per_slot
        )
        self._seq[slot] = self._admit_seq
        self._admit_seq += 1
        tr = self.tracer
        if tr is not None and tr.enabled:
            tid = trace_id or f"slot{slot}"
            self._slot_trace[slot] = tid
            # the call runs `bucket` positions of which `prompt_len -
            # prefix_hit` hold a token (what a recurrent model's scans
            # advance the state over; the rest is the bucket's padding)
            span, host, disp = self._prefill_spans(
                "prefill", slot, tid, bucket=w, prompt_len=p,
                blocks=n_table, prefix_hit=matched)
        else:
            span = host = disp = _NULL
        if self._recurrent:
            self.ssm_scan_tokens += p
            self.ssm_scan_padded_tokens += w - p
        if self.radix is None:
            # plain path, unchanged since PR 3: LEFT-padded scratch
            # prefill + block scatter
            self._len[slot] = w
            self._attn[slot] = w - p
            with span:
                with host:
                    padded = np.full((1, w), self.config.pad_id, np.int32)
                    padded[0, w - p:] = np.asarray(prompt, np.int32)
                    args = (jnp.asarray(padded), jnp.int32(w - p),
                            jnp.asarray(ids, jnp.int32), jnp.int32(slot))
                with disp:
                    self._cache, self._last_logits = self._prefill_jit(
                        self.params, self._cache, self._last_logits,
                        *args,
                    )
                    _await_dispatch(self._cache, self._last_logits)
        else:
            # prefix path: canonical positions, RIGHT-padded suffix
            # appended at `matched` through the page table; the hit's
            # [0, matched) prefill chunks are never recomputed
            sl = p - matched
            self._len[slot] = matched + sl
            self._attn[slot] = 0
            with span:
                with host:
                    padded = np.full((1, w), self.config.pad_id, np.int32)
                    padded[0, :sl] = np.asarray(prompt[matched:], np.int32)
                    args = (jnp.asarray(padded), jnp.int32(matched),
                            jnp.int32(sl),
                            jnp.asarray(self._pt[slot:slot + 1]),
                            jnp.int32(slot))
                with disp:
                    self._cache, self._last_logits = self._prefix_jit(
                        self.params, self._cache, self._last_logits,
                        *args,
                    )
                    _await_dispatch(self._cache, self._last_logits)
            # publish this prompt's own full blocks for future hits
            # (already-cached chunks keep their existing node)
            n_full = p // bs
            if n_full:
                self.radix.insert(
                    prompt, [int(b) for b in self._pt[slot, :n_full]]
                )
        # keyed by the REQUEST's seed alone (not the slot), so a
        # request's sampled tokens are independent of where admission
        # happened to place it — batch composition stays invisible
        self._keys = self._keys.at[slot].set(jax.random.PRNGKey(seed))
        self._slot_seed[slot] = (seed,)
        self._fork_n.pop(slot, None)
        self._active[slot] = True
        if self.drafter is not None:
            # readmission after preemption passes prompt + salvaged
            # tokens here, so the drafter's context is always the
            # slot's true prefix — it never needs to survive a preempt
            self.drafter.begin(slot, [int(t) for t in prompt])
        return slot

    # ------------------------------------------------- chunked prefill
    def is_prefilling(self, slot: int) -> bool:
        """True while a chunk-admitted slot still has prompt chunks to
        run (the scheduler's chunk pump drives prefill_step until this
        flips)."""
        return slot in self._pending_prompt

    def prefill_step(self, slot: int) -> bool:
        """Run ONE prefill chunk for a chunk-admitted slot; True when
        the prompt is fully prefilled (the slot just went active).

        Each chunk is a `_prefix_prefill` dispatch — the suffix-append
        program admission already compiles, at the chunk's bucket width
        — placed at slot-local positions [done, done+take) through the
        page table. Blocks grow per chunk via `_acquire_decode` (free
        list → prefix eviction → LIFO preemption of ACTIVE slots; this
        inactive slot is never its own victim), and only for the REAL
        tokens: a chunk's pad-tail rows scatter into the garbage block
        past the table, so no block is ever held for padding. The final
        chunk publishes the prompt's full blocks to the radix cache,
        seeds the drafter, and activates the slot — exactly the state a
        whole-prompt admission leaves behind, so everything downstream
        (decode, preemption, release) is chunk-blind.

        Raises RuntimeError when the pool cannot cover a chunk even
        after preempting every active slot — the scheduler treats that
        like any admission failure (releases and requeues)."""
        st = self._pending_prompt[slot]
        prompt = st["prompt"]
        p = len(prompt)
        done = st["done"] = self._adopt_published(slot, prompt, st["done"])
        take = min(p - done, self.config.prefill_chunk)
        w = self.bucket_for(take)
        need = self._blocks_for(done + take)
        grow = need - int(self._nblk[slot])
        if grow > 0:
            if need > self.max_blocks_per_slot:
                raise RuntimeError(
                    f"slot {slot} prompt chunk needs {need} blocks, "
                    f"past the per-slot capacity "
                    f"{self.max_blocks_per_slot}"
                )
            ids = self._acquire_decode(grow, protect=slot)
            self._pt[slot, self._nblk[slot]:need] = ids
            self._nblk[slot] = need
        if self.wgroup is not None:
            freed = self._window_pages(slot, done, done + take)
        tr = self.tracer
        if tr is not None and tr.enabled:
            pages = {}
            if self.wgroup is not None:   # what the chunk's kernels walk
                pages = dict(self._chunk_pages(done, take, w),
                             window_pages_freed=freed)
            span, host, disp = self._prefill_spans(
                "prefill_chunk", slot,
                self._slot_trace.get(slot, f"slot{slot}"),
                # `take` of the `bucket` positions hold a token
                bucket=w, pos0=done, take=take,
                chunk=done // self.config.prefill_chunk,
                prefix_hit=st["hit"], **pages)
        else:
            span = host = disp = _NULL
        if self._recurrent:
            self.ssm_scan_tokens += take
            self.ssm_scan_padded_tokens += w - take
        with span:
            with host:
                padded = np.full((1, w), self.config.pad_id, np.int32)
                padded[0, :take] = np.asarray(prompt[done:done + take],
                                              np.int32)
                args = (jnp.asarray(padded), jnp.int32(done),
                        jnp.int32(take),
                        self._tables(slice(slot, slot + 1)),
                        jnp.int32(slot))
            with disp:
                self._cache, self._last_logits = self._prefix_jit(
                    self.params, self._cache, self._last_logits, *args,
                )
                _await_dispatch(self._cache, self._last_logits)
        done += take
        st["done"] = done
        self._len[slot] = done
        if done < p:
            # publish what this chunk completed, so that a request with
            # the same context admitted meanwhile does not prefill it
            # again (it adopts the blocks: `_adopt_published`)
            n_full = done // self.config.block_size
            if self.radix is not None:
                self.radix.insert(
                    prompt[:n_full * self.config.block_size],
                    [int(b) for b in self._pt[slot, :n_full]])
            return False
        # final chunk: the slot now looks exactly like a whole-prompt
        # prefix admission — publish, seed the drafter, go active
        del self._pending_prompt[slot]
        floor = self._blocks_for(p)
        self._nblk[slot] = rewind_block_tail(
            self.blocks, self._pt[slot], int(self._nblk[slot]), floor
        )
        n_full = p // self.config.block_size
        if n_full and self.radix is not None:
            self.radix.insert(
                prompt, [int(b) for b in self._pt[slot, :n_full]]
            )
        if self.drafter is not None:
            self.drafter.begin(slot, [int(t) for t in prompt])
        self._active[slot] = True
        return True

    def _adopt_published(self, slot: int, prompt: list, done: int) -> int:
        """Blocks of `prompt` past `done` that another request has
        published since this slot's last chunk join the slot's table
        refcounted instead of being prefilled again; returns the new
        `done`. At least one token is always left to prefill (the
        radix's own clamp), and a block this slot has begun to write is
        never replaced (`done` on a block boundary)."""
        bs = self.config.block_size
        have = int(self._nblk[slot])
        if self.radix is None or done % bs or have != done // bs:
            return done
        chain = self.radix.ref_prefix(prompt)   # pins the matched chain
        self.blocks.free(chain[:have])          # the slot holds its own
        ahead = chain[have:]
        if not ahead:
            return done
        self._pt[slot, have:have + len(ahead)] = ahead
        self._nblk[slot] = have + len(ahead)
        self.radix.hit_tokens += len(ahead) * bs
        self.radix.miss_tokens -= len(ahead) * bs
        return done + len(ahead) * bs

    def fork(self, slot: int, *, seed: Optional[int] = None,
             trace_id: Optional[str] = None) -> int:
        """Clone a running request into a new slot WITHOUT copying its
        context: the child references every parent block (refcounted)
        and carries the same pending logits under a fresh PRNG chain —
        n>1 parallel sampling per prompt for the price of the tail
        blocks the siblings eventually split via copy-on-write.

        Child keys are REPLAYABLE: with no explicit seed the child's
        chain is folded from the parent's seed path plus this fork's
        ordinal — a pure function of (request seed, fork order), so
        siblings diverge by construction AND a replay reproduces each
        sibling's exact stream whatever slot the allocator hands out
        and however many decode steps ran before the fork (the old
        fold-from-current-key default was deterministic in-process but
        changed with both). An explicit `seed=` starts a fresh chain —
        the per-request knob the front door's n>1 sampling rides. A
        slot with no recorded seed path (direct `_keys` manipulation in
        tests) falls back to folding the parent's current key."""
        if self._recurrent:
            raise ValueError(
                "fork is refused for a model with recurrent state: the "
                "child would share the parent's pages but needs a state "
                "of its own (ROADMAP M6: state snapshots)")
        if self._window:
            raise ValueError(
                "fork is refused for a model with a window page group: the "
                "child would share the parent's window pages, which the "
                "parent gives back as it decodes on (ROADMAP M3)")
        if not self._active[slot]:
            raise ValueError(f"slot {slot} is not active")
        child = self.allocator.alloc()
        if child is None:
            raise RuntimeError("no free slot — gate fork like an admit")
        n = int(self._nblk[slot])
        self.blocks.ref([int(b) for b in self._pt[slot, :n]])
        self._pt[child, :] = self._pt[slot, :]
        self._len[child] = self._len[slot]
        self._attn[child] = self._attn[slot]
        self._nblk[child] = n
        self._budget[child] = self._budget[slot]
        # siblings sample under the parent's params (they diverge by
        # PRNG chain, not by distribution)
        self._temp[child] = self._temp[slot]
        self._topk[child] = self._topk[slot]
        self._topp[child] = self._topp[slot]
        self._seq[child] = self._admit_seq
        self._admit_seq += 1
        if seed is not None:
            key = jax.random.PRNGKey(seed)
            self._slot_seed[child] = (seed,)
        else:
            path = self._slot_seed.get(slot)
            if path is not None:
                self._fork_n[slot] = self._fork_n.get(slot, 0) + 1
                path = path + (self._fork_n[slot],)
                key = jax.random.PRNGKey(path[0])
                for ordinal in path[1:]:
                    key = jax.random.fold_in(key, ordinal)
                self._slot_seed[child] = path
            else:
                key = jax.random.fold_in(self._keys[slot], child)
        self._last_logits, self._keys = self._fork_jit(
            self._last_logits, self._keys, jnp.int32(slot),
            jnp.int32(child), key,
        )
        _await_dispatch(self._last_logits, self._keys)
        self._active[child] = True
        if self.drafter is not None:
            self.drafter.begin(child, self.drafter.snapshot(slot))
        if trace_id is not None:
            self._slot_trace[child] = trace_id
        return child

    # ------------------------------------------------------------- decode
    def _window_pages(self, slot: int, first: int, end: int) -> int:
        """The window group's pages of `slot` for queries at positions
        [first, end): the pages wholly behind the FIRST query's window go
        back to the group's allocator, the pages up to `end` are taken from
        it (growth may preempt, as the global group's). Returns how many
        went back."""
        g, bs = self.wgroup, self.config.block_size
        freed = g.trim(slot, max(0, first - self._window + 1) // bs)
        grow = self._blocks_for(end) - int(g.end[slot])
        if grow > 0:
            g.extend(slot, self._acquire_decode(
                grow, protect=slot, blocks=g.blocks))
        assert g.held(slot) <= self.window_pages_a_slot, (
            slot, g.held(slot), self.window_pages_a_slot)
        return freed

    def _chunk_pages(self, pos0: int, take: int, width: int) -> dict:
        """The pages the `window_prefill` tiles of a chunk of `take` tokens
        in a bucket of `width` WALK (a tile of rows from its first key's
        page to its last row's own), by page group, over the layers of each
        and for one KV head, counted on the host by the kernel's own rule
        (ops/window_attention.py tile_walks)."""
        from ddp_practice_tpu.ops.window_attention import (
            NO_WINDOW,
            WINDOW_TILE,
            tile_walks,
        )

        tile = min(WINDOW_TILE, width)
        far, near = (int(tile_walks(
            pos0, 0, window, take, tiles=width // tile, tile=tile,
            block=self.config.block_size, columns=self._pt.shape[1],
            xp=np)[1].sum()) for window in (NO_WINDOW, self._window))
        return {"global_pages": self._global_layers * far,
                "window_pages": self._window_layers * near}

    def _grow_tables(self, k: int) -> int:
        """Allocate the blocks the next k decode positions need, per
        active slot oldest-first (growth may preempt — LIFO victims must
        still be ungrown, not half-grown). Stepping a slot past its
        admit-time `max_positions` budget raises BEFORE touching the
        allocator (the scheduler's burst-rounded max_positions never
        trips it). Returns the number of blocks grown (the decode-burst
        span's `blocks_grown` attribute)."""
        total_grown = 0
        order = sorted(np.flatnonzero(self._active),
                       key=lambda s: self._seq[s])
        for slot in order:
            if not self._active[slot]:
                continue  # preempted by an older slot's growth
            need = self._blocks_for(int(self._len[slot]) + k)
            grow = need - int(self._nblk[slot])
            if grow <= 0:
                continue
            if need > int(self._budget[slot]) \
                    or need > self.max_blocks_per_slot:
                raise RuntimeError(
                    f"slot {slot} stepped past its admit-time block "
                    f"budget (needs {need} blocks, budget "
                    f"{int(self._budget[slot])}) — admit with a larger "
                    f"max_positions"
                )
            ids = self._acquire_decode(grow, protect=int(slot))
            self._pt[slot, self._nblk[slot]:need] = ids
            self._nblk[slot] = need
            total_grown += grow
        if self.wgroup is not None:
            self._burst_freed = 0
            for slot in order:
                if self._active[slot]:
                    length = int(self._len[slot])
                    self._burst_freed += self._window_pages(
                        int(slot), length, length + k)
        return total_grown

    def _cow_split(self, k: int) -> int:
        """Copy-on-write pass before a burst: any EXISTING table block
        the next k positions will write into (fork siblings' shared
        tail) is first copied into a private block — a shared block is
        never mutated, so no sibling or cached prefix ever sees another
        request's tokens. Returns the number of splits (decode-burst
        span attribute)."""
        splits = 0
        bs = self.config.block_size
        for slot in sorted(np.flatnonzero(self._active),
                           key=lambda s: self._seq[s]):
            if not self._active[slot]:
                continue
            length = int(self._len[slot])
            first = length // bs
            last = min((length + k - 1) // bs, int(self._nblk[slot]) - 1)
            for idx in range(first, last + 1):
                b = int(self._pt[slot, idx])
                if self.blocks.refcount(b) <= 1:
                    continue
                assert b != GARBAGE_BLOCK, \
                    "garbage block can never be shared"
                (new,) = self._acquire_decode(1, protect=int(slot))
                # `protect` excludes this slot from the victim list, so
                # the acquire can never have preempted it
                assert self._active[slot], "protected slot was preempted"
                self._cache = self._cow_jit(
                    self._cache, jnp.int32(b), jnp.int32(new)
                )
                _await_dispatch(self._cache)
                self.blocks.free([b])     # drop this slot's ref
                self._pt[slot, idx] = new
                splits += 1
        return splits

    def step_burst(self) -> np.ndarray:
        """One dispatch of `decode_burst` steps; tokens (K, max_slots).
        Per-slot lengths advance by K for active slots; free slots emit
        pad_id and write only the garbage block. Growth / CoW happen
        host-side first and may PREEMPT young slots under pressure —
        preempted slots drop out of this burst (their rows are pads) and
        surface via `take_preempted()`."""
        k = self.config.decode_burst
        tr = self.tracer
        traced = tr is not None and tr.enabled
        with self._span("burst_plan") if traced else _NULL:
            grown = self._grow_tables(k)
            splits = self._cow_split(k)
        if traced:
            # how far the kernel's walk is from O(table): step j of the
            # burst attends pages attn // bs .. (len + j) // bs of a slot
            act, bs = self._active, self.config.block_size
            last_page = (self._len[act][:, None] + np.arange(k)) // bs
            walked = int(
                (last_page - self._attn[act][:, None] // bs + 1).sum())
            span, disp, read = self._burst_spans(
                "decode_burst", burst=k, blocks_grown=grown,
                cow_splits=splits, blocks_free=self.blocks.num_free,
                # (a latent model's attention layers walk latent rows)
                pages_walked=walked,
                pages_held=int(self._nblk[act].sum()) * k)
        else:
            span = disp = read = _NULL
        with span:
            with disp:
                (self._cache, self._last_logits, toks,
                 self._keys, finite, stats) = self._decode_jit(
                    self.params, self._cache, self._last_logits,
                    jnp.asarray(self._attn), jnp.asarray(self._active),
                    self._keys, self._tables(),
                    jnp.asarray(self._len), self._sampling_args(),
                )
                _await_dispatch(self._cache, self._last_logits,
                                self._keys)
            self._len[self._active] += k
            with read:  # the host waits for the device here
                toks, finite, (stats, sparse, window) = jax.device_get(
                    (toks, finite, stats))
            if self._window_layers:
                # what the burst's window layers read, from the program:
                # pages their walks read, pages whole walks would have
                near, whole = (int(v) for v in window)
                self.last_burst_window = (near, whole)
                self.window_pages_walked += near
                self.window_pages_whole += whole
                if traced and getattr(span, "attrs", None) is not None:
                    span.attrs.update(
                        window_pages=near,
                        global_pages=walked * self._global_layers,
                        window_pages_freed=self._burst_freed)
            if self._sparse_layers:
                # what the burst's sparse attention layers read, from the
                # program: pages their walks read, pages dense walks would
                # have, slots past `dense_len` when the burst began
                walked, held, slots = (int(v) for v in sparse)
                self.last_burst_sparse = (walked, held, slots)
                self.sparse_pages_walked += walked
                self.sparse_pages_held += held
                if traced and getattr(span, "attrs", None) is not None:
                    span.attrs.update(sparse_pages_walked=walked,
                                      sparse_pages_held=held,
                                      sparse_slots=slots)
            if self._moe_layers:
                # what the burst's expert layers saw, from the program:
                # picks that landed on held experts, held experts with a
                # row (summed over layers and steps) and the most rows
                # one expert took in a step
                # ... and the rows the layers' layouts moved in and out
                # of their tile buffers beside the rows of the whole
                # layouts: this burst's, then those of the admissions
                # before it (a prefill is not read back: its count comes
                # with the next burst's)
                (rows, touched, most, moved, layout,
                 pre_moved, pre_layout) = (int(v) for v in stats)
                self.last_burst_experts = (rows, touched, most)
                self.moe_rows_held += rows
                self.moe_rows_routed += k * self._picks_a_step
                self.moe_rows_moved += moved + pre_moved
                self.moe_rows_layout += layout + pre_layout
                if traced and getattr(span, "attrs", None) is not None:
                    span.attrs.update(expert_rows=rows,
                                      experts_touched=touched,
                                      expert_rows_max=most,
                                      expert_rows_moved=moved,
                                      expert_rows_layout=layout,
                                      prefill_rows_moved=pre_moved,
                                      prefill_rows_layout=pre_layout)
        self.burst_seq += 1
        self.last_burst_active = int(np.count_nonzero(self._active))
        self.last_finite = np.asarray(finite)
        toks = np.asarray(toks)
        if self.drafter is not None:
            # plain-burst tokens grow the drafter's context too — a tick
            # without proposals must not blind the next one
            for slot in np.flatnonzero(self._active):
                self.drafter.extend(int(slot), toks[:, slot].tolist())
        return toks

    # ------------------------------------------------- speculative decoding
    def propose_drafts(self):
        """Ask the drafter for every active slot's next-token proposals
        (host-pure, microseconds). Returns (drafts (max_slots, spec_k)
        int32, draft_lens (max_slots,) int32, any_drafted bool) — the
        scheduler dispatches `step_verify` when any slot drafted and
        falls back to `step_burst` otherwise (both greedy-exact, so the
        choice is invisible in the token stream)."""
        if self.drafter is None:
            raise RuntimeError("propose_drafts needs spec_decode=True")
        k = self.config.spec_k
        drafts = np.zeros((self.config.max_slots, k), np.int32)
        lens = np.zeros((self.config.max_slots,), np.int32)
        for slot in np.flatnonzero(self._active):
            d = self.drafter.propose(int(slot), k)
            if d:
                drafts[slot, :len(d)] = d
                lens[slot] = len(d)
        return drafts, lens, bool(lens.any())

    def step_verify(self, drafts: np.ndarray,
                    draft_lens: np.ndarray) -> tuple:
        """One verify dispatch over a drafted window (`_verify` for the
        program; this is its host half). Returns (tokens, counts,
        finite): tokens (spec_k+1, max_slots) row-major like a burst,
        counts (max_slots,) how many leading rows are REAL for each
        slot (accepted + 1 correction; 0 for inactive slots), finite
        (spec_k+1, max_slots) per-token flags.

        Per-slot lengths advance by counts — a slot whose whole draft
        was rejected still nets one real token (the correction IS the
        plain greedy token), so a verify dispatch never loses ground
        to a burst. Growth covers the worst case (spec_k + 1
        positions) up front and the rejected tail's surplus blocks are
        returned to the pool after the dispatch — speculation holds
        blocks only for tokens it actually kept."""
        if self.drafter is None:
            raise RuntimeError("step_verify needs spec_decode=True")
        k = int(drafts.shape[1])
        nblk_before = self._nblk.copy()
        tr = self.tracer
        traced = tr is not None and tr.enabled
        with self._span("burst_plan") if traced else _NULL:
            grown = self._grow_tables(k + 1)
            splits = self._cow_split(k + 1)
        if traced:
            span, disp, read = self._burst_spans(
                "verify", k=k, drafted=int(draft_lens.sum()),
                blocks_grown=grown, cow_splits=splits)
        else:
            span = disp = read = _NULL
        with span:
            with disp:
                (self._cache, self._last_logits, toks,
                 accepted, finite) = self._verify_jit(
                    self.params, self._cache, self._last_logits,
                    jnp.asarray(self._attn), jnp.asarray(self._active),
                    jnp.asarray(drafts), jnp.asarray(draft_lens),
                    jnp.asarray(self._pt), jnp.asarray(self._len),
                )
                _await_dispatch(self._cache, self._last_logits)
            with read:  # the host waits for the device here
                toks, accepted, finite = jax.device_get(
                    (toks, accepted, finite)
                )
        accepted = np.asarray(accepted)
        counts = np.where(self._active, accepted + 1, 0).astype(np.int64)
        self._len[self._active] += counts[self._active].astype(np.int32)
        # rollback, block half: surplus blocks grown for the rejected
        # tail (provably this dispatch's own fresh allocations — the
        # floor never dips below the pre-grow table) go back to the pool
        for slot in np.flatnonzero(self._active):
            floor = max(self._blocks_for(int(self._len[slot])),
                        int(nblk_before[slot]))
            self._nblk[slot] = rewind_block_tail(
                self.blocks, self._pt[slot], int(self._nblk[slot]), floor
            )
        self.spec_drafted_tokens += int(draft_lens[self._active].sum())
        self.spec_accepted_tokens += int(accepted[self._active].sum())
        self.spec_dispatches += 1
        self.burst_seq += 1
        self.last_burst_active = int(np.count_nonzero(self._active))
        toks = np.asarray(toks).T          # (k+1, max_slots) row-major
        finite = np.asarray(finite).T
        self.last_finite = finite
        if self.drafter is not None:
            for slot in np.flatnonzero(self._active):
                n = int(counts[slot])
                self.drafter.extend(int(slot), toks[:n, slot].tolist())
        return toks, counts, finite

    def context_len(self, slot: int) -> int:
        """The slot's current context length (prompt span + decoded
        tokens) — can exceed the model's max_len, the paged headline."""
        return int(self._len[slot])

    def poison_slot(self, slot: int) -> None:
        """Overwrite one slot's pending sampling input with NaN — the
        deterministic stand-in for a numerical blow-up (serve/faults.py
        `nan_logits`). Host-side, between dispatches; the next decode
        burst's finite flag turns False for exactly this slot."""
        self._last_logits = self._last_logits.at[slot].set(jnp.nan)

    def compile_stats(self) -> dict:
        """Jit cache sizes — the no-recompilation-churn observable: the
        prefill and decode programs, the prefix-cache admission paths
        and the speculative verify program. After warmup (one admit per
        bucket width in play, one decode dispatch) all five counters
        must stay flat however many requests churn through (prefix
        hits, CoW splits, preempt/readmit, verify dispatches included;
        conftest `compile_guard`, tests/test_serve_scheduler.py)."""
        return {
            "prefill_compiles": self._prefill_jit._cache_size(),
            "decode_compiles": self._decode_jit._cache_size(),
            "prefix_prefill_compiles": self._prefix_jit._cache_size(),
            "cow_compiles": self._cow_jit._cache_size(),
            "verify_compiles": self._verify_jit._cache_size(),
        }

    def _clear_slot(self, slot: int) -> None:
        self._pending_prompt.pop(slot, None)
        n = int(self._nblk[slot])
        if n:
            self.blocks.free([int(b) for b in self._pt[slot, :n]])
        if self.wgroup is not None:
            self.wgroup.clear(slot)
        if self.drafter is not None:
            self.drafter.end(slot)
        self.allocator.free(slot)
        self._pt[slot, :] = 0
        self._nblk[slot] = 0
        self._budget[slot] = 0
        self._len[slot] = 0
        self._attn[slot] = 0
        self._active[slot] = False

    def release(self, slot: int) -> None:
        """Free the slot and DEREF its blocks: sole-owned blocks return
        to the pool, blocks shared with the prefix cache or fork
        siblings stay for their other holders. The page-table row is
        pointed back at the garbage block so the batched decode keeps
        static shapes; stale K/V in freed blocks is invisible to the
        next occupant (masked to its own written positions — pinned in
        tests/test_kv_pages.py)."""
        self._clear_slot(slot)
        self._slot_trace.pop(slot, None)
        self._slot_seed.pop(slot, None)
        self._fork_n.pop(slot, None)
