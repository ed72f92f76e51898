"""Continuous-batching serving engine over the paged KV store.

The split architecture at serving time (DESIGN.md §3.4):
  * data plane: ONE compiled fixed-shape ``serve_step(tokens[B, C],
    n_new[B])`` over the pool arrays — never retraced, never reallocated
    (the pre-fault + mmap-cache analogue).  Each step processes up to C new
    tokens per slot: prefill consumes the prompt chunk-by-chunk, decode is
    the degenerate n_new=1 slice of the SAME program, and mixed
    prefill/decode batches are one call.  C defaults to ``page_tokens``, so
    a full prefill chunk fills exactly one KV page and costs exactly ONE
    metadata publish — the chunk/page invariant (DESIGN.md §3.4/§8).
  * control plane: this engine + core.kvcache.PagedKVCache do *metadata
    only* — slot admission (with prefix-cache attach: a prompt whose
    prefix matches a published page chain adopts those pages and skips
    their prefill chunks entirely), per-slot chunk cursors, bulk page
    allocation (pre-allocated free list), publish-on-page-fill via
    ``PagedKVCache.commit`` (relink; one 64 B ``OP_KV_COMMIT`` oplog entry
    per page for STRICT sequences), refcounted prefix sharing, CoW forks.

Consistency modes are PER-REQUEST (per-sequence in the controller): STRICT
and POSIX requests batch together on one engine, and only the STRICT ones
pay oplog publishes — the libfs-per-application split of the paper.
Sampling parameters are also per-request (``SamplingParams``); the host
sampler stays in one place (``_sample``).

The controller is AUTHORITATIVE for the device page table: the engine
mirrors controller rows into the device array whenever metadata changes.
Pool geometry comes from ``api.kv_geometry`` — the same formula that sizes
the pools — never from inspecting an initial page table (which under-sizes
the pool when the table is sparse).

Token ids are picked on the device: one jitted selector (``select_tokens``)
takes the step's argmax over every position, and the host copies those
ids back, ``[B, C]`` int32, never the ``[B, C, V]`` logits.  Softmax rows
for stochastic requests are sampled on the host: only then does the
step also copy each slot's last valid logits row.
"""

from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field, replace
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.kvcache import KVPoolFullError, PagedKVCache
from ..core.modes import Mode
from ..core.oplog import OpLog
from ..core.tier import HostTier
from ..models.registry import ModelAPI
from ..obs import Obs, attach_serving
from ..obs.trace import interval, span
from .prefix_cache import PrefixCache, _Node


@dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling: temperature <= 0 means greedy (argmax);
    top_k == 0 means the full vocabulary.  The host sampler itself stays
    in one place (``ServingEngine._sample``)."""
    temperature: float = 0.0
    top_k: int = 0

    def __post_init__(self) -> None:
        if self.top_k < 0:
            raise ValueError("top_k must be >= 0")

    @property
    def greedy(self) -> bool:
        """Argmax sampling: the token is the device's pick."""
        return self.temperature <= 0.0 or self.top_k == 1

GREEDY = SamplingParams()


@jax.jit
def select_tokens(logits: jax.Array, n_new: jax.Array
                  ) -> Tuple[jax.Array, jax.Array]:
    """On the device, what the host sampler needs of a step's ``logits
    [B, C, V]``: ``ids [B, C]``, the argmax of every position (int32;
    ``jnp.argmax`` returns the first maximal index, the lowest-id tie rule
    of ``ServingEngine._sample``), and ``last [B, V]``, each slot's row at
    its last valid position ``max(n_new - 1, 0)``.  One module-level jit:
    every engine in the process shares its one executable per width."""
    ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # a per-slot dynamic slice reads B rows (a take_along_axis here
    # compiled to a copy of the whole logits for the v5e)
    last = jax.vmap(lambda rows, p: jax.lax.dynamic_index_in_dim(
        rows, p, 0, keepdims=False))(logits, jnp.maximum(n_new - 1, 0))
    return ids, last


class _Selected(NamedTuple):
    """What the host holds of one step's logits (``select_tokens``)."""
    ids: np.ndarray                 # [B, C] argmax ids
    last: Optional[np.ndarray]      # [B, V] last valid rows, when copied
    vocab: int


class _Picked:
    """A logits row of which the host holds only the device's argmax:
    all the greedy branch of ``ServingEngine._sample`` reads of a row."""
    __slots__ = ("token", "vocab")

    def __init__(self, token: int, vocab: int) -> None:
        self.token = token
        self.vocab = vocab

    def argmax(self) -> int:
        return self.token

    def __len__(self) -> int:
        return self.vocab


@dataclass(frozen=True)
class SpecConfig:
    """Per-session speculative decoding (DESIGN.md §8): an n-gram
    prompt-lookup drafter proposes up to ``k`` tokens per decode step;
    the engine stages them through the SAME fixed-shape chunk lane
    prefill uses, verifies all of them against the target logits in ONE
    step, keeps the longest agreeing prefix and ``rollback``s the rest
    (metadata-only, relink-style).  Greedy-only: a stochastic sampler
    has no stable notion of draft/target agreement, so non-greedy
    requests silently run unspeculated."""
    k: int = 4          # max drafted tokens per step (clamped to C - 1)
    ngram_max: int = 3  # longest suffix n-gram the drafter matches
    ngram_min: int = 1

    def __post_init__(self) -> None:
        if self.k < 1:
            raise ValueError("spec k must be >= 1")
        if not 1 <= self.ngram_min <= self.ngram_max:
            raise ValueError("need 1 <= ngram_min <= ngram_max")

# cache sub-dict keys that hold recurrent/SSM state (vs paged KV pools).
# ONE source of truth: the slot-state walks, the recurrent-arch guard for
# the prefix cache, and the fork page copy all consult this set — adding a
# new state kind in the models must extend it here or the guard misses.
RECURRENT_STATE_KEYS = frozenset({"conv", "h", "ssd"})


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int = 16
    mode: Mode = Mode.POSIX              # per-request consistency mode
    sampling: SamplingParams = GREEDY
    output: List[int] = field(default_factory=list)
    slot: Optional[int] = None
    seq_id: Optional[int] = None
    prompt_pos: int = 0                  # per-slot chunk cursor
    prefix_tokens: int = 0               # prompt tokens adopted from the cache
    spec: Optional[SpecConfig] = None    # speculative decode (None = off)
    spec_drafted: int = 0                # drafted tokens (this request)
    spec_accepted: int = 0               # drafts the target model agreed with
    promoting: bool = False              # host-tier H2D copy in flight: the
                                         # slot is held out of the step until
                                         # the page-table flip lands
    engine_id: Optional[int] = None      # owning engine in a cluster (the
                                         # router tags it; migration retags)
    done: bool = False
    truncated: bool = False              # finished early (pool backpressure)
    stalled: bool = False                # run_until_done hit max_steps first
    cancelled: bool = False              # aborted by the caller
    # raw perf_counter_ns stamps: submit, and slot assignment (the
    # serve.queued interval between them)
    t_submit_ns: int = 0
    t_admit_ns: int = 0
    # obs-only per-request overhead ledger (None when uninstrumented).
    # Shared batch time is attributed by even split across the step's
    # participants, so request ledgers sum to the engine's phase totals.
    ledger: Optional[Dict[str, int]] = None

    @property
    def in_prefill(self) -> bool:
        return self.prompt_pos < len(self.prompt)


class ServingEngine:
    def __init__(self, api: ModelAPI, params, *, max_batch: int = 8,
                 max_seq: int = 512, page_tokens: int = 16,
                 chunk_tokens: Optional[int] = None, greedy: bool = True,
                 seed: int = 0, mode: Mode = Mode.POSIX,
                 oplog: Optional[OpLog] = None,
                 prefix_cache: "bool | PrefixCache | None" = None,
                 spec: Optional[SpecConfig] = None,
                 host_cache_pages: int = 0,
                 pool_pages: Optional[int] = None,
                 obs: Optional[Obs] = None,
                 step_fn=None,
                 device: Optional[jax.Device] = None) -> None:
        self.api = api
        # ``device`` pins this engine to one chip: its weights, pools and
        # every host upload live there, so the step runs there (a cluster
        # replica per chip).  None keeps JAX's default placement.
        self.device = device
        self.params = params if device is None \
            else jax.device_put(params, device)
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.page_tokens = page_tokens
        # C == page_tokens by default: one full chunk == one page == one
        # publish; chunk_tokens=1 recovers the token-at-a-time baseline
        self.chunk = int(chunk_tokens) if chunk_tokens else page_tokens
        # engine-wide DEFAULT sampling; requests override per-call
        self.default_sampling = GREEDY if greedy \
            else SamplingParams(temperature=1.0)
        self.rng = np.random.default_rng(seed)
        # built on the device itself, then committed to it
        with jax.default_device(device):
            self.caches = jax.device_put(
                api.init_caches(max_batch, max_seq, page_tokens), device)
        geom = api.kv_geometry(max_batch, max_seq, page_tokens)
        if "page_table" in self.caches:
            assert tuple(self.caches["page_table"].shape) == \
                (max_batch, geom.pages_per_seq), "geometry/pool mismatch"
        # cache-pressure cap (benchmarks, capacity planning): the device
        # arrays keep their full geometry — the controller simply never
        # hands out pages past ``pool_pages``, so pressure is modeled
        # purely on the metadata plane (free list + backpressure ladder)
        if pool_pages is not None and 1 < pool_pages < geom.num_pages:
            geom = replace(geom, num_pages=pool_pages)
        self.controller = PagedKVCache(geom, mode=mode, oplog=oplog)
        # prefix cache: True builds one over this controller; an instance
        # is adopted as-is; None/False disables.  Models carrying recurrent
        # state (conv/h/ssd leaves) cannot reuse KV pages without also
        # replaying the recurrent scan, so the cache is refused for them —
        # attaching would silently skip state updates for the shared span.
        self._recurrent = self._has_recurrent_state()
        if prefix_cache and self._recurrent:
            prefix_cache = None
        self.prefix_cache: Optional[PrefixCache] = (
            PrefixCache(self.controller) if prefix_cache is True
            else prefix_cache or None)
        # host-memory cold tier under the pool (DESIGN.md §8a): spilled
        # prefix chains survive eviction as HOST-resident trie nodes and
        # come back via staged, compute-overlapped H2D promotion.  Only
        # meaningful with a prefix cache (the trie holds the residency
        # markers), hence implicitly refused for recurrent archs too.
        self.tier: Optional[HostTier] = None
        if self.prefix_cache is not None and host_cache_pages > 0:
            self.tier = HostTier(host_cache_pages,
                                 read_page=self._gather_page,
                                 write_page=self._scatter_page)
            self.prefix_cache.tier = self.tier
        # staged promotions awaiting their page-table flip; each entry is
        # {"req", "plan": [(node, dst_page, host_slot)], "tokens", "t_enq"}
        self._promotions: List[dict] = []
        self._page_ops = None        # fused page gather/scatter/copy jits
        # speculative decoding default (requests override per-submit).
        # Refused for recurrent-state models for the same reason as the
        # prefix cache: rollback can rewind paged KV (metadata-only) but
        # NOT carried conv/h/ssd state, so a rejected draft would leave
        # the recurrent state advanced past the accepted extent.
        self.default_spec = None if self._recurrent else spec
        # hard per-slot token cap: the fixed-shape step addresses positions
        # up to lengths + C - 1, which must stay inside the page-table row
        self._cap = min(max_seq - 1, geom.max_tokens_per_seq - self.chunk)
        # step_fn lets a cluster share ONE jitted program across its
        # engines (identical shapes => identical executable; N engines
        # must not pay N compiles)
        self._step_fn = step_fn if step_fn is not None \
            else jax.jit(api.serve_step)
        self.waiting: List[Request] = []
        self.active: Dict[int, Request] = {}     # slot -> request
        self.finished: List[Request] = []
        self._rid = itertools.count()
        self.steps = 0
        # plain-int stats, read lazily by the obs registry (DESIGN.md §10);
        # kept unconditionally — incrementing an int costs nothing, and
        # benches read them even with obs off
        self.tokens_processed = 0
        self.truncations = 0
        self.cancels = 0
        self.backpressure_stalls = 0
        # speculative-decode counters (accept rate = accepted / drafted)
        self.spec_steps = 0             # steps that carried >=1 draft
        self.spec_drafted_tokens = 0
        self.spec_accepted_tokens = 0
        self.spec_rejected_tokens = 0
        self.spec_rollbacks = 0         # rollbacks that actually shrank
        # token selection: tokens taken from the device's argmax ids, and
        # logits rows copied to the host for a stochastic request
        self.device_selected_tokens = 0
        self.host_sampled_rows = 0
        self.draft_ns = 0               # host drafting time (client bucket)
        # tier promotion counters (lag = enqueue -> page-table flip; the
        # windowed profiler derives promote_lag_ms from the pair)
        self.promote_events = 0
        self.promote_lag_ns = 0
        self.obs = obs
        self._tracer = obs.tracer if obs is not None else None
        if obs is not None:
            attach_serving(obs, self)
            if self.tier is not None:
                self.tier.tracer = obs.tracer

    # ------------------------------------------------------------------ API

    def submit(self, prompt: List[int], max_new_tokens: int = 16, *,
               mode: Optional[Mode] = None,
               sampling: Optional[SamplingParams] = None,
               spec: Optional[SpecConfig] = None) -> Request:
        if not prompt:
            raise ValueError("empty prompt")
        # statically infeasible prompts are rejected here; prompts that fit
        # but contend for pages at runtime go through backpressure and come
        # back flagged ``truncated`` instead.  Bounds: every prefill chunk
        # starts at a multiple of C and addresses pad positions up to
        # start + C - 1 (whole-chunk floor of the page-table row), and a
        # lone sequence can allocate at most the usable pool (num_pages
        # minus the reserved null page).
        g = self.controller.geom
        limit = min(self.max_seq - 1,
                    (g.max_tokens_per_seq // self.chunk) * self.chunk,
                    min(g.pages_per_seq, g.num_pages - 1) * g.page_tokens)
        if len(prompt) > limit:
            # a prompt that can never stage must be rejected at admission —
            # raising mid-step would abort every request in the batch
            raise ValueError(
                f"prompt of {len(prompt)} tokens exceeds the per-slot "
                f"capacity of {limit} (pool geometry / window bound)")
        samp = self.default_sampling if sampling is None else sampling
        eff_spec = spec if spec is not None else self.default_spec
        if eff_spec is not None and (
                self._recurrent                       # can't rewind state
                or not samp.greedy):
            eff_spec = None      # greedy-only (see SpecConfig docstring)
        req = Request(next(self._rid), list(prompt), max_new_tokens,
                      mode=self.controller.mode if mode is None else mode,
                      sampling=samp, spec=eff_spec)
        req.t_submit_ns = time.perf_counter_ns()
        if self._tracer is not None:
            self._tracer.instant(
                "submit", "serve",
                args={"rid": req.rid, "prompt": len(req.prompt)})
        self.waiting.append(req)
        return req

    def run_until_done(self, max_steps: int = 10000) -> List[Request]:
        for req in list(self.active.values()) + self.waiting:
            req.stalled = False          # a fresh drive gets a fresh verdict
        steps0 = self.steps              # budget is per-call, not lifetime
        while (self.waiting or self.active) and \
                self.steps - steps0 < max_steps:
            self.step()
        # hitting max_steps with work outstanding is a TIMEOUT, not
        # completion: flag the survivors so callers can tell the two apart
        # (they stay queued/active and resume if stepped again)
        for req in list(self.active.values()) + self.waiting:
            req.stalled = True
        return self.finished

    # ------------------------------------------------------------------ engine step

    def _span(self, name: str, **args: int) -> span:
        """A ``serve.*`` phase of the current step (DESIGN.md §10)."""
        return span(name, self._tracer, step=self.steps, **args)

    def _admit(self) -> None:
        free_slots = [s for s in range(self.max_batch) if s not in self.active]
        while self.waiting and free_slots:
            slot = free_slots.pop(0)
            req = self.waiting.pop(0)
            req.slot = slot
            req.t_admit_ns = time.perf_counter_ns()
            interval("serve.queued", req.t_submit_ns, req.t_admit_ns,
                     self._tracer, step=self.steps, rid=req.rid)
            req.seq_id = self.controller.create_seq(mode=req.mode)
            # prefix-cache attach: adopt the longest published page chain
            # matching the prompt (refcounted hard links) — those tokens'
            # prefill chunks are skipped outright, and the device length
            # starts past them so the first real chunk lands after the
            # shared span
            start = 0
            if self.prefix_cache is not None and req.in_prefill:
                links, n_tok = self.prefix_cache.match_links(
                    req.prompt, align=self.chunk)
                links, n_tok = self._promotable(links, n_tok)
                n_host = sum(1 for nd in links if nd.on_host)
                if n_tok and not n_host:
                    pages = [nd.page for nd in links]
                    with self._span("serve.adopt_prefix", rid=req.rid,
                                    pages=len(pages), tokens=n_tok):
                        self.controller.adopt_prefix(req.seq_id, pages)
                    req.prompt_pos = req.prefix_tokens = start = n_tok
                elif n_tok:
                    # tiered attach: hard-link the device links, reserve
                    # fresh pages for the host links, and hold the slot
                    # out of the step until the async H2D copies are
                    # enqueued and the page table flips
                    # (_flip_promotions).  Device length stays 0 so the
                    # fixed-shape step cannot read the in-flight pages.
                    t_enq = time.perf_counter_ns()
                    spec = [None if nd.on_host else nd.page for nd in links]
                    _, fresh = self.controller.adopt_prefix_staged(
                        req.seq_id, spec)
                    hosted = [nd for nd in links if nd.on_host]
                    plan: List[Tuple[_Node, int, int]] = [
                        (nd, page, nd.host_slot)
                        for nd, (_, page) in zip(hosted, fresh)]
                    req.promoting = True
                    req.prompt_pos = req.prefix_tokens = n_tok
                    self._promotions.append(
                        {"req": req, "plan": plan, "tokens": n_tok,
                         "t_enq": t_enq})
            self._set_device_length(slot, start)
            self._zero_slot_state(slot)
            if self.obs is not None:
                # per-request overhead ledger: client/API time is the queue
                # wait from submit to admission; scheduler/device/persistence
                # accrue per step, split evenly across the step's batch so
                # request ledgers sum to the engine's phase totals
                req.ledger = {
                    "client_ns": req.t_admit_ns - req.t_submit_ns,
                    "scheduler_ns": 0, "device_ns": 0, "persistence_ns": 0,
                    "steps": 0}
            self.active[slot] = req

    def step(self) -> None:
        """One engine step.  Each phase is one ``serve.*`` span; the
        overhead ledger reads its split from their stamps."""
        persist0 = self.controller.persist_ns
        with self._span("serve.step") as st:
            with self._span("serve.admit"):
                self._admit()
            if not self.active:
                return
            # decode-only batches run the WIDTH-1 slice of the same jitted
            # step (jax caches one executable per shape: one prefill
            # program, one decode program — still never retraced), so
            # steady-state decode never pays the C-wide compute for 1
            # valid token
            prefill_any = any(r.in_prefill for r in self.active.values())
            with self._span("serve.stage"):
                # drafting runs BEFORE the width choice: speculative tokens
                # ride the same chunk lane prefill uses, so a step with
                # drafts runs the C-wide program
                drafts, draft_ns = self._draft_all()
                C = self.chunk if (prefill_any or drafts) else 1
                tokens, n_new, feeds, spec_feeds = self._stage(C, drafts)
            if not feeds:
                # nothing to compute this step, but staged promotions must
                # still land (their adopters are the only work left)
                self._flip_promotions(overlapped=False)
                return
            st.args["width"] = C
            with self._span("serve.sync_page_table"):
                self._sync_page_table()
            # keep the participants: finished requests leave ``active`` in
            # the post loop, but the step's shared cost is still theirs
            part_reqs = [self.active[slot] for slot in feeds]
            with self._span("serve.dispatch") as dispatch:
                tok_dev = self._upload(tokens)
                n_new_dev = self._upload(n_new)
                logits, self.caches = self._step_fn(
                    self.params, tok_dev, self.caches, n_new_dev)
                vocab = logits.shape[-1]
                ids, last = select_tokens(logits, n_new_dev)
                del logits        # the full logits never leave the device
            # staged promotions land HERE — after the step's compute was
            # dispatched, against the post-step pool arrays (disjoint
            # pages), so the H2D copies ride the async queue concurrent
            # with the step instead of serializing ahead of the prefill
            # that needs them; dataflow ordering guarantees the NEXT step
            # reads the copied bytes
            self._flip_promotions(overlapped=True)
            with self._span("serve.device_wait") as wait:
                jax.block_until_ready(ids)
            # the ids always come back; the last rows only when a
            # stochastic request samples on the host this step
            rows = self._host_rows(feeds, spec_feeds)
            nbytes = ids.nbytes + (last.nbytes if rows else 0)
            with self._span("serve.logits_to_host", bytes=nbytes, rows=rows):
                sel = _Selected(np.asarray(ids),
                                np.asarray(last) if rows else None, vocab)
            self.tokens_processed += int(sum(feeds.values()))
            with self._span("serve.sample"):
                self._sample_feeds(feeds, spec_feeds, sel)
            self.steps += 1
        if self.obs is not None:
            self._account_step(self.obs, st, dispatch.ns + wait.ns, draft_ns,
                               self.controller.persist_ns - persist0,
                               part_reqs,
                               "prefill" if prefill_any else "decode")

    def _draft_all(self) -> Tuple[Dict[int, List[int]], int]:
        """Host-side prompt lookup for every speculating slot: the drafts
        and the ns spent, which the overhead split charges to CLIENT time
        (guesswork spent on the model's behalf, not engine scheduling)."""
        drafts: Dict[int, List[int]] = {}
        if not any(r.spec is not None for r in self.active.values()):
            return drafts, 0
        with self._span("serve.draft") as dr:
            for slot, req in self.active.items():
                sp = req.spec
                if sp is None or req.in_prefill or not req.output:
                    continue
                total = self.controller.seq_length(req.seq_id)
                # width-aware clamp: the feed is 1 + k tokens, and the
                # NEXT step's 1-token append must still fit under _cap
                k = min(sp.k, self.chunk - 1,
                        self._cap - total - 1,
                        req.max_new_tokens - len(req.output) - 1)
                if k >= 1:
                    d = self._draft(req, k)
                    if d:
                        drafts[slot] = d
            dr.args.update(slots=len(drafts),
                           tokens=sum(map(len, drafts.values())))
        self.draft_ns += dr.ns
        return drafts, dr.ns

    def _stage(self, C: int, drafts: Dict[int, List[int]],
               ) -> Tuple[np.ndarray, np.ndarray, Dict[int, int],
                          Dict[int, List[int]]]:
        """The feed loop: pick each slot's tokens, make room, and stage
        them in the controller.  Returns the step's ``tokens[B, C]``,
        ``n_new[B]``, the fed count per slot, and the drafts fed."""
        tokens = np.zeros((self.max_batch, C), np.int32)
        n_new = np.zeros((self.max_batch,), np.int32)
        feeds: Dict[int, int] = {}
        spec_feeds: Dict[int, List[int]] = {}    # slot -> drafts actually fed
        for slot, req in list(self.active.items()):
            if req.promoting:
                continue        # H2D copy in flight; joins after the flip
            total = self.controller.seq_length(req.seq_id)
            if req.in_prefill:
                # prompts are bounded at submit; prefill may stage up to
                # that limit regardless of the decode cap below
                take = min(C, len(req.prompt) - req.prompt_pos)
                feed = req.prompt[req.prompt_pos:req.prompt_pos + take]
            else:
                # width-aware overflow guard (was `total >= _cap` checked
                # AFTER the append — correct only for 1 token per step):
                # a decode/speculative append of ``take`` tokens must keep
                # total + take <= _cap, or the fixed-shape step addresses
                # past the page-table row / length capacity
                room = self._cap - total
                if room <= 0:
                    req.truncated = True    # capacity-bound, not completed
                    self._finish(slot, req)
                    continue
                if slot in drafts:
                    d = drafts[slot][:max(min(room - 1, C - 1), 0)]
                    feed = [req.output[-1]] + d
                    take = len(feed)
                    if d:
                        spec_feeds[slot] = d
                else:
                    take = 1
                    feed = [req.output[-1]]
            # backpressure: only the VALID tokens need pages (pad positions
            # fall back to the null page when the over-reserve can't be
            # had).  Cached-but-idle prefix pins are evicted first — live
            # sequences always outrank the cache — and only a chunk that
            # STILL cannot stage its valid tokens finishes the request,
            # flagged truncated, instead of stalling the whole batch
            need = self.controller.pages_needed(req.seq_id, total + take)
            if need > self.controller.num_free_pages:
                self.backpressure_stalls += 1
                if self.prefix_cache is not None:
                    # cached-but-idle prefixes yield to live sequences:
                    # release() evicts only pins whose page actually returns
                    # to the pool (idle — not shared with a live sequence),
                    # so it never drains hot shared chains for zero pages
                    self.prefix_cache.release(
                        need - self.controller.num_free_pages)
            if need > self.controller.num_free_pages:
                req.truncated = True
                self._finish(slot, req)
                continue
            tokens[slot, :take] = feed
            n_new[slot] = take
            feeds[slot] = take
            # CoW guard: after a rollback (or a fork/adopt) the kept tail
            # page may still be shared — an append must never write
            # through a shared page (rollback CoWs its own kept tail, so
            # this is belt-and-braces; it is O(1) metadata)
            try:
                cow = self.controller.prepare_append(req.seq_id, take)
            except KVPoolFullError:
                req.truncated = True
                self._finish(slot, req)
                del feeds[slot]
                spec_feeds.pop(slot, None)
                n_new[slot] = 0
                tokens[slot, :] = 0
                continue
            if cow is not None:
                self._copy_page_on_device(*cow)
            # metadata: reserve the FULL chunk's staging slots (pad tokens
            # land in allocated-but-unpublished slots), advance by the valid
            # count, publish (commit + oplog) every page the chunk filled.
            # Speculative feeds STAGE instead (publish=False): their pages
            # are published only for the verified prefix, by the epilogue's
            # commit(upto_len) — so a crash mid-speculation can never replay
            # an unverified extent (DESIGN.md §8)
            self.controller.append_tokens(req.seq_id, take, reserve=C,
                                          publish=slot not in spec_feeds)
        return tokens, n_new, feeds, spec_feeds

    def _host_rows(self, feeds: Dict[int, int],
                   spec_feeds: Dict[int, List[int]]) -> int:
        """Rows the host samples this step: one per stochastic request
        whose feed ends its prompt or decodes (speculation is greedy)."""
        rows = 0
        for slot, take in feeds.items():
            req = self.active[slot]
            if slot not in spec_feeds and not req.sampling.greedy \
                    and req.prompt_pos + take >= len(req.prompt):
                rows += 1
        return rows

    def _token(self, req: Request, i: int, sel: _Selected) -> int:
        """The token after feed position ``i`` of ``req``: the device's
        argmax for a greedy request, else a host sample of its last valid
        row (``i`` is then the feed's last position).  Either way it comes
        out of ``_sample``."""
        if req.sampling.greedy:
            self.device_selected_tokens += 1
            return self._sample(_Picked(int(sel.ids[req.slot, i]), sel.vocab),
                                req.sampling)
        self.host_sampled_rows += 1
        return self._sample(sel.last[req.slot], req.sampling)

    def _sample_feeds(self, feeds: Dict[int, int],
                      spec_feeds: Dict[int, List[int]],
                      sel: _Selected) -> None:
        """The post-step loop: advance cursors, publish finished prompts
        into the trie, sample (or verify drafts), and finish requests."""
        for slot, take in feeds.items():
            req = self.active[slot]
            if req.in_prefill:
                req.prompt_pos += take
                if req.in_prefill:
                    continue              # more prompt chunks to go
                if self.prefix_cache is not None:
                    # prompt fully ingested: publish its full pages into
                    # the trie so later prompts sharing the prefix adopt
                    # them (idempotent for the pages this request itself
                    # adopted at admission)
                    with self._span("serve.publish", rid=req.rid):
                        self.prefix_cache.insert(
                            req.prompt,
                            self.controller.committed_extents(req.seq_id))
            if slot in spec_feeds:
                # draft-and-verify epilogue: all take ids came back
                # from ONE step; accept the longest agreeing prefix and
                # roll back the rejected tail (metadata-only)
                self._verify_spec(req, take, spec_feeds[slot], sel)
            else:
                # the chunk's last valid position predicts the next
                # token: the final prefill chunk yields the first
                # generated token for free
                req.output.append(self._token(req, take - 1, sel))
            total = self.controller.seq_length(req.seq_id)
            if len(req.output) >= req.max_new_tokens:
                self._finish(slot, req)
            elif total >= self._cap:
                req.truncated = True        # capacity-bound, not completed
                self._finish(slot, req)

    def _verify_spec(self, req: Request, take: int, d: List[int],
                     sel: _Selected) -> None:
        """Accept the longest draft prefix the target model agrees with.

        The step fed ``[output[-1]] + d`` (take = 1 + len(d) positions),
        so position i's argmax (speculation is greedy-only) is the token
        AFTER the i-th fed token: take each in turn, stop at the first
        disagreement — every token up to and including that position is a
        real model output (the token after the last accepted draft comes
        free, exactly like the final prefill chunk's bonus token).

        KV protocol (DESIGN.md §8): the append above STAGED all ``take``
        positions (no publish).  ``commit(upto_len=target)`` publishes
        exactly the accepted full pages (STRICT: OP_KV_COMMIT), THEN
        ``rollback(target)`` drops the rejected tail and logs an
        OP_TRUNCATE tombstone on any shrink — in that order, so a crash
        at ANY point replays to exactly the accepted extent.  Rollback
        also CoWs a kept-but-shared tail page; the engine applies the
        device-side copy here."""
        with self._span("serve.verify", rid=req.rid,
                        drafted=len(d)) as sv:
            new_toks: List[int] = []
            for i in range(take):
                tok = self._token(req, i, sel)
                new_toks.append(tok)
                if i < take - 1 and d[i] != tok:
                    break
            accepted = len(new_toks) - 1      # drafts the model agreed with
            sv.args["accepted"] = accepted
        emit = new_toks[:req.max_new_tokens - len(req.output)]
        req.output.extend(emit)
        req.spec_drafted += len(d)
        req.spec_accepted += accepted
        self.spec_steps += 1
        self.spec_drafted_tokens += len(d)
        self.spec_accepted_tokens += accepted
        self.spec_rejected_tokens += len(d) - accepted
        # the KV invariant (prompt + output[:-1] staged) pins the target:
        # the last emitted token is NEXT step's feed, so its KV position
        # does not exist yet — exactly like normal decode
        total_after = self.controller.seq_length(req.seq_id)
        target = (total_after - take) + len(emit)
        if target < total_after:
            self.spec_rollbacks += 1
        with self._span("serve.rollback", rid=req.rid,
                        rejected=total_after - target) as rb:
            self.controller.commit(req.seq_id, upto_len=target)
            rb.args["cow"] = int(self._rollback_to(req, target))

    def _account_step(self, obs: Obs, st: span, device_ns: int,
                      draft_ns: int, persist_ns: int,
                      part_reqs: List[Request], phase: str) -> None:
        """Obs-only epilogue: split the ``serve.step`` span into scheduler
        / device / persistence (SplitFS-style attribution, DESIGN.md §10)
        — device is ``serve.dispatch`` + ``serve.device_wait`` — charge
        the phase ledger and each participant's request ledger, and tick
        the windowed profiler.  Drafting time is CLIENT time (guesswork
        outside the engine's control plane), subtracted from the
        scheduler bucket."""
        sched_ns = max(st.ns - device_ns - persist_ns - draft_ns, 0)
        obs.ledger.add(phase, sched_ns=sched_ns, device_ns=device_ns,
                       persist_ns=persist_ns, steps=1)
        if draft_ns:
            obs.ledger.add_client(draft_ns)
        n_part = len(part_reqs)
        for req in part_reqs:
            led = req.ledger
            if led is not None:
                led["scheduler_ns"] += sched_ns // n_part
                led["device_ns"] += device_ns // n_part
                led["persistence_ns"] += persist_ns // n_part
                led["client_ns"] += draft_ns // n_part
                led["steps"] += 1
        obs.profiler.observe()

    def cancel(self, req: Request) -> None:
        """Abort a queued or in-flight request, releasing its batch slot
        and pages immediately (an abandoned stream must not keep decoding
        on everyone else's engine pumps).  Finished requests are left
        untouched."""
        if req.done:
            return
        req.cancelled = True
        self.cancels += 1
        if self._tracer is not None:
            self._tracer.instant("cancel", "serve", args={"rid": req.rid})
        if req in self.waiting:
            self.waiting.remove(req)
            req.done = True
            self.finished.append(req)
        elif req.slot is not None and self.active.get(req.slot) is req:
            self._finish(req.slot, req)

    def detach(self, req: Request) -> None:
        """Hand a LIVE request off this engine (session migration,
        DESIGN.md §12): release its slot, sequence, and any staged
        promotion WITHOUT finishing it — the caller re-installs it on
        another engine from its snapshot.  ``free_seq`` tombstones
        (OP_UNLINK) the sequence in THIS engine's log, so this volume's
        crash replay never resurrects a session that moved away.  Called
        only on a live source (straggler steal); a dead engine's state is
        frozen and merely read."""
        if req in self.waiting:
            self.waiting.remove(req)
            return
        if req.slot is not None and self.active.get(req.slot) is req:
            self._promotions = [p for p in self._promotions
                                if p["req"] is not req]
            self.controller.free_seq(req.seq_id)
            del self.active[req.slot]
            req.slot = None
            req.seq_id = None

    def _finish(self, slot: int, req: Request) -> None:
        req.done = True
        req.stalled = False      # it completed after all: not a timeout
        if req.truncated:
            self.truncations += 1
        self.finished.append(req)
        self.controller.free_seq(req.seq_id)
        del self.active[slot]
        tracer = self._tracer
        if tracer is not None and req.ledger:
            # one request-lifetime span per slot lane, ledger in the args
            tracer.complete(
                f"req{req.rid}", "request", tracer.rel(req.t_admit_ns),
                tracer.now_ns(), tid=100 + slot,
                args={"rid": req.rid, "mode": req.mode.name,
                      "prompt": len(req.prompt), "output": len(req.output),
                      "prefix_tokens": req.prefix_tokens,
                      "spec_drafted": req.spec_drafted,
                      "spec_accepted": req.spec_accepted,
                      "truncated": req.truncated,
                      "cancelled": req.cancelled, **req.ledger})

    def _sample(self, row: np.ndarray, sp: SamplingParams = GREEDY) -> int:
        """The ONE host sampler: per-request temperature / top-k feed it
        parameters, but every emitted token comes out of this path.

        Tie-break contract: LOWEST token id wins every tie.  Greedy reads
        only ``row.argmax()``, the first maximal index: the engine hands
        it a ``_Picked`` row whose argmax ``select_tokens`` took on the
        device by the same rule (a NumPy row works alike); top-k truncation
        uses a stable descending sort so a tie straddling the k-th place
        keeps exactly k candidates (the lowest-id ones) rather than
        admitting every tied logit (the old partition-threshold behavior,
        which made verify-vs-draft agreement depend on memory order)."""
        if sp.greedy:
            return int(row.argmax())     # first (lowest-id) maximal entry
        z = row.astype(np.float64) / sp.temperature
        if sp.top_k and sp.top_k < len(row):
            keep = np.argsort(-z, kind="stable")[:sp.top_k]
            mask = np.full_like(z, -np.inf)
            mask[keep] = z[keep]
            z = mask
        z -= z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(self.rng.choice(len(row), p=p))

    # ------------------------------------------------------------------ speculation plumbing

    def _draft(self, req: Request, k: int) -> List[int]:
        """Prompt-lookup drafter: find the most recent earlier occurrence
        of the context's longest suffix n-gram (length ngram_max down to
        ngram_min) and propose up to k tokens that followed it.  Pure
        host-side guesswork — no model, no device."""
        ctx = req.prompt + req.output
        sp = req.spec
        for n in range(min(sp.ngram_max, len(ctx) - 1),
                       sp.ngram_min - 1, -1):
            pat = ctx[-n:]
            for i in range(len(ctx) - n - 1, -1, -1):
                if ctx[i:i + n] != pat:
                    continue
                cont = ctx[i + n:i + n + k]
                if len(cont) < k:
                    # the match runs into the live tail: the span from
                    # i+n to the end repeats with period p, so extend
                    # the draft by cycling it — a token stuck on
                    # ...x,x,x drafts [x]*k, a looping a,b,c drafts
                    # whole periods instead of a truncated stub
                    p = len(ctx) - (i + n)
                    cont = [ctx[i + n + (j % p)] for j in range(k)]
                return cont
        return []

    def _rollback_to(self, req: Request, target: int) -> bool:
        """Shrink a live request's KV to ``target`` tokens: controller
        rollback (OP_TRUNCATE tombstone on shrink + CoW of a kept-but-
        shared tail page) plus the device-side page copy and length
        mirror.  The page-table mirror refreshes at the next step's
        ``_sync_page_table`` — no device compute reads it in between.
        Returns True when the kept tail page was CoW'd."""
        cow = self.controller.rollback(req.seq_id, target)
        if cow is not None:
            self._copy_page_on_device(*cow)
        self._set_device_length(req.slot, target)
        return cow is not None

    # ------------------------------------------------------------------ host tier (DESIGN.md §8a)

    def _promotable(self, links: List[_Node], n_tok: int,
                    ) -> "Tuple[List[_Node], int]":
        """Trim a matched chain to what this admission can actually take.
        Host-resident links need one fresh device page each; the pool is
        asked to make room (release -> demote idle pins) first, and only
        a chain that STILL cannot reserve its pages is cut back to the
        leading device-resident run, re-aligned to the chunk grid."""
        n_host = sum(1 for nd in links if nd.on_host)
        if not n_host:
            return links, n_tok
        if self.tier is not None:
            shortfall = n_host - self.controller.num_free_pages
            if shortfall > 0:
                self.prefix_cache.release(shortfall)
            if n_host <= self.controller.num_free_pages:
                return links, n_tok
        keep = 0
        for nd in links:
            if nd.on_host:
                break
            keep += 1
        pt = self.page_tokens
        while keep and (keep * pt) % self.chunk:
            keep -= 1
        return links[:keep], keep * pt

    def _flip_promotions(self, *, overlapped: bool) -> None:
        """Land every staged promotion: enqueue the H2D copies (async),
        then flip — controller publish (``finish_adopt``: commit + oplog
        under the adopter's mode), trie re-pin (``promote_commit``), and
        the device length that lets the slot feed next step.  The flip
        strictly FOLLOWS the enqueue, so no step can address a promoted
        page before its copy is in the dispatch queue (relink-style
        publish ordering).  A node two admissions raced to promote is
        copied D2D from the winner's flipped page instead (the loser's
        pages stay privately owned by its adopter — correct, merely
        unshared)."""
        if not self._promotions:
            return
        pending, self._promotions = self._promotions, []
        with self._span("serve.promote_flip"):
            for pr in pending:
                self._flip_one(pr, overlapped)

    def _flip_one(self, pr: dict, overlapped: bool) -> None:
        req: Request = pr["req"]
        if req.done:
            # cancelled mid-promotion: free_seq already released the
            # reserved pages; the chain stays host-resident
            return
        for node, dst, slot in pr["plan"]:
            if node.on_host and node.host_slot == slot:
                self.tier.promote(slot, dst)
            else:
                self._copy_page_on_device(node.page, dst)
        self.controller.finish_adopt(req.seq_id)
        for node, dst, slot in pr["plan"]:
            self.prefix_cache.promote_commit(node, dst, slot)
        self._set_device_length(req.slot, pr["tokens"])
        req.promoting = False
        t1 = time.perf_counter_ns()
        lag = t1 - pr["t_enq"]
        self.promote_events += 1
        self.promote_lag_ns += lag
        tracer = self._tracer
        if tracer is not None:
            # own lane per slot (200+): the [enqueue -> flip] interval
            # deliberately OVERLAPS the engine lane's serve.dispatch span
            # — that overlap is the proof the copy ran concurrent with
            # compute, so it must not share tid 0 (nesting validator)
            tracer.complete(
                "promote", "tier", tracer.rel(pr["t_enq"]),
                tracer.rel(t1), tid=200 + req.slot,
                args={"rid": req.rid, "pages": len(pr["plan"]),
                      "tokens": pr["tokens"], "lag_us": lag / 1e3,
                      "overlapped": overlapped})

    def _pool_leaves(self) -> List:
        """The layer page pools in a deterministic walk order — that order
        IS the host arena's page layout, shared by gather/scatter/copy."""
        out: List = []

        def walk(node):
            if isinstance(node, dict):
                if set(node) <= RECURRENT_STATE_KEYS:
                    return          # recurrent state carries no pages
                for v in node.values():
                    walk(v)
            elif isinstance(node, tuple):
                for x in node:
                    if hasattr(x, "ndim") and x.ndim >= 4:
                        out.append(x)

        for key in ("group", "tail", "pools"):
            if key in self.caches:
                walk(self.caches[key])
        return out

    def _set_pool_leaves(self, new) -> None:
        """Rebind updated pool arrays into the cache pytree (the writeback
        half of ``_pool_leaves``; same walk order)."""
        it = iter(new)

        def walk(node):
            if isinstance(node, dict):
                if set(node) <= RECURRENT_STATE_KEYS:
                    return node
                return {k: walk(v) for k, v in node.items()}
            if isinstance(node, tuple):
                return tuple(next(it) if hasattr(x, "ndim") and x.ndim >= 4
                             else x for x in node)
            return node

        for key in ("group", "tail", "pools"):
            if key in self.caches:
                self.caches[key] = walk(self.caches[key])

    # page-granular device ops are fused into ONE jitted call each (page
    # index traced, so each compiles once): a per-leaf .at[].set loop
    # costs a dispatch per layer pool, which is exactly the host overhead
    # a demotion on the admission path or a promotion flip cannot afford.
    # Buffer donation makes the updates in-place where the backend
    # supports it (CPU ignores donation, so skip it there to avoid the
    # per-compile warning).
    def _jit_page_ops(self):
        if self._page_ops is None:
            donate = () if jax.default_backend() == "cpu" else (0,)

            def sl(x, page):
                return x[:, page] if x.ndim == 5 else x[page]

            def put(x, page, v):
                return (x.at[:, page].set(v) if x.ndim == 5
                        else x.at[page].set(v))

            gather = jax.jit(
                lambda leaves, page: tuple(sl(x, page) for x in leaves))
            scatter = jax.jit(
                lambda leaves, views, page: tuple(
                    put(x, page, v) for x, v in zip(leaves, views)),
                donate_argnums=donate)
            copy = jax.jit(
                lambda leaves, src, dst: tuple(
                    put(x, dst, sl(x, src)) for x in leaves),
                donate_argnums=donate)
            self._page_ops = (gather, scatter, copy)
        return self._page_ops

    def _gather_page(self, page: int) -> List[np.ndarray]:
        """D2H snapshot of one physical page across every layer pool (the
        demotion copy)."""
        gather, _, _ = self._jit_page_ops()
        dev = gather(tuple(self._pool_leaves()), page)
        return list(jax.device_get(dev))

    def _scatter_page(self, views: List[np.ndarray], page: int) -> None:
        """H2D write of a demoted page's bytes into device page ``page``.
        Dispatched asynchronously: callers sequence the metadata flip
        AFTER this returns, and dataflow ordering makes any later step
        that reads the page see the copied bytes."""
        _, scatter, _ = self._jit_page_ops()
        self._set_pool_leaves(
            scatter(tuple(self._pool_leaves()), tuple(views), page))

    # ------------------------------------------------------------------ device mirrors

    def _sync_page_table(self) -> None:
        """Mirror the controller's extent maps into the device page table.
        Inactive rows stay 0 = the reserved null page, so their fixed-shape
        pad writes are harmless by construction."""
        if "page_table" not in self.caches:
            return
        ctrl = self.controller.page_table()
        pt = np.zeros_like(ctrl[:self.max_batch])
        for slot, req in self.active.items():
            pt[slot] = ctrl[req.seq_id]
        self.caches["page_table"] = self._upload(pt)

    def _set_device_length(self, slot: int, value: int) -> None:
        lengths = np.asarray(self.caches["lengths"]).copy()
        lengths[slot] = value
        self.caches["lengths"] = self._upload(lengths)

    def _upload(self, x: np.ndarray) -> jax.Array:
        """H2D copy onto this engine's device (the default one when
        unpinned)."""
        return jax.device_put(x, self.device)

    def _walk_state(self, fn) -> None:
        """Apply ``fn(leaf, batch_dim) -> leaf`` to every recurrent/SSM
        state leaf (cache sub-dicts keyed conv/h/ssd; stacked group leaves
        carry a leading layer dim)."""
        def rewrite(node, batch_dim):
            if isinstance(node, dict):
                if set(node) <= RECURRENT_STATE_KEYS:
                    return {k: fn(v, batch_dim) for k, v in node.items()}
                return {k: rewrite(v, batch_dim) for k, v in node.items()}
            return node

        for key, batch_dim in (("group", 1), ("tail", 0)):
            if key in self.caches:
                self.caches[key] = rewrite(self.caches[key], batch_dim)

    def _has_recurrent_state(self) -> bool:
        """True when any cache leaf-group is recurrent/SSM state (conv/h/
        ssd): such models fold EVERY token into carried state, so adopting
        KV pages without re-running the span would corrupt generation."""
        found = False

        def visit(node):
            nonlocal found
            if isinstance(node, dict):
                if node and set(node) <= RECURRENT_STATE_KEYS:
                    found = True
                else:
                    for v in node.values():
                        visit(v)

        for key in ("group", "tail"):
            if key in self.caches:
                visit(self.caches[key])
        return found

    def _zero_slot_state(self, slot: int) -> None:
        """A freshly admitted slot must not inherit the previous occupant's
        recurrent state (pools need no reset — the extent walk only reads
        published positions)."""
        def zero(leaf, batch_dim):
            idx = (slice(None),) * batch_dim + (slot,)
            return leaf.at[idx].set(0)
        self._walk_state(zero)

    def _gather_slot_state(self, slot: int) -> List[np.ndarray]:
        """D2H snapshot of one slot's recurrent/SSM state across every
        conv/h/ssd leaf, in the deterministic ``_walk_state`` order (the
        migration payload for recurrent archs)."""
        out: List[np.ndarray] = []

        def grab(leaf, batch_dim):
            idx = (slice(None),) * batch_dim + (slot,)
            out.append(np.asarray(leaf[idx]))
            return leaf

        self._walk_state(grab)
        return out

    def _scatter_slot_state(self, slot: int, views: List[np.ndarray]) -> None:
        """H2D restore of a gathered slot state (same walk order)."""
        it = iter(views)

        def put(leaf, batch_dim):
            idx = (slice(None),) * batch_dim + (slot,)
            return leaf.at[idx].set(self._upload(next(it)))

        self._walk_state(put)

    def _copy_slot_state(self, src: int, dst: int) -> None:
        def copy(leaf, batch_dim):
            idx_s = (slice(None),) * batch_dim + (src,)
            idx_d = (slice(None),) * batch_dim + (dst,)
            return leaf.at[idx_d].set(leaf[idx_s])
        self._walk_state(copy)

    # ------------------------------------------------------------------ forking

    def fork(self, req: Request) -> Request:
        """Zero-copy fork (beam/speculative): shares full pages by refcount
        (hard links); the partially-filled tail page is CoW-copied on the
        device using the page pair the controller allocates."""
        assert req.slot is not None and not req.done
        # a mid-promotion fork would share a partially-committed extent
        # map; the flip lands at the next step, so callers just step first
        assert not req.promoting, "cannot fork during a staged promotion"
        free_slots = [s for s in range(self.max_batch) if s not in self.active]
        if not free_slots:
            raise RuntimeError("no free slot for fork")
        slot = free_slots[0]
        child = Request(next(self._rid), list(req.prompt), req.max_new_tokens,
                        mode=req.mode, sampling=req.sampling, spec=req.spec)
        child.output = list(req.output)
        child.prompt_pos = req.prompt_pos
        child.prefix_tokens = req.prefix_tokens
        child.slot = slot
        child.seq_id = self.controller.fork(req.seq_id)
        cow = self.controller.prepare_append(child.seq_id, 1)
        if cow is not None:
            self._copy_page_on_device(*cow)
        self._set_device_length(slot, self.controller.seq_length(child.seq_id))
        self._copy_slot_state(req.slot, slot)
        self.active[slot] = child
        self._sync_page_table()
        return child

    def _copy_page_on_device(self, src_page: int, dst_page: int) -> None:
        """Give the fork a private copy of its tail page in every layer pool
        (the partial-block copy analogue — the only data movement a fork
        costs)."""
        _, _, copy = self._jit_page_ops()
        self._set_pool_leaves(
            copy(tuple(self._pool_leaves()), src_page, dst_page))
