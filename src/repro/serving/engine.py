"""Continuous batching over the paged KV cache.

One engine iteration (:meth:`ServeEngine.step`) admits waiting requests
into free slots (derived flash prefill — ONE kernel sweep per prompt,
scattered into freshly allocated slabs), then decodes every active slot.
For paged families the slots decode TOGETHER: the slot axis is one more
dimension-lift level, so one ``batched_decode`` launch covers all of
them through a stacked ``[slot, k]`` page table, with greedy sampling on
device and ONE host transfer per iteration.  The stacked table always
has ``max_slots`` rows (trimmed to the widest live slot's page count, so
guard-skipped grid steps don't pile up behind short sequences), and each
slot pins ONE row for its whole residency (lowest free row at
admission).  A row whose slot is inactive is dead by runtime data alone
— position -1 fails every block-skip guard, and the dead slot's K/V
write is routed past the pool and dropped — so its entries are
canonically all zeros and slot-count changes re-key NOTHING.  The table
is rebuilt each launch as a PURE function of live occupancy (slabs
zero-padded per row), so the executable key depends on nothing
historical: position and liveness are runtime data in the POS aux, and
the canonical allocator makes freed slabs (hence whole tables) recur
across requests so executables stay cached.

Under page pressure the engine preempts: the youngest other running
sequence is evicted (slabs freed, request re-queued with its tokens so
far) and re-prefills when re-admitted — recompute preemption, the
standard continuous-batching fallback.

Families without a paged KV view (ssm, dense MHA with one head a group,
MLA, the Mamba-2/attention hybrid) serve contiguous, under the same
admission/slot scheduler: the slot axis of their decode cache is lifted,
so ONE stacked cache holds every slot on the batch axis (axis 1, under
the layer axis; the hybrid's recurrent state and its K/V alike), each
slot pins one row for its whole residency (lowest free row at
admission), and ONE
``registry.decode_step`` launch per iteration decodes every live slot,
with greedy argmax on device.  A launch covers rows ``[0, kb)``, where
``kb`` is the smallest power of two past the highest live row (capped at
``max_slots``): occupancy buckets, so a lone slot does not pay for the
state traffic of dead rows.  A dead row inside the bucket decodes token
0 at position 0; rows never interact, and admission overwrites the whole
row with the prefilled cache.  Families with no forward->decode cache
re-layout (windowed dense, the RG-LRU hybrid, moe, vlm) are refused at
admission.

Each iteration records host spans on the profiler's clock
(``jax.profiler.TraceAnnotation``; inert unless a trace is running):
``engine.step`` holds ``engine.admit``, one ``engine.prefill`` per
admitted request (``n`` tokens prefilled, until its first token is on the
host), ``engine.launch`` (the decode dispatch, ``n`` launches),
``engine.sync`` (the one host transfer) and ``engine.emit``.  The jitted
steps are named ``engine_prefill``, ``engine_decode``,
``engine_decode_paged`` and ``engine_decode_batched``, which the device
trace shows as their module names; ``engine_read_rows`` and
``engine_write_rows`` read and write rows of the stacked contiguous
cache.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.kernels import ops
from repro.models import registry, transformer
from repro.models.common import ArchConfig
from repro.serving.cache import OutOfPages, PagePool, pages_needed


@functools.partial(jax.jit, donate_argnums=0)
def engine_write_rows(cache: dict, rows: dict, row) -> dict:
    """``rows`` written into the stacked ``cache`` from batch row ``row``
    on (axis 1), in place: the cache is donated, and the row is traced, so
    one executable serves every row."""
    return jax.tree.map(
        lambda c, r: jax.lax.dynamic_update_slice_in_dim(c, r, row, axis=1),
        cache, rows)


@functools.partial(jax.jit, static_argnums=1)
def engine_read_rows(cache: dict, n: int) -> dict:
    """Rows ``[0, n)`` of the stacked ``cache`` (axis 1), in one dispatch
    for every leaf."""
    return jax.tree.map(lambda t: t[:, :n], cache)


@dataclass
class Request:
    """One generation request and its lifecycle metrics (caller clock)."""
    rid: int
    prompt: tuple
    max_new: int
    submit_t: float = 0.0
    admit_t: Optional[float] = None
    first_tok_t: Optional[float] = None
    done_t: Optional[float] = None
    evictions: int = 0


@dataclass
class _Slot:
    req: Request
    tokens: list            # prompt + emitted tokens, in order
    n_emitted: int = 0
    slabs: list = field(default_factory=list)     # the page table
    row: int = -1              # stacked-table or stacked-cache row


def _paged_capable(cfg: ArchConfig) -> bool:
    """The derived paged path covers dense GQA/MQA-grouped decode: the
    folding weld needs a blocked group-row axis (g >= 2) and a plain KV
    cache (not MLA's latent, not vlm's patch-prefixed prefill)."""
    return (cfg.family == "dense" and cfg.attention != "mla"
            and cfg.n_heads // cfg.n_kv_heads >= 2)


class ServeEngine:
    """Continuous-batching scheduler over one model.

    ``max_len`` bounds any sequence (prompt + generated); ``pool_pages``
    sizes the shared slab pool; ``page=None`` takes the page size from
    ``ops.default_decode_page`` — the solved stream block IS the page.
    ``interpret`` rides through to the kernels (interpret-mode Pallas on
    CPU).  The caller supplies timestamps (``now``, or a ``clock`` to
    ``step``/``run``) so latency metrics use one clock.
    """

    def __init__(self, cfg: ArchConfig, params: Optional[dict] = None, *,
                 key=None, max_slots: int = 2, max_len: int = 256,
                 pool_pages: Optional[int] = None,
                 page: Optional[int] = None, dtype=jnp.float32,
                 interpret: Optional[bool] = None,
                 eos_id: Optional[int] = None,
                 batched: Optional[bool] = None):
        self.cfg = cfg
        if params is None:
            params, _ = registry.init(cfg, key if key is not None
                                      else jax.random.PRNGKey(0))
        self.params = params
        self.max_slots = int(max_slots)
        self.max_len = int(max_len)
        self.interpret = interpret
        self.eos_id = eos_id
        self.paged = _paged_capable(cfg)
        # batched multi-slot decode rides the paged psi view (the stacked
        # table IS the slot lift); contiguous families lift the slot axis
        # of their cache instead (``_launch_contiguous``)
        self.batched = self.paged and batched is not False
        if batched and not self.paged:
            raise ValueError(
                f"batched decode needs the paged path; family "
                f"{cfg.family!r}/{cfg.attention!r} serves contiguous")
        if page is None:
            g = cfg.n_heads // max(1, cfg.n_kv_heads)
            page = min(ops.default_decode_page(
                self.max_len, cfg.n_kv_heads, max(2, g), cfg.head_dim_,
                dtype=str(jnp.dtype(dtype))), self.max_len)
        self.page = int(page)
        if pool_pages is None:
            pool_pages = self.max_slots * pages_needed(self.max_len,
                                                       self.page)
        #: stacked-table row width cap: the most pages a slot can ever
        #: hold (each launch trims to the widest live slot)
        self._view_pages = pages_needed(self.max_len, self.page)
        self.pool: Optional[PagePool] = (
            PagePool(cfg, pool_pages, self.page, dtype) if self.paged
            else None)
        self.dtype = dtype
        #: the stacked contiguous decode cache, ``max_slots`` rows on axis
        #: 1; allocated at the first admission from that decode cache's
        #: own shapes and dtypes
        self._cache: Optional[dict] = None
        #: decode-step executions since construction (a batched launch
        #: counts once however many slots it covers) — the denominator of
        #: the benchmark's ``engine.tokens_per_launch``
        self.kernel_calls = 0
        #: ``{"in_place": n, "whole": m}`` for the decode executable last
        #: built: per decode step, the model GEMMs that read a stacked
        #: weight in place at their layer and those handed a whole weight
        #: (``ops.count_gemm_reads``, counted as the step traces)
        self.decode_gemm_reads: Optional[dict] = None
        self._waiting: list[Request] = []
        self._slots: list[_Slot] = []
        self._done: dict[int, Request] = {}
        self._out: dict[int, list] = {}
        self._next_rid = 0
        self._decode_fns: dict[tuple, callable] = {}
        self._prefill_fns: dict[int, callable] = {}

    # -- public API --------------------------------------------------------

    def submit(self, prompt, max_new: int, now: float = 0.0) -> int:
        """Queue a request; returns its id."""
        prompt = tuple(int(t) for t in prompt)
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) + max_new > self.max_len:
            raise ValueError(
                f"prompt {len(prompt)} + max_new {max_new} exceeds "
                f"max_len {self.max_len}")
        rid = self._next_rid
        self._next_rid += 1
        self._waiting.append(Request(rid, prompt, int(max_new),
                                     submit_t=now))
        self._out[rid] = []
        return rid

    def step(self, now: float = 0.0, clock=None) -> list[tuple[int, int]]:
        """One engine iteration: admit, then decode every active slot —
        ONE launch over the stacked page table (paged) or the stacked
        contiguous cache bucketed by occupancy (contiguous), a per-slot
        loop with one deferred host transfer for ``batched=False``; either
        way the token vector is the one host transfer.  Returns the ``(rid,
        token)`` pairs emitted.  With ``clock`` (e.g. ``time.perf_counter``)
        a request's ``admit_t``, ``first_tok_t`` and ``done_t`` are read
        from it as they happen (a token once it is on the host); without,
        they are ``now``."""
        stamp = (lambda: now) if clock is None else clock
        with TraceAnnotation("engine.step"):
            emitted = self._admit(stamp)
            calls = self.kernel_calls
            with TraceAnnotation("engine.launch") as span:
                launched, toks = (
                    self._launch_batched() if self.batched
                    else self._launch_sequential() if self.paged
                    else self._launch_contiguous())
                span.set_metadata(n=self.kernel_calls - calls)
            if not launched:
                return emitted
            with TraceAnnotation("engine.sync"):
                toks = jax.device_get(toks)      # ONE sync per iteration
            with TraceAnnotation("engine.emit"):
                for slot, i in launched:
                    if slot not in self._slots:
                        # evicted after its launch by a later slot's
                        # allocation: drop the token — greedy decode
                        # recomputes it identically on re-admission
                        continue
                    tok = self._emit(slot, int(toks[i]), stamp)
                    self._retire_if_done(slot, stamp)
                    if tok is not None:
                        emitted.append((slot.req.rid, tok))
        return emitted

    @property
    def idle(self) -> bool:
        return not self._waiting and not self._slots

    def run(self, now: float = 0.0, clock=None) -> dict:
        """Step until idle; returns ``{rid: {"tokens", "request"}}``.
        ``clock`` (e.g. ``time.perf_counter``) refreshes ``now`` between
        iterations and stamps requests within them (``step``)."""
        while not self.idle:
            self.step(now if clock is None else clock(), clock)
        return self.results()

    def results(self) -> dict:
        return {rid: {"tokens": list(self._out[rid]), "request": req}
                for rid, req in self._done.items()}

    # -- scheduling --------------------------------------------------------

    def _admit(self, stamp: Callable[[], float]) -> list[tuple[int, int]]:
        emitted = []
        with TraceAnnotation("engine.admit"):
            while self._waiting and len(self._slots) < self.max_slots:
                req = self._waiting[0]
                try:
                    slot, tok = self._start(req, stamp)
                except OutOfPages:
                    if not self._evict(protect=None):
                        break               # nothing evictable; wait
                    continue
                self._waiting.pop(0)
                self._slots.append(slot)
                if tok is not None:
                    emitted.append((req.rid, tok))
                self._retire_if_done(slot, stamp)
        return emitted

    def _start(self, req: Request, stamp: Callable[[], float]
               ) -> tuple[_Slot, Optional[int]]:
        """Claim a fresh slot for the request's tokens-so-far (its
        stacked row and, paged, the pages its prefill fills), prefill it
        (contiguous: into its row of the stacked cache) and bring its
        first token to the host."""
        tokens = list(req.prompt) + list(self._out[req.rid])
        used = {s.row for s in self._slots}
        slot = _Slot(req=req, tokens=tokens,
                     n_emitted=len(self._out[req.rid]),
                     row=min(i for i in range(self.max_slots)
                             if i not in used))
        s0 = len(tokens)
        if self.paged:
            slot.slabs = self.pool.alloc(pages_needed(s0, self.page))
        with TraceAnnotation("engine.prefill", n=s0):
            if req.admit_t is None:
                req.admit_t = stamp()
            logits, cache = self._prefill(tokens)
            if self.paged:
                self.pool.write_prefill(cache, slot.slabs, s0)
            else:
                cache = transformer.prefill_cache_to_decode(
                    self.cfg, cache, self.max_len)
                if cache is None:
                    raise NotImplementedError(
                        f"family {self.cfg.family!r} has no forward->decode "
                        f"cache re-layout; the engine cannot serve it")
                if self._cache is None:
                    self._cache = jax.tree.map(
                        lambda t: jnp.zeros((t.shape[0], self.max_slots)
                                            + t.shape[2:], t.dtype), cache)
                self._cache = engine_write_rows(self._cache, cache,
                                                slot.row)
            tok = self._emit(slot, int(jnp.argmax(logits[0])), stamp)
        return slot, tok

    def _launch_batched(self) -> tuple[list, Optional[jax.Array]]:
        """Decode every active paged slot in ONE derived-kernel launch:
        ``([(slot, row of the token vector)], device token vector)``.

        Page allocation for all slots happens first (it may evict — a
        victim simply drops out of this iteration's batch, exactly as it
        dropped out of the old per-slot loop).  The stacked table is then
        rebuilt as a PURE function of live state: each live slot's slabs
        fill its pinned row, zero-padded to the widest live slot; dead
        rows are all zeros (POS -1 makes them inert and their writes
        drop, so the entries never matter).  Canonical rows mean the
        executor key — and hence the jitted executable — recurs whenever
        the engine revisits the same occupancy, including across whole
        replays of an identical trace.  Greedy argmax runs on device
        inside the jitted step; the (max_slots,) token vector is the one
        host transfer."""
        live = []
        for slot in list(self._slots):
            if slot not in self._slots:   # evicted by an earlier ensure
                continue
            try:
                self._ensure_pages(slot, len(slot.tokens))
            except OutOfPages:
                continue                  # pool saturated; retry next step
            live.append(slot)
        live = [s for s in live if s in self._slots]
        if not live:
            return [], None
        by_row = {s.row: s for s in live}
        # trim the view to the widest LIVE slot: shorter tables mean
        # fewer streamed grid steps per launch.  Width growth re-keys
        # the executor exactly as per-slot page allocation does
        width = max(len(s.slabs) for s in live)
        toks, poss, rows = [], [], []
        for i in range(self.max_slots):
            slot = by_row.get(i)
            if slot is not None:
                slabs = tuple(slot.slabs)
                rows.append(slabs + (0,) * (width - len(slabs)))
                toks.append(slot.tokens[-1])
                poss.append(len(slot.tokens) - 1)
            else:
                rows.append((0,) * width)
                toks.append(0)
                poss.append(-1)
        fn = self._batched_decode_fn(tuple(rows))
        next_toks, pools = fn(jnp.asarray(toks, jnp.int32),
                              jnp.asarray(poss, jnp.int32),
                              self.pool.pools)
        self.pool.update(pools)
        self.kernel_calls += 1
        return [(s, s.row) for s in live], next_toks

    def _launch_contiguous(self) -> tuple[list, Optional[jax.Array]]:
        """Decode every live contiguous slot in ONE launch over rows
        ``[0, kb)`` of the stacked cache: ``([(slot, its row)], device
        token vector)``.  ``kb`` is the smallest power of two past the
        highest live row, capped at ``max_slots`` (one executable per
        bucket).  At ``kb == max_slots`` the launch's cache output IS the
        new stacked cache; a smaller bucket's output goes back through
        the donated row write at offset 0."""
        if not self._slots:
            return [], None
        kb = min(self.max_slots,
                 1 << max(s.row for s in self._slots).bit_length())
        toks, poss = [0] * kb, [0] * kb     # dead rows: token 0 at 0
        for slot in self._slots:
            toks[slot.row] = slot.tokens[-1]
            poss[slot.row] = len(slot.tokens) - 1
        full = kb == self.max_slots
        cache = self._cache if full else engine_read_rows(self._cache, kb)
        next_toks, cache = self._contig_decode_fn()(
            jnp.asarray(toks, jnp.int32), jnp.asarray(poss, jnp.int32),
            cache)
        self._cache = (cache if full else
                       engine_write_rows(self._cache, cache, 0))
        self.kernel_calls += 1
        return [(s, s.row) for s in self._slots], next_toks

    def _launch_sequential(self) -> tuple[list, Optional[jax.Array]]:
        """The per-slot paged path (``batched=False``): one decode launch
        per slot, ``([(slot, index)], stacked device argmax)``.  Sampling
        stays on device and the token vector transfers ONCE after every
        slot has launched — JAX's async dispatch overlaps the launches,
        and no slot blocks the host per token."""
        pending = []
        for slot in list(self._slots):
            if slot not in self._slots:   # evicted by an earlier ensure
                continue
            pos = len(slot.tokens) - 1    # feed the newest token here
            try:
                self._ensure_pages(slot, pos + 1)
            except OutOfPages:
                continue                  # pool saturated; retry next step
            fn = self._paged_decode_fn(tuple(slot.slabs))
            logits, pools = fn(
                jnp.asarray([slot.tokens[-1]], jnp.int32),
                jnp.asarray([pos], jnp.int32), self.pool.pools)
            self.pool.update(pools)
            self.kernel_calls += 1
            pending.append((slot, jnp.argmax(logits[0])))
        if not pending:
            return [], None
        return ([(slot, i) for i, (slot, _) in enumerate(pending)],
                jnp.stack([t for _, t in pending]))

    def _emit(self, slot: _Slot, tok: int,
              stamp: Callable[[], float]) -> Optional[int]:
        if slot.req.first_tok_t is None:
            slot.req.first_tok_t = stamp()
        slot.tokens.append(tok)
        slot.n_emitted += 1
        self._out[slot.req.rid].append(tok)
        return tok

    def _retire_if_done(self, slot: _Slot,
                        stamp: Callable[[], float]) -> None:
        done = (slot.n_emitted >= slot.req.max_new or
                (self.eos_id is not None and
                 slot.tokens[-1] == self.eos_id) or
                len(slot.tokens) >= self.max_len)
        if done and slot in self._slots:
            slot.req.done_t = stamp()
            if self.paged:
                self.pool.free(slot.slabs)
            self._slots.remove(slot)
            self._done[slot.req.rid] = slot.req

    def _ensure_pages(self, slot: _Slot, tokens_needed: int) -> None:
        """Grow the slot's page table to cover ``tokens_needed`` rows,
        evicting other slots under pressure."""
        while len(slot.slabs) < pages_needed(tokens_needed, self.page):
            try:
                slot.slabs.extend(self.pool.alloc(1))
            except OutOfPages:
                if not self._evict(protect=slot):
                    raise

    def _evict(self, protect: Optional[_Slot]) -> bool:
        """Preempt the youngest running paged slot (recompute on
        re-admission).  Returns False when nothing is evictable."""
        victims = [s for s in self._slots if s is not protect and s.slabs]
        if not victims:
            return False
        victim = victims[-1]              # youngest admitted
        self.pool.free(victim.slabs)
        victim.slabs = []
        self._slots.remove(victim)
        victim.req.evictions += 1
        self._waiting.insert(0, victim.req)
        return True

    # -- executables (cached on static keys only) --------------------------
    # Every executable takes ``params`` as its first jit argument: a closed-
    # over array would be baked into the program as a constant, once per
    # prompt length and page table.

    def _prefill(self, tokens: list):
        fn = self._prefill_fns.get(len(tokens))
        if fn is None:
            def engine_prefill(params, t):
                return registry.prefill(params, self.cfg, {"tokens": t})
            fn = jax.jit(engine_prefill)
            self._prefill_fns[len(tokens)] = fn
        return fn(self.params, jnp.asarray([tokens], jnp.int32))

    def _decode_traced(self, step, *args, **kwargs):
        """Run a decode step's body as its executable traces, recording
        ``decode_gemm_reads``."""
        with ops.count_gemm_reads() as reads:
            out = step(*args, **kwargs)
        self.decode_gemm_reads = reads
        return out

    def _paged_decode_fn(self, table: tuple):
        """The jitted paged decode step for one page table — THE derived
        ``windowed_decode`` kernel reading through the table's psi view."""
        fn = self._decode_fns.get(table)
        if fn is None:
            def engine_decode_paged(params, toks, poss, pools, _table=table):
                return self._decode_traced(
                    transformer.decode_step_paged, params, self.cfg, toks,
                    poss, pools, page_table=_table, page=self.page,
                    interpret=self.interpret)
            fn = functools.partial(jax.jit(engine_decode_paged), self.params)
            self._decode_fns[table] = fn
        return fn

    def _batched_decode_fn(self, tables: tuple):
        """The jitted batched decode step for one STACKED page table —
        the derived ``batched_decode`` kernel covering every slot in one
        launch, with greedy argmax folded in so sampling happens on
        device and only the (max_slots,) token vector crosses to host."""
        fn = self._decode_fns.get(tables)
        if fn is None:
            def engine_decode_batched(params, toks, poss, pools,
                                      _tables=tables):
                logits, pools = self._decode_traced(
                    transformer.decode_step_paged_batched, params, self.cfg,
                    toks, poss, pools, page_tables=_tables, page=self.page,
                    interpret=self.interpret)
                return jnp.argmax(logits, axis=-1), pools
            fn = functools.partial(jax.jit(engine_decode_batched),
                                   self.params)
            self._decode_fns[tables] = fn
        return fn

    def _contig_decode_fn(self):
        """The jitted contiguous decode step over a stacked cache,
        ``fn(toks, poss, cache) -> (greedy tokens, new cache)``; one
        executable per occupancy bucket (the rows of ``toks``).  The cache
        is not donated, so the input stays a live array after the call:
        a wrapper may hand it back as the state."""
        fn = self._decode_fns.get(())
        if fn is None:
            def engine_decode(params, toks, poss, cache):
                logits, cache = self._decode_traced(
                    registry.decode_step, params, self.cfg, toks, poss, cache)
                return jnp.argmax(logits, axis=-1), cache
            fn = functools.partial(jax.jit(engine_decode), self.params)
            self._decode_fns[()] = fn
        return fn
