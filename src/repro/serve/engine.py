"""Batched serving engine with continuous batching over KV-cache slots.

One fixed-size decode batch (``num_slots`` rows) steps every iteration;
requests are attached to free slots with their own position counters
(the per-slot ``pos`` vector the model's decode path supports), so new
requests join mid-flight without draining the batch — continuous batching.

Three cache/attention modes, all greedy-token-identical (differentially
tested in tests/test_serving_decode.py):

  * dense (``paged=False``)          — the retained XLA reference: one
    ``(B, max_len)`` cache, masked slots kept by a where-merge;
  * paged + ``attn_impl="xla"``      — pages gathered through the table,
    attention still XLA (the paged reference oracle);
  * paged + ``attn_impl="flash"``    — the Pallas grouped decode kernel
    gathers K/V page-by-page through the table (no (B, S) gather ever
    materialises).

Paged mode replaces the per-slot where-merge with *trash-page write
diversion* (masked slots scatter into reserved physical page 0, see
serve/kv_pages.py), so the pool buffers are donated through the step —
no copy of the cache per tick.  Page-id → memory layout follows the
registry's Hilbert map over (slot, page): co-scheduled slots' pages
cluster, so the per-step gather stream decomposes into few long runs
(the paper's locality claim applied to serving; measured by
``PagedKVCache.gather_runs`` in benchmarks/bench_serving.py).

Prefill has two modes (``prefill=``).  ``"chunked"`` advances
``prefill_chunk`` prompt tokens in ONE dispatch (a lax.scan of masked
single-token decode steps — exact, and ``chunk``× fewer dispatches than
the old token-by-token loop).  ``"compiled"`` (paged only, PR 10) runs
the whole cohort's prompts through ONE batched forward per admission:
every layer scatters all new K/V through the page table, then attends
all new tokens causally over their prefixes — O(prompt) total flops per
slot instead of the chunked walk's O(prompt²), and a handful of
dispatches instead of ``prompt/chunk``.

``prefix_sharing=True`` (paged only) turns admission into a prefix-trie
walk over :class:`~repro.serve.kv_pages.PagedKVCache`: whole pages
whose token chain matches an earlier prompt are mapped refcount++ with
zero copies, prefill resumes at the first unmatched token, and the
first divergent write to a still-shared page triggers a copy-on-write
(one batched device page copy per dispatch, placed by the Hilbert
layout).  Eviction decrements refcounts; pages free only at zero.
Both features compose with either prefill mode and stay
greedy-token-identical to the dense reference.

Since PR 8 the request-side machinery — typed queue, capacity-limited
admission, cohort ordering, the per-tick stats ring — is the generic
tick core (:mod:`repro.serve.tick`), shared with the streaming
data-mining services (:mod:`repro.serve.apps`).  The engine registers
one command kind (``"generate"``, capacity = free slots, optional
Hilbert admission ordering) and one step callback (the masked decode
dispatch); ``step()`` is one tick.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import (
    ModelConfig,
    decode_step,
    decode_step_paged,
    init_cache,
    init_paged_cache,
    prefill_paged,
)
from .kv_pages import PagedKVCache
from .tick import TickCore

# All step functions are module-level jits (cfg static/hashable) so every
# engine over the same config shares ONE compiled executable.  Per-engine
# closures re-jitted per instance, and two XLA compilations of the same
# jaxpr are not guaranteed instruction-schedule-identical — their logits
# could differ in the last ulp, which is exactly the cross-program argmax
# flip the serving differential tests kept tripping over (and a waste of
# compile time in production).


@functools.partial(jax.jit, static_argnames=("cfg",))
def _masked_step(params, toks, cache, pos, mask, *, cfg):
    """Decode one token; slots with mask=False keep their cache untouched
    (recurrent SSM states must not see filler tokens)."""
    logits, new_c = decode_step(params, toks, cache, pos, cfg)

    def merge(old, new):
        m = mask.reshape((1, -1) + (1,) * (old.ndim - 2))
        return jnp.where(m, new, old)

    return logits, jax.tree.map(merge, cache, new_c)


@functools.partial(
    jax.jit, static_argnames=("cfg", "attn_impl"), donate_argnums=(2,)
)
def _masked_step_paged(params, toks, cache, pos, mask, page_table, *, cfg, attn_impl):
    """Paged twin of :func:`_masked_step`.  No where-merge: masked slots'
    cache writes are diverted to the trash page inside the scatter, so
    the pool buffers are donated — the step never copies the cache."""
    return decode_step_paged(
        params, toks, cache, pos, page_table, cfg,
        write_mask=mask, attn_impl=attn_impl,
    )


@functools.partial(jax.jit, static_argnames=("cfg",))
def _masked_chunk_step(params, toks, mask, cache, pos, *, cfg):
    """Chunked prefill: advance each slot by its masked tokens in ONE
    dispatch.  toks/mask: (B, C); a lax.scan of C masked single-token
    decode steps (exact — same math as the token-by-token loop).
    Returns (cache, pos)."""

    def body(carry, inp):
        cache, pos = carry
        t, m = inp
        _, new_c = decode_step(params, t[:, None], cache, pos, cfg)

        def merge(old, new):
            mm = m.reshape((1, -1) + (1,) * (old.ndim - 2))
            return jnp.where(mm, new, old)

        cache = jax.tree.map(merge, cache, new_c)
        return (cache, pos + m.astype(jnp.int32)), None

    (cache, pos), _ = jax.lax.scan(body, (cache, pos), (toks.T, mask.T))
    return cache, pos


@functools.partial(
    jax.jit, static_argnames=("cfg", "attn_impl"), donate_argnums=(3,)
)
def _masked_chunk_step_paged(params, toks, mask, cache, pos, page_table, *,
                             cfg, attn_impl):
    """Chunked prefill against the paged cache (trash-diverted writes in
    place of the merge).  Returns (cache, pos)."""

    def body(carry, inp):
        cache, pos = carry
        t, m = inp
        _, cache = decode_step_paged(
            params, t[:, None], cache, pos, page_table, cfg,
            write_mask=m, attn_impl=attn_impl,
        )
        return (cache, pos + m.astype(jnp.int32)), None

    (cache, pos), _ = jax.lax.scan(body, (cache, pos), (toks.T, mask.T))
    return cache, pos


@functools.partial(
    jax.jit, static_argnames=("cfg", "attn_impl"), donate_argnums=(2,)
)
def _compiled_prefill_paged(params, toks, cache, pos0, n_new, page_table,
                            schedule, *, cfg, attn_impl):
    """Compiled-forward prefill: the whole cohort's new prompt tokens in
    one batched dispatch per admission.  Donates the pools like the
    decode steps (pad and inactive lanes trash-divert their writes)."""
    return prefill_paged(
        params, toks, cache, pos0, n_new, page_table, cfg,
        attn_impl=attn_impl, schedule=schedule,
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_pages(cache, src, dst):
    """Batched copy-on-write page copy: physical page src[i] → dst[i]
    across every layer's pool leaf ((L, P, ...) arrays).  The pair list
    is padded with (0, 0) — trash-page self-copies are harmless — so a
    few pow2 pair-count buckets serve every COW batch."""
    return jax.tree.map(lambda x: x.at[:, dst].set(x[:, src]), cache)


@functools.partial(jax.jit, donate_argnums=(0,))
def _zero_slot(cache, slot):
    """Zero ONE slot's rows across the cache pytree (slot is a traced
    scalar — one executable serves every slot).  With donation this is
    an in-place O(slot-row) scatter, not an O(cache) rebuild."""
    return jax.tree.map(lambda x: x.at[:, slot].set(jnp.zeros_like(x[:1, 0])), cache)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new: int
    out: list[int] = dataclasses.field(default_factory=list)
    done: bool = False


class ServeEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        *,
        num_slots: int = 4,
        max_len: int = 256,
        temperature: float = 0.0,
        seed: int = 0,
        paged: bool = False,
        attn_impl: str = "flash",
        page_size: int = 16,
        num_pages: int | None = None,
        page_layout: str = "hilbert",
        prefill_chunk: int = 8,
        prefill: str = "chunked",
        prefix_sharing: bool | str = False,
        hilbert_admission: bool = False,
        admitted_log: int = 4096,
        stats_capacity: int = 256,
    ):
        assert not cfg.encoder_only, "encoder-only archs have no decode path"
        if attn_impl not in ("flash", "xla"):
            raise ValueError(f"attn_impl {attn_impl!r}; one of ('flash', 'xla')")
        if paged and (cfg.block_kind == "mamba2" or cfg.hybrid_attn_every):
            raise ValueError(
                "paged serving requires a pure attention stack "
                "(recurrent blocks carry O(1) state — nothing to page)"
            )
        if prefill not in ("chunked", "compiled"):
            raise ValueError(
                f"prefill {prefill!r}; one of ('chunked', 'compiled')"
            )
        if prefill == "compiled" and not paged:
            raise ValueError(
                "compiled prefill writes K/V through the page table — "
                "requires paged=True"
            )
        if isinstance(prefix_sharing, str):
            if prefix_sharing not in ("off", "on"):
                raise ValueError(
                    f"prefix_sharing {prefix_sharing!r}; one of ('off', 'on')"
                )
            prefix_sharing = prefix_sharing == "on"
        if prefix_sharing and not paged:
            raise ValueError("prefix sharing maps pages — requires paged=True")
        self.prefill_mode = prefill
        self.prefix_sharing = bool(prefix_sharing)
        self.cfg = cfg
        self.params = params
        self.num_slots = num_slots
        self.max_len = max_len
        self.temperature = temperature
        self.paged = paged
        self.attn_impl = attn_impl
        self.prefill_chunk = max(1, prefill_chunk)
        self.hilbert_admission = hilbert_admission
        if paged:
            self.page_size = page_size
            self.max_pages = -(-max_len // page_size)
            self.kv_pages = PagedKVCache(
                num_slots, self.max_pages, page_size,
                num_pages=num_pages, layout=page_layout,
            )
            self.cache = init_paged_cache(cfg, self.kv_pages.num_pages, page_size)
        else:
            self.kv_pages = None
            self.cache = init_cache(cfg, num_slots, max_len)
        self.pos = np.zeros((num_slots,), dtype=np.int32)
        self.slot_req: list[Request | None] = [None] * num_slots
        self.next_token = np.zeros((num_slots,), dtype=np.int32)
        self.active = np.zeros((num_slots,), dtype=bool)
        self.key = jax.random.PRNGKey(seed)
        self.last_logits: np.ndarray | None = None
        self._rid = 0
        if admitted_log < 1:
            raise ValueError(f"admitted_log must be >= 1, got {admitted_log}")
        self._admitted_log = admitted_log
        self.admitted: list[int] = []  # rids in admission order (bounded)
        # the request-side machinery is the shared tick core: one command
        # kind admitted up to the free-slot count per tick, with the
        # Hilbert cohort ordering as the kind's coalescer hook, and the
        # decode dispatch as the per-tick step
        self._core = TickCore(stats_capacity=stats_capacity)
        self._core.register_kind(
            "generate",
            self._admit,
            capacity=lambda: int(self.num_slots - np.count_nonzero(self.active)),
            order=self._admission_order if hilbert_admission else None,
        )
        self._core.register_step(self._decode_tick)

    @property
    def _queue(self):
        """The live generate queue (the tick core's deque) — kept under
        the pre-tick-core name because the benchmarks and tests poll its
        truthiness."""
        return self._core.queue("generate")

    @property
    def stats(self):
        """Per-tick stats ring (tick wall time drives the p99 rows)."""
        return self._core.stats

    # ------------------------------------------------------------------
    def submit(self, prompt: list[int], max_new: int = 16) -> Request:
        prompt = list(prompt)
        if not prompt:
            raise ValueError("empty prompt: a request needs >= 1 prompt token")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        req = Request(rid=self._rid, prompt=prompt, max_new=max_new)
        self._rid += 1
        self._core.submit("generate", req)
        return req

    def _admission_order(self, cohort: list) -> list:
        """Hilbert token batching (opt-in): order the admitted cohort by
        the curve rank of each prompt's token signature, so requests with
        similar prefixes land in adjacent slots — and, with the curve
        page layout, in adjacent pages."""
        from repro.data.pipeline import hilbert_token_order

        reqs = [t.payload for t in cohort]
        width = max(len(r.prompt) for r in reqs)
        toks = np.zeros((len(reqs), width), dtype=np.int32)
        for i, r in enumerate(reqs):
            toks[i, : len(r.prompt)] = r.prompt
        perm = hilbert_token_order(toks)
        return [cohort[i] for i in perm]

    def _attach(self) -> None:
        """Run one admission pass (queue → cohort → slots → prefill)
        without a decode step — the tick core's admission phase only.
        Tests and warm-up paths use this to separate admission from
        decode."""
        self._core.admit("generate")

    def _admit(self, cohort: list) -> None:
        """Admission handler: attach the tick's cohort to free slots and
        chunk-prefill them (capacity() guarantees enough free slots)."""
        free = [s for s in range(self.num_slots) if not self.active[s]]
        new_slots: list[int] = []
        for slot, ticket in zip(free, cohort):
            req = ticket.payload
            self.slot_req[slot] = req
            self.active[slot] = True
            self.pos[slot] = 0
            self.admitted.append(req.rid)
            ticket.done = True
            ticket.result = slot
            if self.paged:
                if self.prefix_sharing:
                    # map trie-matched pages (refcount++, zero copy) and
                    # resume prefill at the first unmatched token
                    self.pos[slot] = self.kv_pages.share_prefix(
                        slot, req.prompt[:-1]
                    )
                # stale page contents are unreachable (positional mask +
                # write-before-attend), so admission allocates, never zeroes
                self.kv_pages.ensure_pos(slot, max(len(req.prompt) - 1, 0))
            else:
                self.cache = _zero_slot(self.cache, np.int32(slot))
            new_slots.append(slot)
        if len(self.admitted) > self._admitted_log:
            # bounded admission log: keep only the most recent rids, so a
            # long-running engine's memory stays O(admitted_log)
            del self.admitted[: len(self.admitted) - self._admitted_log]
        self._prefill(new_slots)

    def _prepare_cow(self, ranges: list[tuple[int, int, int]]) -> None:
        """Copy-on-write barrier before a dispatch that writes positions
        ``[lo, hi)`` per slot: remap still-shared pages in range to
        fresh physical pages and run ONE batched device copy for the
        (src, dst) pairs."""
        pairs: list[tuple[int, int]] = []
        for slot, lo, hi in ranges:
            pairs.extend(self.kv_pages.prepare_write(slot, lo, hi))
        if not pairs:
            return
        n = 1 << max(len(pairs) - 1, 0).bit_length()
        src = np.zeros((n,), dtype=np.int32)
        dst = np.zeros((n,), dtype=np.int32)
        src[: len(pairs)] = [p[0] for p in pairs]
        dst[: len(pairs)] = [p[1] for p in pairs]
        self.cache = _copy_pages(self.cache, jnp.asarray(src), jnp.asarray(dst))

    def _prefill(self, slots: list[int]) -> None:
        """Prefill freshly admitted slots via the configured mode, then
        publish their full pages into the prefix trie (registration is
        post-prefill, so sharing is strictly cross-cohort — a dispatch
        never attends pages it is also writing for another slot)."""
        if self.prefill_mode == "compiled":
            self._prefill_compiled(slots)
        else:
            self._prefill_chunked(slots)
        if self.paged and self.prefix_sharing:
            for s in slots:
                self.kv_pages.register_prefix(s, self.slot_req[s].prompt[:-1])
        for s in slots:
            self.next_token[s] = self.slot_req[s].prompt[-1]

    def _prefill_compiled(self, slots: list[int]) -> None:
        """One batched compiled-forward dispatch admits the cohort: all
        new prompt tokens of all new slots, positions
        ``pos0[s]..pos0[s]+n_new[s]-1``, written through the page table
        (inactive and pad lanes trash-diverted, so old active slots
        ride along untouched).  Token width is bucketed to pow2 pages so
        same-bucket cohorts share one executable."""
        new = {s: self.slot_req[s].prompt[int(self.pos[s]) : -1] for s in slots}
        n_max = max((len(v) for v in new.values()), default=0)
        if self.prefix_sharing:
            self._prepare_cow(
                [(s, int(self.pos[s]), int(self.pos[s]) + len(new[s]))
                 for s in slots]
            )
        if n_max == 0:
            return  # fully shared (or single-token) prompts: nothing new
        ps = self.page_size
        T = ps * (1 << max(-(-n_max // ps) - 1, 0).bit_length())
        toks = np.zeros((self.num_slots, T), dtype=np.int32)
        n_new = np.zeros((self.num_slots,), dtype=np.int32)
        for s in slots:
            toks[s, : len(new[s])] = new[s]
            n_new[s] = len(new[s])
        pos0 = self.pos.copy()
        schedule = None
        if self.attn_impl == "flash":
            from repro.kernels.attention import prefill_page_schedule_device

            schedule = prefill_page_schedule_device(
                tuple(int(p) for p in pos0), tuple(int(n) for n in n_new),
                ps, self.max_pages,
            )
        self.cache = _compiled_prefill_paged(
            self.params, jnp.asarray(toks), self.cache, jnp.asarray(pos0),
            jnp.asarray(n_new), self.kv_pages.device_table(), schedule,
            cfg=self.cfg, attn_impl=self.attn_impl,
        )
        for s in slots:
            self.pos[s] = int(pos0[s]) + len(new[s])

    def _prefill_chunked(self, slots: list[int]) -> None:
        """Chunked prefill for freshly admitted slots: prefill_chunk
        prompt tokens per dispatch, batched ACROSS the new slots (old
        active slots ride along masked — their cache and pos are
        untouched).  With prefix sharing the walk resumes at each slot's
        matched-token position."""
        remaining = {
            s: list(self.slot_req[s].prompt[int(self.pos[s]) : -1])
            for s in slots
        }
        if self.paged and self.prefix_sharing:
            self._prepare_cow(
                [(s, int(self.pos[s]), int(self.pos[s]) + len(remaining[s]))
                 for s in slots]
            )
        C = self.prefill_chunk
        while any(remaining.values()):
            toks = np.zeros((self.num_slots, C), dtype=np.int32)
            mask = np.zeros((self.num_slots, C), dtype=bool)
            for s in slots:
                take = remaining[s][:C]
                remaining[s] = remaining[s][C:]
                toks[s, : len(take)] = take
                mask[s, : len(take)] = True
            if self.paged:
                self.cache, pos = _masked_chunk_step_paged(
                    self.params, jnp.asarray(toks), jnp.asarray(mask),
                    self.cache, jnp.asarray(self.pos),
                    self.kv_pages.device_table(),
                    cfg=self.cfg, attn_impl=self.attn_impl,
                )
            else:
                self.cache, pos = _masked_chunk_step(
                    self.params, jnp.asarray(toks), jnp.asarray(mask),
                    self.cache, jnp.asarray(self.pos), cfg=self.cfg,
                )
            self.pos = np.array(pos)  # copy: np.asarray of a jax array is read-only

    # ------------------------------------------------------------------
    def step(self) -> None:
        """One engine tick: admission (via the tick core's generate
        cohort) followed by one decode iteration across active slots."""
        self._core.tick()

    def _decode_tick(self) -> None:
        """The tick core's step callback: one masked decode dispatch."""
        if not self.active.any():
            return
        toks = self.next_token[:, None].astype(np.int32)
        if self.paged:
            for slot in range(self.num_slots):
                if self.active[slot]:
                    self.kv_pages.ensure_pos(slot, int(self.pos[slot]))
            if self.prefix_sharing:
                # first divergent write into a still-shared page (e.g. a
                # fully-matched prompt's first generated token) COWs it
                self._prepare_cow(
                    [(s, int(self.pos[s]), int(self.pos[s]) + 1)
                     for s in range(self.num_slots) if self.active[s]]
                )
            logits, self.cache = _masked_step_paged(
                self.params, jnp.asarray(toks), self.cache,
                jnp.asarray(self.pos), jnp.asarray(self.active),
                self.kv_pages.device_table(),
                cfg=self.cfg, attn_impl=self.attn_impl,
            )
        else:
            logits, self.cache = _masked_step(
                self.params, jnp.asarray(toks), self.cache,
                jnp.asarray(self.pos), jnp.asarray(self.active), cfg=self.cfg,
            )
        logits = np.asarray(logits)
        self.last_logits = logits  # (B, V) of the latest decode tick
        if self.temperature > 0:
            self.key, sub = jax.random.split(self.key)
            sampled = np.asarray(
                jax.random.categorical(sub, jnp.asarray(logits) / self.temperature)
            )
        else:
            sampled = logits.argmax(axis=-1)
        for slot in range(self.num_slots):
            if not self.active[slot]:
                continue
            self.pos[slot] += 1
            req = self.slot_req[slot]
            req.out.append(int(sampled[slot]))
            self.next_token[slot] = sampled[slot]
            if len(req.out) >= req.max_new or self.pos[slot] >= self.max_len - 1:
                req.done = True
                self.active[slot] = False
                self.slot_req[slot] = None
                if self.paged:
                    self.kv_pages.free_slot(slot)

    def run_until_done(self, max_iters: int = 10_000) -> None:
        self._core.run_until_idle(
            busy=lambda: bool(self.active.any()), max_ticks=max_iters
        )
