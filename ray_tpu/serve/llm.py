"""Continuous-batching LLM engine + serve deployment.

The vLLM-capability analog for TPU (BASELINE.md config 4: continuous-batched
llama serving; SURVEY.md §7.9). The reference has no native LLM engine — its
serve layer delegates to user code. TPU-first design constraints drive the
shape of this engine (SURVEY.md §7 hard parts: "static-shape XLA vs dynamic
batch composition; bucketed compilation"):

- a fixed pool of decode SLOTS: the decode step is one jitted program of
  static shape [max_slots] regardless of how many requests are active
  (inactive rows are masked) — no recompilation as requests come and go.
- bucketed prefill: prompts are right-padded to a power-of-two bucket, so
  XLA compiles one prefill program per bucket size; per-row true lengths
  keep attention exact (pad slots are never attended).
- admission: new requests prefill into free slots between decode steps —
  continuous batching, not static batches.
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from ray_tpu.core import compile_cache as _compile_cache
from ray_tpu.observability import health as _health
from ray_tpu.util import tracing as _tracing

logger = logging.getLogger("ray_tpu.serve.llm")


class LLMQueueFull(Exception):
    """Raised by submit() when the engine's admission queue is at
    max_queue_depth — the serve layer maps it to HTTP 429 so load sheds
    at the proxy instead of building unbounded queue-wait (VERDICT r2
    weak #3: 'no backpressure/429 path')."""


@dataclass
class _Request:
    req_id: int
    prompt: List[int]
    max_new_tokens: int
    temperature: float = 0.0
    slot: int = -1
    generated: List[int] = field(default_factory=list)
    #: tokens present in BOTH prompt and generated after a recompute-
    #: preemption folded generated tokens into the resume prompt; real
    #: sequence length = len(prompt) + len(generated) - overlap
    overlap: int = 0
    error: Optional[str] = None
    #: status the serve layer answers with when `error` is set: 400 = the
    #: request could not be served as asked, 500 = the engine failed
    error_status: int = 400
    done_event: threading.Event = field(default_factory=threading.Event)
    # pulsed whenever generated grows (token-streaming consumers wait on it)
    progress: threading.Event = field(default_factory=threading.Event)
    submit_time: float = field(default_factory=time.time)
    #: when it (re)joined the queue, if later than submit_time: a
    #: preempted request's second wait is not counted from its submission
    queued_time: Optional[float] = None
    first_token_time: Optional[float] = None


class LLMEngine:
    """Synchronous engine core; drive with step(). Thread-safe submit."""

    def __init__(self, cfg=None, params=None, *, preset: str = "tiny",
                 max_slots: int = 8, max_seq_len: Optional[int] = None,
                 eos_token: int = -1, seed: int = 0, mesh=None, rules=None,
                 kv_layout: str = "contiguous", page_size: int = 64,
                 num_pages: Optional[int] = None,
                 max_queue_depth: Optional[int] = None,
                 prefix_caching: bool = True,
                 prefix_cache_max_tail: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 quantize: Optional[str] = None):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import cached, llama

        self._jax = jax
        self._jnp = jnp
        self._llama = llama
        _compile_cache.listen()   # `compiles` in stats(), xla.compile
        if cfg is None:
            cfg = llama.PRESETS[preset]
            if jax.default_backend() != "tpu":
                cfg = cfg.replace(dtype=jnp.float32)
        self.max_seq = max_seq_len or cfg.max_seq_len
        if self.max_seq > cfg.max_seq_len:
            # decode paths size their RoPE tables from cfg.max_seq_len;
            # serving past it would CLAMP the position index (jax OOB
            # gather) — position>=cfg.max_seq_len tokens would all get
            # the last row's rotation, silently diverging from prefill
            # (whose tables are sized to the actual prompt). RoPE is
            # computed, not learned, so extending the cfg is exact.
            cfg = cfg.replace(max_seq_len=self.max_seq)
        self.cfg = cfg
        self.max_slots = max_slots
        self.eos = eos_token
        self.max_queue_depth = max_queue_depth
        if quantize is not None and quantize != "int8":
            raise ValueError(f"quantize must be 'int8', got {quantize!r}")
        quantized = False
        if params is None:
            # Serving holds no optimizer/master weights: init straight in
            # the compute dtype (bf16 on TPU). f32 masters would DOUBLE
            # weight HBM — at 2.7B that alone is 10.8 of the chip's
            # 16 GB and the engine OOMs before its first admit.
            icfg = cfg.replace(param_dtype=cfg.dtype)
            cpu_dev = None
            if quantize == "int8" and mesh is None \
                    and jax.default_backend() != "cpu":
                try:
                    cpu_dev = jax.devices("cpu")[0]
                except RuntimeError:
                    cpu_dev = None   # no host backend: quantize on-chip
            if cpu_dev is not None:
                # init + quantize on HOST, ship only the int8 tree: doing
                # both on-chip transiently holds bf16 AND int8 copies
                # (7B: ~20 GB peak — past the chip) before the bf16 side
                # is freed
                with jax.default_device(cpu_dev):
                    params = llama.init_params(jax.random.PRNGKey(seed),
                                               icfg)
                    params = llama.quantize_params_int8(params)
                params = jax.device_put(params, jax.devices()[0])
                quantized = True
            else:
                params = llama.init_params(jax.random.PRNGKey(seed), icfg)
        if mesh is not None and rules is not None:
            from ray_tpu.parallel.sharding import shard_params

            params = shard_params(mesh, params, llama.param_specs(cfg), rules)
        if quantize == "int8" and not quantized:
            # weight-only int8: HBM at rest halves vs bf16 (7B: ~6.8 GB);
            # weights dequantize inside the consuming dots. Idempotent:
            # already-quantized caller trees pass through unchanged.
            # (After sharding: the quantized tree's {"q8","s8"} leaves no
            # longer match param_specs.)
            params = llama.quantize_params_int8(params)
        self.quantize = quantize
        self.params = params
        if kv_layout not in ("contiguous", "paged"):
            raise ValueError(f"kv_layout must be 'contiguous' or 'paged', "
                             f"got {kv_layout!r}")
        if kv_layout == "paged" and cfg.sliding_window is not None:
            # fail HERE, not inside the server's background decode thread
            # (where the ValueError would kill the loop and hang clients)
            raise ValueError(
                "kv_layout='paged' does not support sliding_window "
                "configs; use the contiguous layout for windowed models")
        self.kv_layout = kv_layout
        if kv_layout == "paged":
            from ray_tpu.serve.paged_kv import PagePool

            maxP = -(-self.max_seq // page_size)
            # default pool = the HBM a contiguous cache would commit
            # (+ trash page); the paged win is packing MORE slots into it
            num_pages = num_pages or max_slots * maxP + 1
            self.kp, self.vp = cached.init_paged_cache(cfg, num_pages,
                                                      page_size)

            def _nb(x):
                try:
                    return int(x.nbytes)
                except Exception:
                    try:
                        return sum(int(a.nbytes) for a in x)
                    except Exception:
                        return 0

            # per-page device bytes (K+V across layers) so the pool can
            # report occupied-page bytes to the memory plane
            page_nbytes = (_nb(self.kp) + _nb(self.vp)) // num_pages
            self.pool = PagePool(num_pages, page_size, max_slots, maxP,
                                 page_nbytes=page_nbytes)
            # automatic prefix caching (ref: vLLM APC): share full
            # prompt pages by content hash; a hit skips that prefix's
            # prefill compute AND its page memory, and ONE chunked
            # tail-prefill call (O(T x total) attention against the
            # cached pages) finishes admission. The tail cap bounds
            # that call's cost; a mostly-unmatched prompt takes the
            # plain batched prefill instead.
            self.prefix_caching = bool(prefix_caching)
            self.prefix_cache_max_tail = (
                prefix_cache_max_tail if prefix_cache_max_tail is not None
                else 4 * page_size)
            self._len_host = np.zeros((max_slots,), np.int64)
            self._pt_dev = jnp.asarray(self.pool.table)
            self._len_dev = jnp.zeros((max_slots,), jnp.int32)
            self._table_dirty = False
            self.cache = None
        else:
            self.cache = cached.init_cache(cfg, max_slots,
                                          max_seq=self.max_seq)
        self.slots: List[Optional[_Request]] = [None] * max_slots
        self.lock = threading.Lock()
        self.pending: List[_Request] = []
        # requests that own a slot but are still mid-prefill: each _admit
        # round advances them by one bounded chunk (ref: vLLM chunked
        # prefill — prefill work is scheduled in chunks between decode
        # steps instead of monopolizing a round). They are masked OUT of
        # decode until their tail completes.
        self._prefilling: List[_Request] = []
        #: chunk size for the chunked-prefill path. None ⇒ plain prompts
        #: prefill in one call (current perf behavior) and only prefix-
        #: cache tails are chunked (at prefix_cache_max_tail tokens per
        #: round). Set it to bound per-round prefill latency for BOTH
        #: kv layouts.
        self.prefill_chunk = prefill_chunk
        self._next_id = 0
        # device-resident decode state: last tokens, active mask, temps,
        # PRNG key. Uploaded only when slot membership changes — per-block
        # host->device transfers each cost a transport round trip
        self._last = jnp.zeros((max_slots, 1), jnp.int32)
        self._active_dev = jnp.zeros((max_slots,), jnp.int32)
        self._temps_dev = jnp.zeros((max_slots,), jnp.float32)
        self._key = jax.random.PRNGKey(seed ^ 0x5eed)
        self._masks_dirty = True

        # Every program is a named def: a device trace names a program
        # after its function (`jit_serve_prefill`), and a lambda's name
        # says nothing, so the device's time could not be split between
        # admission and decoding.
        if kv_layout == "paged":
            def serve_decode_step_paged(p, t, kp, vp, pt, ln, a):
                return cached.decode_step_paged(p, t, kp, vp, pt, ln, cfg,
                                               active=a)

            self._decode_paged = jax.jit(serve_decode_step_paged,
                                         donate_argnums=(2, 3))

            def serve_scatter_pages(kp, vp, ks, vs, pt, sl, ln):
                return cached.scatter_prefill_pages(kp, vp, ks, vs, pt, sl,
                                                   ln, page_size)

            self._scatter = jax.jit(serve_scatter_pages,
                                    donate_argnums=(0, 1))

            # chunked tail prefill against cached prefix pages: ONE
            # device call finishes a prefix-hit admission (token-by-token
            # draining costs a transport round trip per tail token)
            def serve_prefill_tail(p, t, tl, pl, pt, kp, vp):
                return cached.prefill_paged_tail(p, t, tl, pl, pt, kp, vp,
                                                cfg)

            self._prefill_tail = jax.jit(serve_prefill_tail,
                                         donate_argnums=(5, 6))

            def serve_decode_block_paged(params, last, kp, vp, pt, ln,
                                         active, temps, key, n):
                def body(carry, _):
                    last, kp, vp, ln, key = carry
                    logits, kp, vp, ln = cached.decode_step_paged(
                        params, last, kp, vp, pt, ln, cfg, active=active)
                    key, sub = jax.random.split(key)
                    greedy = jnp.argmax(logits, axis=-1)
                    sampled = jax.random.categorical(
                        sub, logits / jnp.maximum(temps, 1e-4)[:, None],
                        axis=-1)
                    tok = jnp.where(temps <= 0.0, greedy, sampled)
                    return ((tok[:, None].astype(jnp.int32), kp, vp, ln,
                             key), tok)

                (last, kp, vp, ln, key), toks = jax.lax.scan(
                    body, (last, kp, vp, ln, key), None, length=n)
                return toks, last, kp, vp, ln, key

            self._decode_n_paged = jax.jit(
                serve_decode_block_paged, static_argnames="n",
                donate_argnums=(2, 3))
        else:
            def serve_decode_step(p, t, c, a):
                return cached.decode_step(p, t, c, cfg, active=a)

            self._decode = jax.jit(
                serve_decode_step,
                donate_argnums=(2,))  # cache aliases in place across calls

            # chunked-prefill twin for the contiguous layout: writes a
            # bounded token chunk into slot rows at their current fill
            def serve_prefill_tail_contig(p, t, tl, pl, sl, c):
                return cached.prefill_tail_contiguous(p, t, tl, pl, c, sl,
                                                     cfg)

            self._prefill_tail_contig = jax.jit(serve_prefill_tail_contig,
                                                donate_argnums=(5,))

        def serve_prefill(p, t, lens):
            return cached.prefill(p, t, lens, cfg)

        self._prefill = jax.jit(serve_prefill)

        def serve_decode_block(params, last, cache, active, temps, key, n):
            # n fused decode steps with ON-DEVICE sampling: one host
            # round-trip per n tokens instead of per token (the per-step
            # logits fetch dominates decode latency on any transport)
            def body(carry, _):
                last, cache, key = carry
                logits, cache = cached.decode_step(params, last, cache, cfg,
                                                  active=active)
                key, sub = jax.random.split(key)
                greedy = jnp.argmax(logits, axis=-1)
                sampled = jax.random.categorical(
                    sub, logits / jnp.maximum(temps, 1e-4)[:, None], axis=-1)
                tok = jnp.where(temps <= 0.0, greedy, sampled)
                return (tok[:, None].astype(jnp.int32), cache, key), tok

            (last, cache, key), toks = jax.lax.scan(
                body, (last, cache, key), None, length=n)
            return toks, last, cache, key  # toks: [n, slots]

        self._decode_n = jax.jit(serve_decode_block, static_argnames="n",
                                 donate_argnums=(2,))
        # a fetch far above its running median is a stall (health.py)
        self._fetch_watch = _health.WaitWatch("serve.decode_block.fetch")

        self.metrics = {"requests": 0, "tokens_generated": 0,
                        "ttft_sum": 0.0, "ttft_count": 0}
        # Cluster-visible instruments (util.metrics -> batched telemetry
        # reports), replica-tagged so the future serve router can read
        # per-replica admission cost and TTFT percentiles from the GCS.
        # The plain dict above stays the local stats() view.
        from ray_tpu.util import metrics as _um
        try:
            import ray_tpu
            replica = (ray_tpu.get_runtime_context().get_actor_id()
                       or "driver")
        except Exception:
            replica = "local"
        tag = {"replica": str(replica)[:16]}
        self._m_ttft = _um.Histogram(
            "ray_tpu_serve_ttft_s", "time to first token per request",
            boundaries=[0.01, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30],
            tag_keys=("replica",)).set_default_tags(tag)
        self._m_admit = _um.Counter(
            "ray_tpu_serve_admit_s", "seconds spent in request admission",
            tag_keys=("replica",)).set_default_tags(tag)
        self._m_decode_block = _um.Histogram(
            "ray_tpu_serve_decode_block_s",
            "fused decode-block wall seconds",
            boundaries=[0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5],
            tag_keys=("replica",)).set_default_tags(tag)
        self._m_tokens = _um.Counter(
            "ray_tpu_serve_tokens_generated", "generated tokens",
            tag_keys=("replica",)).set_default_tags(tag)

    def _record_first_token(self, r, now: float) -> None:
        """Client-visible TTFT, once per request (re-admission after a
        recompute-preemption must not reset it or double-count)."""
        r.first_token_time = now
        ttft = now - r.submit_time
        self.metrics["ttft_sum"] += ttft
        self.metrics["ttft_count"] += 1
        self._m_ttft.observe(ttft)

    # ---- submission --------------------------------------------------------

    def submit(self, prompt: List[int], max_new_tokens: int = 32,
               temperature: float = 0.0) -> _Request:
        with self.lock:
            if (self.max_queue_depth is not None
                    and len(self.pending) >= self.max_queue_depth):
                self.metrics["rejected"] = \
                    self.metrics.get("rejected", 0) + 1
                raise LLMQueueFull(
                    f"admission queue at max_queue_depth="
                    f"{self.max_queue_depth}; retry later")
            req = _Request(self._next_id, list(prompt), max_new_tokens,
                           temperature)
            self._next_id += 1
            self.pending.append(req)
            self.metrics["requests"] += 1
        return req

    def has_work(self) -> bool:
        with self.lock:
            return bool(self.pending) or any(s is not None for s in self.slots)

    def fail_all(self, error: str) -> int:
        """Fail every queued, prefilling and decoding request with
        `error` and free their slots and pages. For a step that raised:
        its donated cache buffers may be gone, so nothing in flight can
        finish, and retrying the same program would only raise again."""
        with self.lock:
            failed = self.pending + [r for r in self.slots if r is not None]
            self.pending = []
            self._prefilling = []
            for r in failed:
                self._free_slot(r)
        for r in failed:
            r.error, r.error_status = error, 500
            r.done_event.set()
            r.progress.set()
        self.metrics["failed"] = self.metrics.get("failed", 0) + len(failed)
        return len(failed)

    def device_report(self) -> Dict[str, Any]:
        """Where and how this engine runs: the device jax gave it, the
        compute dtype it chose, and whether the fused decode block traces
        to a Pallas call (paged layout on a chip) or to the jnp reference.
        Traces the decode block once; not for polling."""
        jax, jnp = self._jax, self._jnp
        dev = jax.devices()[0]
        if self.kv_layout == "paged":
            fn, args = self._decode_n_paged, (
                self.params, self._last, self.kp, self.vp, self._pt_dev,
                self._len_dev, self._active_dev, self._temps_dev, self._key)
        else:
            fn, args = self._decode_n, (
                self.params, self._last, self.cache, self._active_dev,
                self._temps_dev, self._key)
        # shapes only: the decode thread may be donating these buffers
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        lowered = fn.lower(*shapes, n=8)
        return {"platform": dev.platform, "kind": dev.device_kind,
                "count": len(jax.devices()),
                "dtype": str(jnp.dtype(self.cfg.dtype)),
                "kv_layout": self.kv_layout,
                "decode_has_pallas_call":
                    "tpu_custom_call" in lowered.as_text()}

    # ---- engine step -------------------------------------------------------

    def _bucket(self, n: int) -> int:
        b = 8
        while b < n:
            b *= 2
        return min(b, self.max_seq)

    def _chunk_size(self) -> int:
        """Tokens of prefill per admission round for the chunked path."""
        if self.prefill_chunk:
            return self.prefill_chunk
        if self.kv_layout == "paged":
            return self.prefix_cache_max_tail
        return 512

    def _admit(self):
        chunk = self._chunk_size()
        with self.lock:
            free = [i for i, s in enumerate(self.slots) if s is None]
            chunked_new = []
            admit = []
            if self.kv_layout == "paged":
                # FIFO admission gated on BOTH a free slot and enough
                # free pages for the prompt — head-of-line blocks
                # rather than starving long prompts
                for r in list(self.pending):
                    if not free:
                        break
                    plen = min(len(r.prompt), self.max_seq - 1)
                    # a prompt that can NEVER fit must fail now, or it
                    # head-of-line blocks the queue forever
                    if self.pool.pages_for(plen) > min(
                            self.pool.max_pages_per_slot,
                            self.pool.num_pages - 1):
                        self.pending.remove(r)
                        r.error = (f"prompt of {plen} tokens exceeds the "
                                   f"KV page pool capacity")
                        r.done_event.set()
                        r.progress.set()
                        continue
                    if self._try_admit_cached(r, free, plen):
                        chunked_new.append(r)
                        self.pending.remove(r)
                        continue
                    slot = free[0]
                    if not self.pool.grow(slot, plen):
                        break
                    free.pop(0)
                    self._assign_slot(r, slot, plen, chunk, chunked_new,
                                      admit)
                    self.pending.remove(r)
            else:
                for r in list(self.pending):
                    if not free:
                        break
                    plen = min(len(r.prompt), self.max_seq - 1)
                    self._assign_slot(r, free.pop(0), plen, chunk,
                                      chunked_new, admit)
                    self.pending.remove(r)
            self._prefilling.extend(chunked_new)
            pending_after = len(self.pending)
        self._note_admitted(chunked_new, admit)
        # advance every mid-prefill request (fresh prefix hits included)
        # by one bounded chunk — one device call for the whole set
        self._prefill_round(chunk, pending_after)
        if not admit:
            return
        P = self._bucket(max(len(r.prompt) for r in admit))
        toks = np.zeros((len(admit), P), np.int32)
        lens = np.zeros((len(admit),), np.int32)
        for i, r in enumerate(admit):
            p = r.prompt[-P:]
            toks[i, :len(p)] = p
            lens[i] = len(p)
        with _tracing.span("serve.prefill", {
                "rows": len(admit), "batch_bucket": len(admit),
                "length_bucket": P, "prompt_tokens": int(lens.sum()),
                "pending_after": pending_after}):
            self._prefill_plain(admit, toks, lens, P)

    def _note_admitted(self, chunked_new: list, admit: list) -> None:
        """Each request that just got its slot: how long it queued (from
        its submission, or from its preemption), outside the lock."""
        now = time.time()
        # a plain admission has no fill yet; a chunked one starts at the
        # tokens of the pages its prefix hit adopted (0 without a hit)
        for r, hit in ([(r, r._filled) for r in chunked_new]
                       + [(r, 0) for r in admit]):
            wait = max(now - (r.queued_time or r.submit_time), 0.0)
            _tracing.instant("serve.admitted", {
                "queue_ms": wait * 1e3, "prompt_tokens": len(r.prompt),
                "prefix_hit_tokens": hit})

    def _prefill_plain(self, admit: list, toks, lens, P: int) -> None:
        """One prefill program over the whole prompts of `admit`, their
        KV written to the cache, their first tokens fetched."""
        jnp = self._jnp
        logits, ks, vs = self._prefill(self.params, jnp.asarray(toks),
                                       jnp.asarray(lens))
        if self.kv_layout == "paged":
            slots = jnp.asarray([r.slot for r in admit])
            self._pt_dev = jnp.asarray(self.pool.table)
            self.kp, self.vp = self._scatter(
                self.kp, self.vp, ks, vs, self._pt_dev, slots,
                jnp.asarray(lens))
            for i, r in enumerate(admit):
                self._len_host[r.slot] = int(lens[i])
                r._filled = int(lens[i])
            self._len_dev = jnp.asarray(self._len_host.astype(np.int32))
            self._table_dirty = False
        else:
            # scatter new kv into cache slots + set lengths
            slots = jnp.asarray([r.slot for r in admit])
            k = self.cache.k.at[:, slots, :P].set(
                ks.astype(self.cache.k.dtype))
            v = self.cache.v.at[:, slots, :P].set(
                vs.astype(self.cache.v.dtype))
            length = self.cache.length.at[slots].set(jnp.asarray(lens))
            for i, r in enumerate(admit):
                r._filled = int(lens[i])
            from ray_tpu.models.cached import KVCache

            self.cache = KVCache(k, v, length)
        self._masks_dirty = True
        self._emit_first_tokens(list(enumerate(admit)), logits, len(admit))

    def _assign_slot(self, r, slot: int, plen: int, chunk: int,
                     chunked_new: list, admit: list):
        """Bind a request to its slot (caller holds self.lock), routing
        long prompts to the chunked-prefill path when enabled."""
        r.slot = slot
        self.slots[slot] = r
        if self.prefill_chunk and plen > chunk:
            # long prompt: bounded chunks across admission rounds
            # instead of one monopolizing prefill
            r._tail = list(r.prompt[-plen:])
            r._filled = 0
            if self.kv_layout == "paged":
                self._len_host[slot] = 0
            chunked_new.append(r)
        else:
            admit.append(r)

    def _emit_first_tokens(self, pairs, logits, nb: int):
        """Shared completion path for every prefill flavor (plain,
        prefix-hit, chunked): sample each finished row's first token
        from its logits row, record TTFT, register prompt pages for
        prefix caching, and finish/notify. pairs = [(logits_row,
        request)]; nb = the logits batch size (pad rows get temp 0)."""
        import jax.numpy as jnp

        if not pairs:
            return
        temps = [0.0] * nb
        for i, r in pairs:
            temps[i] = r.temperature
        first = np.asarray(self._sample(logits, temps))
        upd = jnp.asarray([r.slot for _, r in pairs])
        self._last = self._last.at[upd, 0].set(jnp.asarray(
            np.asarray([int(first[i]) for i, _ in pairs], np.int32)))
        now = time.time()
        for i, r in pairs:
            r.generated.append(int(first[i]))
            if r.first_token_time is None:
                self._record_first_token(r, now)
            if (self.kv_layout == "paged" and self.prefix_caching
                    and r._filled < self.max_seq):
                from ray_tpu.serve.paged_kv import page_chain_hashes

                # register this prompt's FULL pages for later hits
                # (prefill wrote their KV; they stay read-only — decode
                # appends past the fill). Prompts truncated to the FULL
                # max_seq window are skipped: the lookup side views the
                # last max_seq-1 tokens, so the page boundaries would
                # shift by one token and the pages' KV wouldn't
                # correspond to any lookup view.
                self.pool.register(r.slot, page_chain_hashes(
                    list(r.prompt)[-r._filled:], self.pool.page_size))
            self._maybe_finish(r)
            r.progress.set()
        self._count_tokens(len(pairs))

    def _count_tokens(self, n: int) -> None:
        """One update per delivery, not one per token (a lock and a dict
        update each in the cluster-visible counter)."""
        if n:
            self.metrics["tokens_generated"] += n
            self._m_tokens.inc(n)

    def _prefill_round(self, chunk: int, pending_after: int = 0):
        """One bounded prefill chunk for every mid-prefill request, in
        ONE device call (ref: vLLM chunked prefill scheduling — prefill
        advances between decode steps instead of monopolizing a round).
        Requests whose tail completes sample their first token here and
        join the next decode step; the rest stay masked out of decode
        and continue next round."""
        with self.lock:
            rows = list(self._prefilling)
        if not rows:
            return
        takes = [min(len(r._tail), chunk) for r in rows]
        Tb = self._bucket(max(takes))
        n = len(rows)
        with _tracing.span("serve.prefill", {
                "rows": n, "batch_bucket": self._batch_bucket(n),
                "length_bucket": Tb, "prompt_tokens": sum(takes),
                "pending_after": pending_after}):
            self._prefill_chunk(rows, takes, Tb)

    def _batch_bucket(self, n: int) -> int:
        """Rows of the chunked-prefill program that holds `n` requests."""
        if self.kv_layout != "paged":
            # contiguous has no trash row a pad entry could safely
            # target, so the batch dim stays exact (bounded by
            # max_slots distinct programs)
            return n
        # pad the BATCH dim to a pow2 bucket: every distinct (n, T)
        # shape is its own XLA program. Pad rows have tail_len 0, so
        # their writes land in the trash page.
        nb = 1
        while nb < n:
            nb *= 2
        return nb

    def _prefill_chunk(self, rows: list, takes: list, Tb: int) -> None:
        """The chunked-prefill program over one chunk of every row, and
        the first tokens of the rows whose prompt it finished."""
        jnp = self._jnp
        n, nb = len(rows), self._batch_bucket(len(rows))
        toks = np.zeros((nb, Tb), np.int32)
        tl = np.zeros((nb,), np.int32)
        pl = np.zeros((nb,), np.int32)
        for i, r in enumerate(rows):
            t = r._tail[:takes[i]]
            toks[i, :len(t)] = t
            tl[i] = len(t)
            pl[i] = r._filled
        if self.kv_layout == "paged":
            tab = np.zeros((nb, self.pool.table.shape[1]), np.int32)
            tab[:n] = self.pool.table[[r.slot for r in rows]]
            logits, self.kp, self.vp = self._prefill_tail(
                self.params, jnp.asarray(toks), jnp.asarray(tl),
                jnp.asarray(pl), jnp.asarray(tab), self.kp, self.vp)
        else:
            slot_ids = jnp.asarray([r.slot for r in rows], jnp.int32)
            logits, self.cache = self._prefill_tail_contig(
                self.params, jnp.asarray(toks), jnp.asarray(tl),
                jnp.asarray(pl), slot_ids, self.cache)
        finished = []
        with self.lock:
            for i, r in enumerate(rows):
                r._filled += takes[i]
                r._tail = r._tail[takes[i]:]
                if self.kv_layout == "paged":
                    self._len_host[r.slot] = r._filled
                if not r._tail:
                    finished.append((i, r))
                    self._prefilling.remove(r)
            self._masks_dirty = True
            if self.kv_layout == "paged":
                self._table_dirty = True
        self._emit_first_tokens(finished, logits, nb)

    def _try_admit_cached(self, r, free: List[int], plen: int) -> bool:
        """Prefix-cache admission (caller holds self.lock): if the
        prompt's leading FULL pages are cached, adopt them — no prefill
        compute, no new pages for the prefix. The unmatched tail is
        finished by the chunked-prefill rounds (at most
        prefix_cache_max_tail — or prefill_chunk — tokens per round), so
        a long tail no longer forces a full re-prefill of the matched
        prefix. Returns False to fall back to the full prefill."""
        if not self.prefix_caching:
            return False
        from ray_tpu.serve.paged_kv import page_chain_hashes

        ptoks = list(r.prompt[-plen:])   # view matching registration
        # memoized: a head-of-line-blocked request would otherwise
        # re-hash its whole prompt once per decode step until admission
        # (preemption rebuilds the prompt and clears the memo)
        hashes = getattr(r, "_page_hashes", None)
        if hashes is None:
            hashes = page_chain_hashes(ptoks, self.pool.page_size)
            if len(hashes) * self.pool.page_size >= plen:
                hashes = hashes[:-1]  # keep >=1 tail token as decode input
            r._page_hashes = hashes
        if not hashes:
            return False
        pages = self.pool.match_prefix(hashes)
        if not pages:
            return False
        matched = len(pages) * self.pool.page_size
        slot = free[0]
        self.pool.adopt(slot, pages)
        if not self.pool.grow(slot, plen):   # room for the tail's KV
            self.pool.release(slot)          # rollback: drops the refs
            return False
        free.pop(0)
        r.slot = slot
        self.slots[slot] = r
        self._len_host[slot] = matched       # tail-prefill advances it
        r._tail = ptoks[matched:]
        r._filled = matched
        self.metrics["prefix_hits"] = \
            self.metrics.get("prefix_hits", 0) + 1
        self.metrics["prefix_hit_tokens"] = \
            self.metrics.get("prefix_hit_tokens", 0) + matched
        return True

    def _sample(self, logits, temps):
        import jax

        jnp = self._jnp
        logits = jnp.asarray(logits)
        greedy = jnp.argmax(logits, axis=-1)
        if all(t == 0.0 for t in temps):
            return greedy
        key = jax.random.PRNGKey(np.random.randint(0, 2**31 - 1))
        t = jnp.asarray([max(tt, 1e-4) for tt in temps])[:, None]
        sampled = jax.random.categorical(key, logits / t, axis=-1)
        use_greedy = jnp.asarray([tt == 0.0 for tt in temps])
        return jnp.where(use_greedy, greedy, sampled)

    def _seq_len(self, r: _Request) -> int:
        return len(r.prompt) + len(r.generated) - r.overlap

    @staticmethod
    def _decode_ready(r: Optional[_Request]) -> bool:
        """A slot participates in decode only once its prefill is
        complete — mid-chunked-prefill rows are masked out."""
        return r is not None and not getattr(r, "_tail", None)

    def _maybe_finish(self, r: _Request):
        if (len(r.generated) >= r.max_new_tokens
                or (self.eos >= 0 and r.generated
                    and r.generated[-1] == self.eos)
                or self._seq_len(r) >= self.max_seq - 1):
            with self.lock:
                self._free_slot(r)
            r.done_event.set()
            r.progress.set()

    def _free_slot(self, r: _Request) -> None:
        """Give back a finished or failed request's slot and pages
        (caller holds self.lock)."""
        if r.slot < 0:
            return
        if self.kv_layout == "paged":
            self.pool.release(r.slot)
            self._len_host[r.slot] = 0
            self._table_dirty = True
        self.slots[r.slot] = None
        r.slot = -1
        self._masks_dirty = True

    def _preempt_one(self) -> bool:
        """Paged pools exhausted mid-decode: evict the most recently
        admitted request (vLLM's recompute-preemption policy) — its
        pages free up, it rejoins the FRONT of the queue with
        prompt+generated as the new prompt, and prefill recomputes its
        KV when pages are available again."""
        with self.lock:
            active = [r for r in self.slots if r is not None]
            if len(active) <= 1:
                return False
            victim = max(active, key=lambda r: r.req_id)
            self.pool.release(victim.slot)
            self._len_host[victim.slot] = 0
            self.slots[victim.slot] = None
            victim.slot = -1
            # resume prompt = everything decoded so far; `overlap` keeps
            # sequence-length accounting from double-counting the tokens
            # now present in both prompt and generated (repeat-preempt
            # safe: only the not-yet-folded tail is appended)
            victim.prompt = list(victim.prompt) + \
                list(victim.generated[victim.overlap:])
            victim.overlap = len(victim.generated)
            # the resume prompt changed, so its page hashes did too
            if hasattr(victim, "_page_hashes"):
                del victim._page_hashes
            # a mid-prefill victim restarts admission from scratch:
            # its chunk progress lived in the released pages
            if getattr(victim, "_tail", None):
                victim._tail = None
                try:
                    self._prefilling.remove(victim)
                except ValueError:
                    pass
            victim.queued_time = time.time()
            self.pending.insert(0, victim)
            self._table_dirty = True
            self._masks_dirty = True
            self.metrics["preemptions"] = \
                self.metrics.get("preemptions", 0) + 1
        return True

    def _ensure_paged_capacity(self, n: int) -> int:
        """Grow every active slot to hold n more tokens, preempting if
        the pool runs dry. Returns the usable n (0 if nothing active)."""
        def pages_needed(n_try: int) -> int:
            total = 0
            for r in active:
                if r.slot < 0:
                    continue
                need_tok = min(int(self._len_host[r.slot]) + n_try,
                               self.max_seq)
                need_pages = self.pool.pages_for(need_tok)
                total += max(need_pages - len(self.pool.owned[r.slot]), 0)
            return total

        def try_grow(n_try: int) -> bool:
            # precheck against the pool so a doomed attempt allocates
            # NOTHING: partial grants skew the halved retry's
            # redistribution and can force an avoidable
            # recompute-preemption right after pages were granted.
            # available_pages counts refcount-0 cached pages too —
            # grow() reclaims them on demand.
            if pages_needed(n_try) > self.pool.available_pages:
                return False
            ver_before = self.pool.table_version
            ok = True
            for r in active:
                if r.slot < 0:
                    continue
                need = int(self._len_host[r.slot]) + n_try
                if not self.pool.grow(r.slot, min(need, self.max_seq)):
                    ok = False
                    break
            if self.pool.table_version != ver_before:
                # table mutated: device copy is stale. (used_pages can't
                # detect this — growth served from cache reclaim is a
                # net-zero page-count change.)
                self._table_dirty = True
            return ok

        while True:
            with self.lock:
                active = [r for r in self.slots if r is not None]
            if not active:
                return 0
            # prefer a smaller block over evicting someone: preemption
            # costs a full prefill recompute, a short block costs only
            # extra host syncs
            n_try = n
            while n_try >= 1:
                if try_grow(n_try):
                    return n_try
                n_try //= 2
            if not self._preempt_one():
                # lone request can't grow: cap the block at the tokens
                # its current pages still hold (0 -> caller finishes it)
                slot = active[0].slot
                cap = len(self.pool.owned[slot]) * self.pool.page_size
                return max(min(n, cap - int(self._len_host[slot])), 0)

    def _sync_paged_device_state(self, active_mask, temps=None):
        """Upload ONLY what went stale: every host->device transfer costs
        a transport round-trip, and the steady decode loop should cost
        zero of them (lengths advance on device; the table/masks change
        only on admit/finish/preempt/page-growth)."""
        import jax.numpy as jnp

        if self._table_dirty:
            self._pt_dev = jnp.asarray(self.pool.table)
            self._table_dirty = False
        if self._masks_dirty:
            self._active_dev = jnp.asarray(active_mask)
            if temps is not None:
                self._temps_dev = jnp.asarray(temps)
            self._len_dev = jnp.asarray(self._len_host.astype(np.int32))
            self._masks_dirty = False
        return self._active_dev

    def _live_context(self, active_reqs: list) -> int:
        """Cached tokens the next decode step attends to, all slots."""
        if self.kv_layout == "paged":
            return int(sum(self._len_host[r.slot] for r in active_reqs))
        return sum(self._seq_len(r) for r in active_reqs)

    def _count_block(self, n: int, active: int, context: int,
                     block_s: float, fetch_s: float) -> None:
        """One decode block of `n` steps, the one-step block included."""
        m = self.metrics
        m["decode_block_s"] = m.get("decode_block_s", 0.0) + block_s
        m["decode_blocks"] = m.get("decode_blocks", 0) + 1
        self._m_decode_block.observe(block_s)
        self._fetch_watch.observe(fetch_s, n=n, active=active,
                                  context=context)

    def step(self, n_asked: int = 1) -> int:
        """Admit + one decode step for all active slots. Returns number of
        active requests after the step. `n_asked`: the block `step_n`
        wanted when it fell through to here (for the block's span)."""
        import jax.numpy as jnp

        with _tracing.span("serve.admit"):
            self._admit()
        with self.lock:
            active_reqs = [r for r in self.slots if self._decode_ready(r)]
            active_mask = np.array(
                [1 if self._decode_ready(s) else 0 for s in self.slots],
                np.int32)
            occupied = sum(1 for s in self.slots if s is not None)
        if not active_reqs:
            # mid-prefill slots may still be occupied: report them so
            # callers keep driving the engine
            return occupied
        if self.kv_layout == "paged":
            if self._ensure_paged_capacity(1) < 1:
                for r in list(active_reqs):
                    # page-capped truncation is an ERROR the client must
                    # see — a silent early finish is indistinguishable
                    # from a complete generation
                    r.max_new_tokens = len(r.generated)
                    r.error = ("generation truncated: KV page pool "
                               f"exhausted after {len(r.generated)} tokens")
                    self._maybe_finish(r)
                return 0
            # capacity growth may have preempted a slot — re-snapshot
            with self.lock:
                active_reqs = [r for r in self.slots
                               if self._decode_ready(r)]
                active_mask = np.array(
                    [1 if self._decode_ready(s) else 0
                     for s in self.slots], np.int32)
                np_temps = np.zeros((self.max_slots,), np.float32)
                for r in active_reqs:
                    np_temps[r.slot] = r.temperature
                occupied = sum(1 for s in self.slots if s is not None)
            if not active_reqs:
                return occupied
        t_blk = time.perf_counter()
        active, context = len(active_reqs), self._live_context(active_reqs)
        with _tracing.span("serve.decode_block", {
                "n": 1, "n_asked": n_asked, "active": active,
                "max_slots": self.max_slots, "context": context}):
            with _tracing.span("serve.decode_block.dispatch"):
                if self.kv_layout == "paged":
                    # temps ride along so a later fused block never
                    # samples with a stale _temps_dev after this sync
                    # clears _masks_dirty
                    act = self._sync_paged_device_state(active_mask,
                                                        np_temps)
                    logits, self.kp, self.vp, self._len_dev = \
                        self._decode_paged(
                            self.params, self._last, self.kp, self.vp,
                            self._pt_dev, self._len_dev, act)
                    self._len_host += active_mask
                else:
                    logits, self.cache = self._decode(
                        self.params, self._last, self.cache,
                        jnp.asarray(active_mask))
                temps = [0.0] * self.max_slots
                with self.lock:
                    for r in self.slots:
                        if r is not None:
                            temps[r.slot] = r.temperature
                toks = self._sample(logits, temps)
            with _tracing.span("serve.decode_block.fetch"):
                t_fetch = time.perf_counter()
                toks = np.asarray(toks)
                t_done = time.perf_counter()
        self._count_block(1, active, context, t_done - t_blk,
                          t_done - t_fetch)
        with _tracing.span("serve.deliver"):
            self._last = jnp.asarray(toks[:, None].astype(np.int32))
            now = time.time()
            delivered = 0
            for r in list(active_reqs):
                if r.slot < 0:
                    continue
                r.generated.append(int(toks[r.slot]))
                delivered += 1
                if r.first_token_time is None:
                    self._record_first_token(r, now)
                self._maybe_finish(r)
                r.progress.set()
            self._count_tokens(delivered)
        with self.lock:
            return sum(1 for s in self.slots if s is not None)

    def step_n(self, n: int = 8) -> int:
        """Admit, then run up to n FUSED decode steps (one host sync).
        n is clamped so no active slot can outrun its token budget or the
        cache; mid-block EOS costs a few wasted device steps (the slot's
        surplus tokens are discarded host-side), the same trade vLLM-
        style engines make for multi-step scheduling."""
        import jax
        import jax.numpy as jnp

        t_adm = time.perf_counter()   # not the wall clock: a stepped
        with _tracing.span("serve.admit"):   # clock must not read as a stall
            self._admit()
        adm = time.perf_counter() - t_adm
        self.metrics["admit_s"] = self.metrics.get("admit_s", 0.0) + adm
        self._m_admit.inc(adm)
        with self.lock:
            active_reqs = [r for r in self.slots if self._decode_ready(r)]
            active_mask = np.array(
                [1 if self._decode_ready(s) else 0 for s in self.slots],
                np.int32)
            temps = np.zeros((self.max_slots,), np.float32)
            for r in active_reqs:
                temps[r.slot] = r.temperature
            occupied = sum(1 for s in self.slots if s is not None)
        if not active_reqs:
            return occupied
        n_eff = n
        for r in active_reqs:
            n_eff = min(n_eff,
                        r.max_new_tokens - len(r.generated),
                        self.max_seq - 1 - self._seq_len(r))
        # round DOWN to a power of two: every distinct n is a separate
        # XLA compilation of the n-step scan, so bound the set to
        # {1, 2, 4, ..., n} (same bucketing idea as prefill)
        b = 1
        while b * 2 <= n_eff:
            b *= 2
        n_eff = b
        if self.kv_layout == "paged" and n_eff >= 1:
            n_cap = self._ensure_paged_capacity(n_eff)
            while n_eff > max(n_cap, 1):
                n_eff //= 2
            # capacity growth may have preempted a slot — re-snapshot
            with self.lock:
                active_reqs = [r for r in self.slots
                               if self._decode_ready(r)]
                active_mask = np.array(
                    [1 if self._decode_ready(s) else 0
                     for s in self.slots], np.int32)
                temps = np.zeros((self.max_slots,), np.float32)
                for r in active_reqs:
                    temps[r.slot] = r.temperature
                occupied = sum(1 for s in self.slots if s is not None)
            if not active_reqs:
                return occupied
        if n_eff <= 1:
            return self.step(n_asked=n)
        t_blk = time.perf_counter()
        active, context = len(active_reqs), self._live_context(active_reqs)
        with _tracing.span("serve.decode_block", {
                "n": n_eff, "n_asked": n, "active": active,
                "max_slots": self.max_slots, "context": context}):
            with _tracing.span("serve.decode_block.dispatch"):
                if self.kv_layout == "paged":
                    act = self._sync_paged_device_state(active_mask, temps)
                    (toks, self._last, self.kp, self.vp, self._len_dev,
                     self._key) = self._decode_n_paged(
                        self.params, self._last, self.kp, self.vp,
                        self._pt_dev, self._len_dev, act, self._temps_dev,
                        self._key, n_eff)
                    self._len_host += active_mask.astype(np.int64) * n_eff
                else:
                    if self._masks_dirty:
                        self._active_dev = jnp.asarray(active_mask)
                        self._temps_dev = jnp.asarray(temps)
                        self._masks_dirty = False
                    toks, self._last, self.cache, self._key = self._decode_n(
                        self.params, self._last, self.cache,
                        self._active_dev, self._temps_dev, self._key, n_eff)
            with _tracing.span("serve.decode_block.fetch"):
                t_fetch = time.perf_counter()
                toks = np.asarray(toks)  # the block's single host fetch
                t_done = time.perf_counter()
        # per-block time (dispatch + device + the one fetch): attributes
        # serving throughput between engine time and transport weather
        self._count_block(n_eff, active, context, t_done - t_blk,
                          t_done - t_fetch)
        with _tracing.span("serve.deliver"):
            now = time.time()
            delivered = 0
            for r in list(active_reqs):
                for j in range(n_eff):
                    if r.slot < 0:
                        break  # finished mid-block; surplus tokens dropped
                    r.generated.append(int(toks[j, r.slot]))
                    delivered += 1
                    if r.first_token_time is None:   # defensive: admission
                        self._record_first_token(r, now)  # normally did this
                    self._maybe_finish(r)
                r.progress.set()
            self._count_tokens(delivered)
        with self.lock:
            return sum(1 for s in self.slots if s is not None)

    def generate(self, prompt: List[int], max_new_tokens: int = 32,
                 temperature: float = 0.0, decode_block: int = 8) -> List[int]:
        """Synchronous convenience: submit + drive until done."""
        req = self.submit(prompt, max_new_tokens, temperature)
        while not req.done_event.is_set():
            self.step_n(decode_block)
        return req.generated

    # ---- disagg KV handoff (serve/kv_transfer.py) --------------------------

    def export_kv_pages(self, pages: List[int]):
        """Host-side gather of physical KV pages for a prefill->decode
        handoff (paged layout only; call under self.lock so a reclaim
        can't recycle the pages mid-gather). Returns (k, v) numpy arrays
        shaped (n_layers, n_kv_heads, len(pages), page_size, head_dim) —
        the payload one page-group store object carries."""
        assert self.kv_layout == "paged", "export needs kv_layout='paged'"
        idx = self._jnp.asarray(pages, self._jnp.int32)
        return (np.asarray(self.kp[:, :, idx]),
                np.asarray(self.vp[:, :, idx]))

    def import_kv_pages(self, page_hashes: List[bytes], k, v) -> int:
        """Adopt externally-exported KV pages (disagg decode side):
        allocate physical pages, write the payload in one scatter per
        pool array, and register them under their chain hashes. Imported
        pages park refcount-0/evictable exactly like pages a released
        slot leaves behind, so the next submit's _try_admit_cached
        adopts them with zero prefill compute — decode never re-runs the
        prefix's prefill. Returns the number of NEW pages written
        (already-registered hashes are reused, not rewritten)."""
        assert self.kv_layout == "paged", "import needs kv_layout='paged'"
        jnp = self._jnp
        with self.lock:
            pairs = self.pool.import_pages(list(page_hashes))
            new = [(i, p) for i, (p, is_new) in enumerate(pairs) if is_new]
            if not new:
                return 0
            sel = [i for i, _ in new]
            idx = jnp.asarray([p for _, p in new], jnp.int32)
            self.kp = self.kp.at[:, :, idx].set(
                jnp.asarray(np.asarray(k)[:, :, sel], self.kp.dtype))
            self.vp = self.vp.at[:, :, idx].set(
                jnp.asarray(np.asarray(v)[:, :, sel], self.vp.dtype))
            return len(new)


class LLMServer:
    """Serve deployment hosting an engine; a background thread drives the
    decode loop so concurrent requests batch continuously."""

    def __init__(self, preset: str = "tiny", max_slots: int = 8,
                 eos_token: int = -1, params=None, cfg=None,
                 decode_block: int = 8, mode: str = "monolithic",
                 group_pages: Optional[int] = None,
                 retained_groups: Optional[int] = None,
                 use_directory: bool = True,
                 multiplexed: bool = False,
                 max_models: Optional[int] = None,
                 models: Optional[Dict[str, dict]] = None, **kw):
        if mode not in ("monolithic", "prefill", "decode"):
            raise ValueError(f"unknown LLMServer mode {mode!r}")
        if multiplexed and mode != "monolithic":
            raise ValueError("model multiplexing needs mode='monolithic'")
        if mode != "monolithic":
            # disagg handoff is expressed in physical KV pages + chain
            # hashes: contiguous caches have neither
            kw.setdefault("kv_layout", "paged")
            if kw["kv_layout"] != "paged" or not kw.get("prefix_caching",
                                                        True):
                raise ValueError("disagg modes need kv_layout='paged' "
                                 "with prefix_caching on")
        from ray_tpu.core.config import GLOBAL_CONFIG as _gc
        self.mode = mode
        self.group_pages = (group_pages if group_pages is not None
                            else _gc.serve_disagg_group_pages)
        self.retained_groups = (retained_groups if retained_groups
                                is not None
                                else _gc.serve_disagg_retained_groups)
        self.use_directory = use_directory
        self._exporter = None   # lazy: needs the in-actor runtime
        self._adopter = None
        self.engine = LLMEngine(cfg=cfg, params=params, preset=preset,
                                max_slots=max_slots, eos_token=eos_token, **kw)
        # --- model multiplexing (serve/multiplex.py) ------------------------
        # Model id "" (or absent) always means the default engine above;
        # named models resolve through a _ModelCache of per-model
        # LLMEngines bounded by serve_max_models_per_replica. The LRU's
        # unloader parks the evicted engine on `_retiring` so the decode
        # loop finishes its in-flight generations before dropping it —
        # evicting a busy model must not kill live streams.
        self.multiplexed = multiplexed
        self._engine_kwargs = dict(cfg=cfg, params=params, preset=preset,
                                   max_slots=max_slots, eos_token=eos_token,
                                   **kw)
        self._model_spec: Dict[str, dict] = dict(models or {})
        self._model_registry = None   # lazy: needs the in-actor runtime
        # `_retiring` is shared between the event-loop thread (unloader
        # appends) and the decode thread (filter-reassign): both sides
        # take this lock, or an engine appended mid-filter is lost and
        # its in-flight streams never step again
        self._retire_lock = threading.Lock()
        self._retiring: List[LLMEngine] = []
        self._unpublished: set = set()
        from ray_tpu.serve.multiplex import _ModelCache
        self._models = _ModelCache(
            type(self)._load_model,
            max_models if max_models is not None
            else _gc.serve_max_models_per_replica,
            unloader=type(self)._unload_model)
        # fused decode steps per host sync (1 = lowest latency per token,
        # higher = fewer host round-trips; new arrivals wait at most one
        # block for admission)
        self.decode_block = decode_block
        self._wake = threading.Event()
        self._stop = False
        self._draining = False
        # decode-loop progress beacon: armed while the engine has
        # admitted work, ticked per decode block — a wedged device step
        # (or a deadlocked engine lock) flags as a StallEvent instead of
        # silently freezing every in-flight stream
        self._beacon = _health.beacon("serve:decode", deadline_s=30.0)
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _engines(self) -> List["LLMEngine"]:
        """Every engine the decode loop must drive: default + resident
        multiplexed models + evicted-but-still-busy retirees. Runs on
        the decode thread while the event loop loads/evicts models, so
        it reads the cache's immutable snapshot — never the live
        OrderedDict."""
        engines = [self.engine]
        engines.extend(self._models.values_snapshot())
        with self._retire_lock:
            engines.extend(self._retiring)
        return engines

    def _loop(self):
        while not self._stop:
            idle_s = 0.01
            try:
                worked = self._drive_engines()
            except Exception as e:  # noqa: BLE001 — see below
                # Anything but a step (the model table changing under the
                # iteration, a beacon, an engine's bookkeeping): a thread
                # that ended here would leave every stream of the replica
                # hanging. Fail what is in flight with the error and wait
                # for the next request instead of coming straight back.
                logger.exception("decode loop iteration failed")
                self._fail_engines(
                    [self.engine, *self._models.values_snapshot(),
                     *self._retiring],
                    f"decode loop failed: {type(e).__name__}: {e}")
                worked, idle_s = False, 1.0
            if not worked:
                self._beacon.disarm()
                self._wake.wait(timeout=idle_s)
                self._wake.clear()
        self._beacon.disarm()

    def _drive_engines(self) -> bool:
        """One pass of the decode loop: a fused block on every engine
        that has work. Returns whether any had."""
        worked = False
        for eng in self._engines():
            if eng.has_work():
                if not self._beacon.busy:
                    self._beacon.arm(queue=self.queue_len())
                try:
                    eng.step_n(self.decode_block)
                except Exception as e:  # noqa: BLE001 — see below
                    # A step that raised (a program the compiler or the
                    # runtime refuses) raises again on retry: fail that
                    # engine's requests with the error so clients see it,
                    # and keep the thread for the other engines. With
                    # nothing left in flight the loop sleeps; it cannot
                    # spin on the failure.
                    logger.exception("decode step failed")
                    self._fail_engines(
                        [eng], f"engine step failed: {type(e).__name__}: {e}")
                self._beacon.tick()
                worked = True
        if self._retiring:
            # a retiree with no admitted work left has finished its
            # in-flight generations; drop it (engine GC frees pages)
            with self._retire_lock:
                self._retiring = [e for e in self._retiring
                                  if e.has_work()]
        return worked

    @staticmethod
    def _fail_engines(engines, error: str) -> None:
        for eng in engines:
            try:
                n = eng.fail_all(error)
            except Exception:  # noqa: BLE001 — the other engines still get theirs
                logger.exception("could not fail an engine's requests")
                continue
            if n:
                logger.error("failed %d request(s): %s", n, error)

    # ---- model multiplexing ------------------------------------------------

    def _registry(self):
        if self._model_registry is None:
            from ray_tpu.serve.multiplex import ModelRegistry
            self._model_registry = ModelRegistry()
        return self._model_registry

    def _fetch_published(self, model_id: str):
        """Blocking: resolve published weights from the object store.
        Returns None ONLY when the id is genuinely unpublished (the
        engine then inits from its preset/spec). Registry or fetch
        failures propagate so the load fails loudly — a transient store
        timeout must not silently serve default weights under the
        requested model id."""
        reg = self._registry()
        if not reg.contains(model_id):
            return None
        return reg.fetch(model_id)

    async def _load_model(self, model_id: str) -> "LLMEngine":
        """_ModelCache loader: build the per-model engine. Weights come
        from the ModelRegistry when published (one pinned store copy
        shared by every replica on the node); engine construction (jit
        compiles) runs off the event loop."""
        params = await asyncio.to_thread(self._fetch_published, model_id)
        kw = dict(self._engine_kwargs)
        kw.update(self._model_spec.get(model_id, {}))
        if params is not None:
            kw["params"] = params
        return await asyncio.to_thread(LLMEngine, **kw)

    def _unload_model(self, model_id: str, engine: "LLMEngine"):
        """_ModelCache unloader: retire, don't kill — the decode loop
        keeps driving the engine until its in-flight generations finish,
        then drops the last reference (page pool + weights free)."""
        with self._retire_lock:
            self._retiring.append(engine)
        self._wake.set()

    async def _engine_for(self, model_id: str) -> "LLMEngine":
        if not model_id:
            return self.engine
        if not self.multiplexed:
            raise LLMQueueFull(
                f"replica is not multiplexed; cannot serve model "
                f"{model_id!r}")
        eng = await self._models.get(self, model_id)
        self._wake.set()
        return eng

    async def load_model(self, model_id: str) -> List[str]:
        """Controller scale-up entry: warm-load `model_id` on this
        replica and (re)publish it to the router-visible set."""
        self._unpublished.discard(model_id)
        await self._engine_for(model_id)
        return self.loaded_models()

    def unpublish_model(self, model_id: str) -> bool:
        """Controller scale-down step 1: stop advertising the model so
        routers drain away; the engine stays resident until
        unload_model()."""
        if model_id in self._models.cache:
            self._unpublished.add(model_id)
            return True
        return False

    async def unload_model(self, model_id: str) -> bool:
        """Controller scale-down step 2 (after the per-model queue
        drains): evict the engine through the retiring path."""
        self._unpublished.discard(model_id)
        return await self._models.unload(self, model_id)

    def loaded_models(self) -> List[str]:
        """Models this replica ADVERTISES (resident minus draining) —
        what rides report_load to the router/controller."""
        return [m for m in self._models.models()
                if m not in self._unpublished]

    def model_queue_len(self, model_id: str) -> int:
        """Backlog of one model's engine (0 if not resident) — the
        controller's unpublish->drain->unload poll target."""
        eng = self._models.cache.get(model_id)
        if eng is None:
            return 0
        with eng.lock:
            return (len(eng.pending)
                    + sum(1 for s in eng.slots if s is not None))

    def model_stats(self) -> Dict[str, Any]:
        """Per-model view for the controller's autoscaler tick."""
        return {
            "models": self.loaded_models(),
            "resident": self._models.models(),
            "queues": {m: self.model_queue_len(m)
                       for m in self._models.models()},
            "loads": self._models.load_count,
            "evictions": self._models.eviction_count,
            "retiring": len(self._retiring),
            "draining": self._draining,
        }

    async def __call__(self, request) -> Dict[str, Any]:
        # handle-call payloads arrive as dicts; HTTP POSTs arrive as
        # http_proxy.Request objects (same duality stream_request handles)
        if not isinstance(request, dict):
            request = request.json()
        prompt = list(request["prompt"])
        from ray_tpu.serve.multiplex import get_multiplexed_model_id
        model = str(request.get("model") or get_multiplexed_model_id() or "")
        try:
            if self._draining:
                raise LLMQueueFull("replica draining; retry elsewhere")
            if model and model in self._unpublished:
                raise LLMQueueFull(f"model {model!r} draining on this "
                                   "replica; retry elsewhere")
            eng = await self._engine_for(model)
            req = eng.submit(prompt,
                             int(request.get("max_new_tokens", 32)),
                             float(request.get("temperature", 0.0)))
        except LLMQueueFull as e:
            from ray_tpu.serve.http_proxy import Response

            return Response({"error": str(e)}, status_code=429,
                            headers={"Retry-After": "1"})
        except Exception as e:
            from ray_tpu.serve.http_proxy import Response

            return Response({"error": f"model load failed: {e}"},
                            status_code=500)
        self._wake.set()
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, req.done_event.wait)
        if req.error:
            from ray_tpu.serve.http_proxy import Response

            return Response({"error": req.error},
                            status_code=req.error_status)
        ttft = (req.first_token_time - req.submit_time
                if req.first_token_time else None)
        return {"tokens": req.generated, "ttft_s": ttft}

    async def stream_request(self, request) -> Any:
        """Token-streaming endpoint (the proxy's streaming contract; ref:
        serve response streaming): yields each newly generated token batch
        as soon as the decode loop lands it, finishing with a stats line.
        `request` is an http_proxy.Request (?stream=1) or a plain dict
        (handle calls)."""
        body = request if isinstance(request, dict) else request.json()
        from ray_tpu.serve.multiplex import get_multiplexed_model_id
        model = str(body.get("model") or get_multiplexed_model_id() or "")
        try:
            if self._draining:
                raise LLMQueueFull("replica draining; retry elsewhere")
            if model and model in self._unpublished:
                raise LLMQueueFull(f"model {model!r} draining on this "
                                   "replica; retry elsewhere")
            eng = await self._engine_for(model)
            req = eng.submit(list(body["prompt"]),
                             int(body.get("max_new_tokens", 32)),
                             float(body.get("temperature", 0.0)))
        except LLMQueueFull as e:
            # streaming contract has no status line mid-stream: shed as a
            # typed first frame so clients can back off like on the 429
            yield {"error": str(e), "status": 429, "done": True}
            return
        except Exception as e:
            # model load failed: typed 503 first frame — the router
            # avoids this replica and retries the stream elsewhere
            yield {"error": f"model load failed: {e}", "status": 503,
                   "done": True}
            return
        self._wake.set()
        loop = asyncio.get_running_loop()
        # stream-progress beacon (shared across this replica's streams):
        # ticked per yielded frame, armed while any stream is waiting on
        # the decode loop — no frames across the deadline = stall
        from ray_tpu.observability import health as _health
        sbeacon = _health.beacon("serve:stream", deadline_s=60.0)
        if not sbeacon.busy:
            sbeacon.arm(streaming=True)
        cursor = 0
        while True:
            new = req.generated[cursor:]
            if new:
                cursor += len(new)
                sbeacon.tick()
                yield {"tokens": new}
            elif req.done_event.is_set():
                # done was observed AFTER an empty snapshot; tokens may
                # have landed between the two — drain once more
                new = req.generated[cursor:]
                if new:
                    cursor += len(new)
                    yield {"tokens": new}
                break
            else:
                req.progress.clear()
                if len(req.generated) > cursor or req.done_event.is_set():
                    continue   # progress raced the clear
                await loop.run_in_executor(None, req.progress.wait, 1.0)
        ttft = (req.first_token_time - req.submit_time
                if req.first_token_time else None)
        sbeacon.tick()
        sbeacon.disarm()
        out = {"done": True, "n_tokens": cursor, "ttft_s": ttft}
        if req.error:
            out["error"] = req.error
            out["status"] = req.error_status
        yield out

    # ---- disaggregated serving (serve/disagg.py) ---------------------------

    def _ensure_transfer(self):
        """Lazily build the kv_transfer plumbing — both ends need the
        in-actor runtime (zero-copy put/get + gcs_call)."""
        from ray_tpu.serve.kv_transfer import (HandoffAdopter,
                                               HandoffExporter,
                                               PrefixDirectory)
        if self._adopter is None:
            self._adopter = HandoffAdopter()
        if self._exporter is None and self.mode == "prefill":
            import uuid
            directory = PrefixDirectory() if self.use_directory else None
            self._exporter = HandoffExporter(
                owner=f"llm-{uuid.uuid4().hex[:12]}",
                page_tokens=self.engine.pool.page_size,
                group_pages=self.group_pages,
                retained_groups=self.retained_groups,
                directory=directory)

    async def prefill_request(self, body: Dict[str, Any]) -> Dict[str, Any]:
        """mode="prefill": fill the prompt's KV pages (one generated
        token's worth of engine work — prefill + registration; the token
        is discarded, decode regenerates it bitwise-identically at
        temperature 0), export the leading full page GROUPS through the
        zero-copy store, and return the handoff envelope."""
        assert self.mode == "prefill", self.mode
        self._ensure_transfer()
        body = body if isinstance(body, dict) else body.json()
        prompt = list(body["prompt"])
        res = await self.__call__({"prompt": prompt, "max_new_tokens": 1,
                                   "temperature": 0.0})
        if not isinstance(res, dict):   # Response: shed or engine error
            status = getattr(res, "status_code", 500)
            return {"error": (res.body or {}).get("error", "prefill failed"),
                    "status": status}
        from ray_tpu.serve.paged_kv import page_chain_hashes
        eng = self.engine
        ps = eng.pool.page_size
        per_page = page_chain_hashes(prompt, ps)
        with eng.lock:
            cached = eng.pool.match_prefix(per_page)
        # export only groups whose every page is registered (admission
        # keeps >=1 tail token un-paged, so the final partial group
        # never exports — the decode side tail-prefills it)
        n_groups = len(cached) // self.group_pages
        export_tokens = prompt[:n_groups * self.group_pages * ps]

        def payload_for_group(s: int, e: int) -> dict:
            p0, p1 = s // ps, e // ps
            with eng.lock:
                pages = eng.pool.match_prefix(per_page[:p1])[p0:p1]
                if len(pages) != p1 - p0:
                    raise RuntimeError("page group evicted before export")
                k, v = eng.export_kv_pages(pages)
            return {"k": k, "v": v, "page_hashes": per_page[p0:p1]}

        # store puts + directory registration are blocking runtime calls
        # — banned on the event-loop thread (raylint blocking-in-async)
        envelope = await asyncio.to_thread(
            self._exporter.export,
            export_tokens, payload_for_group,
            lambda p: int(p["k"].nbytes) + int(p["v"].nbytes),
            prompt_len=len(prompt))
        return {"envelope": envelope,
                "matched_tokens": len(export_tokens)}

    def ack_handoff(self, handoff_id: str) -> bool:
        if self._exporter is None:
            return False
        return self._exporter.ack(handoff_id)

    async def adopt_decode(self, envelope: Dict[str, Any], body) -> Any:
        """mode="decode": map the envelope's page groups in from the
        store (engine.import_kv_pages — registered + evictable, no
        prefill compute), then serve the request through the normal
        streaming path: admission's _try_admit_cached adopts the
        imported pages and only the un-paged tail prefills."""
        assert self.mode == "decode", self.mode
        self._ensure_transfer()
        try:
            # blocking zero-copy gets: executor thread, not the loop
            payloads = await asyncio.to_thread(self._adopter.adopt, envelope)
            for payload in payloads:
                self.engine.import_kv_pages(payload["page_hashes"],
                                            payload["k"], payload["v"])
        except Exception:
            # exporter (or its store) died before we mapped the pages
            # in: tell the router to re-prefill on a survivor
            yield {"handoff_lost": True, "done": True}
            return
        async for frame in self.stream_request(body):
            if isinstance(frame, dict) and frame.get("done") \
                    and "handoff_id" not in frame and not frame.get("error"):
                frame = dict(frame)
                frame["handoff_id"] = envelope.get("handoff_id")
            yield frame

    def queue_len(self) -> int:
        """Engine-side backlog: requests queued for admission plus slots
        mid-generation. The serve Replica adds this to its own RPC
        in-flight count, so the controller's autoscaler and the LLM
        router's pressure score both see work the engine has ACCEPTED
        but not finished — not just the RPCs currently parked in
        stream_request. Multiplexed replicas sum across every engine
        (default + per-model + retiring)."""
        total = 0
        for eng in self._engines():
            with eng.lock:
                total += (len(eng.pending)
                          + sum(1 for s in eng.slots if s is not None))
        return total

    def drain(self) -> None:
        """Stop accepting new work; in-flight generations run to
        completion. New submissions shed with LLMQueueFull, which the
        LLM router reads as 'route elsewhere' — the scale-down protocol
        (ServeController._drain_then_kill) then polls queue_len() to 0
        before killing the actor."""
        self._draining = True
        if self._exporter is not None:
            # unpin retained + in-flight page groups and withdraw our
            # global-directory entries before the controller kills us
            self._exporter.close()

    def device_report(self) -> Dict[str, Any]:
        return self.engine.device_report()

    def stats(self) -> Dict[str, Any]:
        m = dict(self.engine.metrics)
        with self.engine.lock:
            m["pending"] = len(self.engine.pending)
            m["active_slots"] = sum(
                1 for s in self.engine.slots if s is not None)
            m["max_slots"] = self.engine.max_slots
        m["draining"] = self._draining
        m["mode"] = self.mode
        if self.multiplexed:
            # advertised set + per-model backlog: the router folds these
            # into its stats map (warm-replica routing) and report_load
            # (per-model autoscaling)
            m["models"] = self.loaded_models()
            m["model_queue"] = {mm: self.model_queue_len(mm)
                                for mm in self._models.models()}
            m["model_loads"] = self._models.load_count
            m["model_evictions"] = self._models.eviction_count
        if self._exporter is not None:
            m.update({f"handoff_{k}": v
                      for k, v in self._exporter.stats().items()})
        if self._adopter is not None:
            m.update({f"adopt_{k}": v
                      for k, v in self._adopter.stats().items()})
        if m["ttft_count"]:
            m["mean_ttft_s"] = m["ttft_sum"] / m["ttft_count"]
        # process-wide: programs built (none once every shape is warm)
        # and times this process stood still (observability/health.py)
        m["compiles"] = _compile_cache.compile_count()
        m["compile_s"] = _compile_cache.compile_seconds()
        m["host_freezes"] = _health.counters()["host_freezes"]
        if getattr(self.engine, "pool", None) is not None:
            m["prefix_cache"] = self.engine.pool.cache_stats()
        return m
