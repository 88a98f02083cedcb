"""TPU-resident mesh-sharded decode replica.

The serving capability target (SURVEY §7 step 9): a deployment whose
weights LIVE on the device mesh across requests, with a jitted,
NamedSharding-annotated decode step driven by the continuous-batching
engine (continuous.py) — the SNIPPETS [1]/[3] pattern: build a logical
device mesh with named axes, annotate tensors with
``NamedSharding(mesh, PartitionSpec(...))``, and let ``jax.jit`` insert
the collectives.  On a single CPU device the mesh degrades to ``(1,)``
and everything still runs — which is how the test tree exercises it.

Decode state is DEVICE-RESIDENT: the ``(MAX_BATCH, embed)`` hidden
matrix never round-trips the host between steps — each jitted step
consumes the previous step's output array directly.  The host touches
the device exactly twice per iteration, both overlapped with compute:

1. Joining requests' initial hidden vectors go up as a masked
   ``(MAX_BATCH, embed)`` update issued BEFORE the previous step's
   tokens are forced — the host→device copy for step *t+1*'s joiners is
   double-buffered against running step *t* (jax dispatch is async).
2. The PREVIOUS step's token vector is forced (device→host) to retire
   finished requests; the step just dispatched keeps the device busy
   behind it.

Because tokens are forced one step late, a request finishes one batcher
step after its last token was computed — the classic pipeline-latency
trade for keeping the device hot.  A retiring request's row may
additionally run one speculative step; the overshoot is dropped at
retire time.

Weights are integer-valued float32 (drawn once from ``seed``, rounded):
every matmul below float32's 2^24 integer window is EXACT, so the
decoded chains are bit-independent of BLAS/XLA reduction order and the
test tree can pin them against a plain host-side reference loop.

PAGED DECODE MODE (``paged_kv`` knob; reference: vLLM PagedAttention
SOSP'23 + Leviathan et al. ICML'23): per-request decode state moves
from a dense ``(MAX_BATCH, embed)`` row reservation into a pool of
fixed-size KV blocks (``kv_cache.PagedKVEngine``) — admission is then
bounded by blocks (tokens actually resident), not slots, and the
batcher packs skewed-length batches.  Each position's value row is the
emitted token's embedding; every step reads the live requests' LAST
rows back THROUGH the paged cache with the ``ops.paged_attention``
pallas kernel (``window=1`` — softmax over one position is exactly 1.0,
so the gather is bitwise) and advances each chain with the same
integer-exact ``x @ W`` argmax the dense path uses: greedy chains stay
bitwise-identical to ``reference_decode``.  On top of it:

- Shared-prefix reuse (``prefix_caching``): prompt token lists are
  prefilled once; block chains are registered per prompt-prefix hash
  and later requests map the SAME physical blocks (copy-on-write on
  first divergence inside a shared partial block).
- Speculative decoding (``speculative_k=k``): a draft model (a
  perturbed integer copy of the projection — cheap, mostly-agreeing)
  proposes k tokens per step host-side; the target verifies all of
  them in ONE batched forward and the accepted prefix plus the
  correction token retire together — multiple tokens per replica step,
  bitwise-unchanged greedy output because acceptance is exact-match.

DISAGGREGATED SERVING (``disaggregated_serving`` knob; reference:
DistServe OSDI'24 / Splitwise ISCA'24): the same class serves both
halves of a split tier.  A PREFILL replica admits requests tagged
``_prefill_only`` — prompt blocks are written, the chain registered,
and the slot finishes the SAME step with a pinned ``ChainExport``
(max_new = 0: prefill replicas never run decode phases).
``prefill_export`` then lays the chain out as a segment image (pages +
block table metadata) and streams it into the decode replica's node
store over the ``reserve_put``/``put_range``/``commit_put`` verbs.  A
DECODE replica (``disagg_generate``) adopts the streamed chain: the
join path writes the imported PAGE ROWS (not recomputed embeddings)
into normally-admitted blocks, so ownership/CoW/prefix-registration
rules apply unchanged and the decoded chain stays bitwise-identical to
the monolithic engine.  With the knob off nothing here runs — the
monolithic paths above are byte-identical and every chain counter
stays zero.

Request format: ``{"prompt": int | [int, ...], "tokens": int}`` → list
of ``tokens`` greedily decoded token ids (the dense path takes the
``int`` form only; decode continues from the LAST prompt token).
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional

from ray_tpu.serve.batching import batch

MAX_BATCH = 8


class MeshShardedDecoder:
    """Deployment-ready greedy decoder with mesh-resident weights."""

    def __init__(self, embed: int = 32, vocab: int = 64, seed: int = 0,
                 paged: Optional[bool] = None, kv_blocks: int = 32,
                 kv_block_size: int = 8, max_slots: int = 16,
                 speculative_k: Optional[int] = None,
                 prefix_caching: Optional[bool] = None):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        self._np = np
        self._jax = jax
        devs = np.asarray(jax.devices())
        n = len(devs)
        # Logical 1-D "model" mesh over every visible device; the vocab
        # (output) dimension shards across it.
        self._mesh = Mesh(devs.reshape(-1), ("model",))
        vocab = ((vocab + n - 1) // n) * n  # divisible over the axis
        kw, ke = jax.random.split(jax.random.PRNGKey(seed))
        w = jnp.round(jax.random.normal(kw, (embed, vocab)) * 4.0)
        emb = jnp.round(jax.random.normal(ke, (vocab, embed)) * 4.0)
        # RESIDENT across requests: the projection is sharded over the
        # model axis, the embedding table replicated (it is read by
        # token id — gather-heavy, cheap to mirror).
        self._w = jax.device_put(
            w.astype(jnp.float32),
            NamedSharding(self._mesh, P(None, "model")))
        self._emb = jax.device_put(
            emb.astype(jnp.float32), NamedSharding(self._mesh, P()))
        self._in_sharding = NamedSharding(self._mesh, P())
        # Host mirrors for slot-state init and the reference loop.
        self._w_host = np.asarray(self._w)
        self._emb_host = np.asarray(self._emb)
        self._embed = embed
        self._vocab = vocab

        @jax.jit
        def step(w, emb_t, x, join_x, join_mask):
            # Joining rows overwrite their hidden state; x is otherwise
            # the previous step's device output.  Logits shard over
            # "model" via w's sharding — the compiler inserts the
            # gather for the argmax reduction.
            x = jnp.where(join_mask, join_x, x)
            logits = x @ w
            tok = jnp.argmax(logits, axis=-1)
            nxt = emb_t[tok]
            return tok, nxt

        self._step = step
        # Device-resident hidden states, one row per batch slot.
        self._dev_x = jax.device_put(
            np.zeros((MAX_BATCH, embed), np.float32), self._in_sharding)
        # row -> owning Slot (host-side occupancy map).
        self._rows: List[Optional[Any]] = [None] * MAX_BATCH
        # Last dispatched step: (token device array, [(row, slot)]).
        self._pending = None

        # -- paged decode mode (serving memory plane) ------------------
        from ray_tpu._private.config import GLOBAL_CONFIG as _CFG

        self._paged = _CFG.paged_kv if paged is None else paged
        self._spec_k = max(0, (_CFG.speculative_k if speculative_k is None
                               else speculative_k))
        # Disaggregated-serving bookkeeping: the pool tag the controller
        # assigned, a cached ingest descriptor, and handoff fallback
        # counters.  The lock is a documented LEAF (pinned in
        # tests/test_lockcheck.py): it guards only these dict/attr
        # mutations and never wraps an out-call.
        self._serve_role: Optional[str] = None
        self._ingest_info: Optional[Dict[str, Any]] = None
        self._chain_stats = {"inline_fallbacks": 0, "handoff_retries": 0}
        self._chain_lock = threading.Lock()  # lock-order: leaf
        if self._paged:
            from ray_tpu.serve.kv_cache import PagedKVEngine

            self._kv_engine = PagedKVEngine(
                kv_blocks, kv_block_size, tokens_for=self._tokens_for,
                prefix_caching=(_CFG.prefix_caching if prefix_caching
                                is None else prefix_caching),
                max_slots=max_slots)
            # The batching decorator picks this attribute up and wires
            # block-gated admission into the continuous batcher.
            self.serve_kv_engine = self._kv_engine
            # Device-resident paged value cache: one head-major (1,
            # block_size, embed) page per block (ops.paged_attention's
            # layout), replicated over the mesh (read by position —
            # gather-heavy, like the embedding table).
            self._kv_cache = jax.device_put(
                np.zeros((kv_blocks, 1, kv_block_size, embed),
                         np.float32), self._in_sharding)
            # Draft model: a perturbed integer copy of the projection —
            # mostly agrees with the target (that is the whole game of
            # speculative decoding), still integer-exact.
            kd = jax.random.PRNGKey(seed + 1)
            self._wd_host = np.asarray(
                self._w_host
                + np.asarray(jnp.round(
                    jax.random.normal(kd, self._w_host.shape) * 0.7)),
                np.float32)

    # -- paged-mode helpers -------------------------------------------------
    def _tokens_for(self, request) -> Any:
        """Admission sizing hook: (prompt token tuple, max new tokens).
        Prefill-only requests (disaggregated handoff) reserve ZERO
        decode tokens — their slot finishes at the end of its own join
        step."""
        body = request or {}
        prompt = body.get("prompt", 0)
        if isinstance(prompt, (list, tuple)):
            ids = tuple(int(t) % self._vocab for t in prompt) or (0,)
        else:
            ids = (int(prompt) % self._vocab,)
        if body.get("_prefill_only"):
            return ids, 0
        return ids, max(1, int(body.get("tokens", 1)))

    # -- continuous decode step (called by the batching engine) ------------
    def _force_pending(self):
        """Force the previously dispatched step's tokens (device→host),
        append them to their slots and finish slots that reached their
        requested length."""
        np = self._np
        if self._pending is None:
            return
        tok_dev, rows = self._pending
        self._pending = None
        tok = np.asarray(tok_dev)
        for r, slot in rows:
            if slot.finished:
                continue  # speculative overshoot for a retired slot
            st = slot.state
            st["out"].append(int(tok[r]))
            if len(st["out"]) >= st["need"]:
                slot.finish(list(st["out"][:st["need"]]))

    # -- paged decode step --------------------------------------------------
    def _apply_cache_writes(self, cow_pairs, blocks, offs, vals):
        """Device updates for one phase: copy-on-write block copies
        FIRST (they must preserve shared content before private writes
        land), then one scatter of the new value rows."""
        import jax.numpy as jnp
        np = self._np
        if cow_pairs:
            olds = jnp.asarray([o for o, _ in cow_pairs], jnp.int32)
            news = jnp.asarray([n for _, n in cow_pairs], jnp.int32)
            self._kv_cache = self._kv_cache.at[news].set(
                self._kv_cache[olds])
        if blocks:
            self._kv_cache = self._kv_cache.at[
                jnp.asarray(blocks, jnp.int32), 0,
                jnp.asarray(offs, jnp.int32)].set(
                    jnp.asarray(np.stack(vals)))

    def _read_last(self, live):
        """Gather every live request's LAST value row back through the
        paged cache — the ops.paged_attention block-table data path.
        ``window=1`` makes the softmax exactly 1.0, so the result is
        bitwise the stored row (= emb[last token])."""
        import jax.numpy as jnp

        from ray_tpu.ops.paged_attention import paged_attention
        np = self._np
        eng = self._kv_engine
        tables = [eng.block_table(s) for s in live]
        width = max(len(t) for t in tables)
        bt = np.zeros((len(live), width), np.int32)
        for i, t in enumerate(tables):
            bt[i, : len(t)] = t
        cl = np.asarray([s.state["pos"] for s in live], np.int32)
        q = np.zeros((len(live), 1, self._embed), np.float32)
        out = paged_attention(jnp.asarray(q), self._kv_cache, self._kv_cache,
                              jnp.asarray(bt), jnp.asarray(cl), window=1)
        return np.asarray(out)[:, 0, :]

    def _paged_step(self, slots):
        """One iteration of the paged engine: prefill joiners into their
        blocks (skipping shared-prefix positions), read last rows via
        the paged kernel, draft + verify ``spec_k`` tokens in one
        batched forward, and retire the accepted prefix."""
        import jax.numpy as jnp
        np = self._np
        eng = self._kv_engine
        k = self._spec_k
        # Phase 1: join + prefill.  Positions [0, n_cached) are mapped
        # from the prefix cache and never rewritten; the rest of the
        # prompt scatters into this request's (fresh or CoW'd) blocks.
        cow, wb, wo, wv = [], [], [], []
        joiners = []
        for s in slots:
            if s.state is not None:
                continue
            kvp = s.kv
            body = s.request or {}
            imp = body.get("_import")
            s.state = {"pos": len(kvp.prompt), "out": [],
                       "need": kvp.max_new,
                       "last": (int(imp["last"]) if imp is not None
                                else kvp.prompt[-1])}
            lo = kvp.n_cached
            if lo < len(kvp.prompt):
                writes, cw = eng.plan_writes(s, lo, len(kvp.prompt) - lo)
                cow += cw
                if imp is not None:
                    # Streamed-chain adoption: value rows come from the
                    # prefill replica's exported PAGES, not recomputed
                    # embeddings — the handoff genuinely rides the data
                    # plane (bitwise-identical here because each page
                    # row IS the token's embedding row).
                    pages, sbs = imp["pages"], int(imp["src_bs"])
                    for (blk, off), p in zip(
                            writes, range(lo, len(kvp.prompt))):
                        wb.append(blk)
                        wo.append(off)
                        wv.append(pages[p // sbs, 0, p % sbs])
                    eng.note_chain_imported()
                else:
                    for (blk, off), tok in zip(writes, kvp.prompt[lo:]):
                        wb.append(blk)
                        wo.append(off)
                        wv.append(self._emb_host[tok])
            joiners.append(s)
        self._apply_cache_writes(cow, wb, wo, wv)
        for s in joiners:
            # Publish AFTER the prefill scatter: a prefix-cache entry
            # must never alias unwritten blocks.
            eng.register_prefix(s)
            if (s.request or {}).get("_prefill_only") and not s.finished:
                # Prefill-only slots finish NOW with their chain pinned
                # for streaming: they never reach the decode phases, so
                # a prefill replica runs prompt-only steps.
                s.finish(eng.export_chain(s))
        live = [s for s in slots if not s.finished]
        if not live:
            return
        # Phase 2: last rows through the paged cache (bitwise gather).
        last = self._read_last(live)                       # (B, embed)
        # Phase 3: draft k tokens per request (host, integer-exact),
        # then verify ALL of them in ONE batched target forward:
        # position j's logits come from token j-1's value row, so row 0
        # is the cache-gathered last row and rows 1..k are the drafts'
        # embeddings.
        drafts = []
        for s in live:
            t = s.state["last"]
            chain = []
            for _ in range(k):
                t = int(np.argmax(self._emb_host[t] @ self._wd_host))
                chain.append(t)
            drafts.append(chain)
        verify = np.empty((len(live), k + 1, self._embed), np.float32)
        verify[:, 0, :] = last
        for i, chain in enumerate(drafts):
            for j, t in enumerate(chain):
                verify[i, j + 1] = self._emb_host[t]
        logits = jnp.asarray(verify) @ self._w     # sharded over "model"
        target = np.asarray(jnp.argmax(logits, axis=-1))   # (B, k+1)
        # Phase 4: exact-match acceptance — emitted tokens are the
        # matching draft prefix plus the target's correction token,
        # which is by construction the plain greedy chain.
        cow, wb, wo, wv = [], [], [], []
        for i, s in enumerate(live):
            st = s.state
            room = st["need"] - len(st["out"])
            usable = min(k, room - 1)
            m = 0
            while m < usable and drafts[i][m] == int(target[i, m]):
                m += 1
            emit = drafts[i][:m] + [int(target[i, m])]
            if k:
                eng.note_spec(usable, m)
            writes, cw = eng.plan_writes(s, st["pos"], len(emit))
            cow += cw
            for (blk, off), tok in zip(writes, emit):
                wb.append(blk)
                wo.append(off)
                wv.append(self._emb_host[tok])
            st["out"] += emit
            st["pos"] += len(emit)
            st["last"] = emit[-1]
            eng.note_tokens(len(emit))
            if len(st["out"]) >= st["need"]:
                s.finish(list(st["out"][: st["need"]]))
        self._apply_cache_writes(cow, wb, wo, wv)

    @batch(mode="continuous", max_batch_size=MAX_BATCH,
           batch_wait_timeout_s=0.002)
    def _decode(self, slots):
        # Paged dispatch requires the batcher to have wired the engine
        # (slots then carry SlotKV plans): with the paged_kv knob off
        # the batcher ignores serve_kv_engine and admission is dense, so
        # a paged=True instance must fall back to the dense path too.
        if self._paged and slots and slots[0].kv is not None:
            return self._paged_step(slots)
        jax, np = self._jax, self._np
        # Retired slots free their rows at the boundary (their final
        # token was forced LAST step; the batcher has already refilled
        # the batch, so freed rows and joiners line up).
        for r, s in enumerate(self._rows):
            if s is not None and s.finished:
                self._rows[r] = None
        join_x = np.zeros((MAX_BATCH, self._embed), np.float32)
        join_mask = np.zeros((MAX_BATCH, 1), np.bool_)
        for s in slots:
            if s.state is None:
                body = s.request or {}
                prompt = body.get("prompt", 0)
                if isinstance(prompt, (list, tuple)):
                    # Token-list form: dense decode continues from the
                    # LAST prompt token (reference_decode semantics).
                    prompt = prompt[-1] if prompt else 0
                prompt = int(prompt) % self._vocab
                s.state = {"row": None, "out": [],
                           "need": max(1, int(body.get("tokens", 1))),
                           "prompt": prompt}
            if s.state["row"] is None:
                r = self._rows.index(None)  # capacity == max_batch_size
                self._rows[r] = s
                s.state["row"] = r
                join_x[r] = self._emb_host[s.state["prompt"]]
                join_mask[r] = True
        # 1. Joiners' hidden states → device (ASYNC h2d, overlapping
        #    the still-running previous step).
        dev_join = jax.device_put(join_x, self._in_sharding)
        dev_mask = jax.device_put(join_mask, self._in_sharding)
        # 2. Previous step's tokens (its compute ran behind us).
        self._force_pending()
        # 3. Dispatch this step (async); forced on the NEXT call.
        live = [(r, s) for r, s in enumerate(self._rows)
                if s is not None and not s.finished]
        if live:
            tok, self._dev_x = self._step(
                self._w, self._emb, self._dev_x, dev_join, dev_mask)
            self._pending = (tok, live)

    def __call__(self, body: Dict[str, Any]) -> List[int]:
        return self._decode(body)

    # -- disaggregated serving (prefill/decode pool split) ------------------
    def set_serve_role(self, role: Optional[str]) -> None:
        """Pool tag from the controller (``ReplicaWrapper`` calls this
        at replica construction): ``"prefill"`` / ``"decode"`` / None
        (monolithic)."""
        self._serve_role = role

    def kv_ingest_info(self) -> Optional[Dict[str, Any]]:
        """Where prefill replicas should stream chains for THIS
        replica: the node store id (the pusher resolves address +
        capabilities itself).  None outside a runtime (plain-process
        tests) — the handoff then degrades to inline descriptors."""
        with self._chain_lock:
            if self._ingest_info is not None:
                return dict(self._ingest_info)
        try:
            from ray_tpu._private import api_internal

            rt = api_internal.require_runtime()
            info = {"store": rt.store_id}
        except Exception:
            return None
        with self._chain_lock:
            self._ingest_info = info
            return dict(info)

    def kv_debug(self) -> Dict[str, Any]:
        """Allocator + handoff gauges for tests (the chaos suite's
        leak assertions): live block count, unreleased exports, and the
        fallback/retry bookkeeping."""
        with self._chain_lock:
            chain = dict(self._chain_stats)
        eng = getattr(self, "_kv_engine", None)
        if eng is None:
            return {"paged": False, "role": self._serve_role,
                    "chain": chain}
        with eng._guard:
            st = eng.stats_locked()
        st.update({"paged": True, "role": self._serve_role,
                   "used": eng.allocator.used,
                   "available": eng.allocator.available,
                   "exports_outstanding": eng.exports_outstanding,
                   "chain": chain})
        return st

    def prefill_export(self, body: Dict[str, Any],
                       ingest: Optional[Dict[str, Any]] = None) -> tuple:
        """Prompt-only admission of ``body`` on THIS (prefill) replica,
        then the chain handoff: block pages + table metadata laid out
        as one segment image and streamed into ``ingest``'s node store
        over the put verbs (``reserve_put`` → ``put_range``* →
        ``commit_put``), falling back to an inline descriptor when no
        data plane is reachable.  Returns ``(block_chain_descr,
        sampler_state)``."""
        import jax.numpy as jnp

        from ray_tpu.serve.kv_cache import ChainExport

        np = self._np
        if not self._paged:
            raise RuntimeError(
                "disaggregated prefill requires the paged KV engine "
                "(paged_kv knob)")
        exp = self._decode({**(body or {}), "_prefill_only": True})
        if not isinstance(exp, ChainExport):
            raise RuntimeError(
                f"prefill produced no chain (got {type(exp).__name__}: "
                "paged admission not wired?)")
        eng = self._kv_engine
        try:
            pages = np.asarray(
                self._kv_cache[jnp.asarray(exp.blocks, jnp.int32)])
            sampler = {"last": int(exp.prompt[-1]),
                       "pos": len(exp.prompt)}
            payload = {"src_bs": eng.block_size,
                       "n_tokens": len(exp.prompt),
                       "pages": pages, **sampler}
            descr = self._stream_chain(payload, ingest)
            if descr[0] == "inline":
                with self._chain_lock:
                    self._chain_stats["inline_fallbacks"] += 1
            else:
                eng.note_chain_streamed(int(descr[2]))
            return descr, sampler
        finally:
            eng.release_export(exp)

    def _stream_chain(self, payload: Dict[str, Any],
                      ingest: Optional[Dict[str, Any]]) -> tuple:
        """Land one chain image in the ingest store.  Returns the
        descriptor ``_open_chain`` consumes: ``(kind, ident, total)``
        for a committed segment in the DECODE replica's node store
        (kind ``"shm"``/``"spilled"``), or ``("inline", payload)`` when
        no put path is reachable (no runtime, or a peer without the put
        verbs) — mirrors the shuffle pusher's hedge shape."""
        store = (ingest or {}).get("store")
        rt = None
        if store:
            try:
                from ray_tpu._private import api_internal

                rt = api_internal.require_runtime()
            except Exception:
                rt = None
        if rt is None:
            return ("inline", payload)
        from ray_tpu._private import object_transfer, serialization
        from ray_tpu._private import shm_store as shm_mod
        from ray_tpu._private.config import GLOBAL_CONFIG as _CFG
        from ray_tpu._private.ids import ObjectID

        res = serialization.dumps_adaptive(payload, 0)  # parts form
        meta, bufs = res[1], res[2]
        oid_bin = ObjectID.for_put().binary()
        try:
            if store != rt.store_id:
                ent = rt.resolve_store_addr(store)
                if ent is None or \
                        not object_transfer.peer_accepts_puts(ent[1]):
                    return ("inline", payload)
                kind, ident, total = rt._pusher.push(
                    store, ent[0], oid_bin, meta, bufs, caps=ent[1],
                    stripe_threshold=_CFG.kv_stream_stripe_threshold)
            else:
                kind, ident, total = shm_mod.put_local(
                    rt.shm, oid_bin, meta, bufs)
        except Exception:
            if store != rt.store_id:
                rt.forget_store_addr(store)
            return ("inline", payload)
        return (kind, ident, total)

    def _open_chain(self, descr: tuple) -> Dict[str, Any]:
        """Adopt a streamed chain on THIS (decode) replica: attach the
        committed segment in the local node store, copy the pages out,
        and release the segment (owner-routed free — ``unlink`` returns
        the node byte accounting the pusher's ``reserve_put`` charged).
        Inline descriptors short-cut."""
        np = self._np
        if descr[0] == "inline":
            payload = dict(descr[1])
            payload["pages"] = np.asarray(payload["pages"])
            return payload
        kind, ident, total = descr[0], descr[1], int(descr[2])
        from ray_tpu._private import api_internal

        rt = api_internal.require_runtime()
        if kind == "spilled":
            seg = rt.shm.attach_path(ident)
        else:
            seg = rt.shm.attach(ident)
        try:
            payload = dict(seg.deserialize())
            # The deserialized pages view aliases the mapping: copy out
            # before the segment goes away.
            payload["pages"] = np.array(payload["pages"], copy=True)
        finally:
            seg.close()
        if kind == "spilled":
            import os

            try:
                os.unlink(ident)
            except OSError:
                pass
        else:
            rt.shm.unlink(ident, total)
        return payload

    def disagg_generate(self, body: Dict[str, Any], prefill=None,
                        pool: str = "") -> Any:
        """Decode-side orchestration of one disaggregated request:
        prefill on the routed prefill replica, stream the chain HERE,
        adopt it, decode locally.  A dead or failing prefill replica is
        retried against the pool's current membership (fetched from the
        controller) — the chaos re-prefill path; any half-received
        chain on this node was already aborted by the put path's
        connection-close cleanup, so a retry starts clean."""
        import ray_tpu as ray

        ingest = self.kv_ingest_info()
        handoff = None
        last_err: Optional[BaseException] = None
        cands = [prefill] if prefill is not None else []
        for attempt in range(2):
            for actor in cands:
                try:
                    handoff = ray.get(actor.call_method.remote(
                        "prefill_export", (body, ingest), {}))
                    break
                except Exception as e:  # noqa: BLE001 — retried below
                    last_err = e
            if handoff is not None or not pool or attempt:
                break
            # Membership may have changed under us (killed replica):
            # re-fetch the prefill pool and re-prefill on a healthy one.
            try:
                from ray_tpu.serve.api import CONTROLLER_NAME

                ctrl = ray.get_actor(CONTROLLER_NAME)
                _, reps, _ = ray.get(ctrl.handle_snapshot.remote(pool))
                cands = list(reps)
                with self._chain_lock:
                    self._chain_stats["handoff_retries"] += 1
            except Exception as e:  # noqa: BLE001 — surfaced below
                last_err = e
                break
        if handoff is None:
            raise RuntimeError(
                f"disaggregated prefill failed: {last_err!r}")
        descr, _sampler = handoff
        imp = self._open_chain(descr)
        return self._decode({**(body or {}), "_import": imp})

    def device_info(self) -> Dict[str, Any]:
        """The devices THIS replica's process runs on, as JAX reports
        them — what a measurement must name beside its numbers."""
        devs = self._jax.devices()
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs)}

    # -- host-side reference (tests pin numerics against this) -------------
    def reference_decode(self, prompt, tokens: int) -> List[int]:
        """Plain sequential greedy decode on the host — exact-integer
        arithmetic makes it bitwise comparable to the device chain.
        ``prompt`` may be an id or a token list (decode continues from
        the LAST prompt token, matching the paged prefill semantics)."""
        np = self._np
        if isinstance(prompt, (list, tuple)):
            prompt = prompt[-1] if prompt else 0
        x = self._emb_host[int(prompt) % self._vocab]
        out = []
        for _ in range(tokens):
            t = int(np.argmax(x @ self._w_host))
            out.append(t)
            x = self._emb_host[t]
        return out
